"""Set-up time: from the process's first statement to the window's
opening (imports, weights, server, compiling or loading every program,
warm-up requests)."""


def read(run):
    return run.setup_s
