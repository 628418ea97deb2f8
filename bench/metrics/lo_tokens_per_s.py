"""LO output tokens emitted inside the window over the window's
seconds."""


def read(run):
    w = run.window
    return w.lo_tokens / w.window_s if w.window_s > 0 else None
