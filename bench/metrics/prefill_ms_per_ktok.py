"""Model step: the window's host time in ``MESCServer.step()`` calls
that ran a prefill (``bench/drive.py``'s spans), per thousand prompt tokens."""


def read(run):
    p = [s for s in run.window.steps if s.in_window and s.kind == "prefill"]
    toks = sum(s.tokens for s in p)
    return sum(s.t1 - s.t0 for s in p) / toks * 1e6 if toks else None
