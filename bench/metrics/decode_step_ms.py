"""Model step: the window's host time in ``MESCServer.step()`` calls
that ran a decode step (``bench/drive.py``'s spans), over their count, in ms.
Scheduler and host sync included."""


def read(run):
    d = [s.t1 - s.t0 for s in run.window.steps
         if s.in_window and s.kind == "decode"]
    return sum(d) / len(d) * 1e3 if d else None
