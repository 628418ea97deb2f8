"""Scheduler: 95th percentile, over the window's HI requests, of the
time from a HI request's due arrival (stamped by ``bench/drive.py``) to its
first dispatch (``Request.started_at``, stamped by ``MESCServer``), in
ms: the time it waited behind the instruction running when it came and
behind earlier HI requests."""
from bench.loadgen import nearest_rank


def read(run):
    w = run.window
    hi = [r for r in w.hi if r.started_at is not None]
    if not hi:
        return None
    return nearest_rank([(r.started_at - w.due[r.rid]) * 1e3 for r in hi],
                        0.95)
