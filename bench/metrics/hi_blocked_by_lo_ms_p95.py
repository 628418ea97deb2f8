"""Scheduler: 95th percentile, over the window's HI requests, of the
time after its arrival in which a LO instruction held the lane before
its first dispatch (``Request.blocked_s``, kept by ``MESCServer``), in
ms: the inversion MESC bounds to one instruction, without the wait
behind earlier HI requests."""
from bench.loadgen import nearest_rank


def read(run):
    hi = [r for r in run.window.hi
          if r.started_at is not None and hasattr(r, "blocked_s")]
    if not hi:
        return None
    return nearest_rank([r.blocked_s * 1e3 for r in hi], 0.95)
