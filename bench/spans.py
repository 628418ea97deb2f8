"""The program's own per-step records of a run's window.

``MESCServer.step`` appends one record to ``Request.steps`` of the
request it ran, with five stamps from the server's clock, which
``bench/drive.py`` makes ``time.perf_counter``, the window's clock:
``start``, ``launch``, ``returned``, ``token``, ``end``, delimiting the
spans ``mesc.schedule``, ``mesc.launch``, ``mesc.wait`` and
``mesc.finish`` inside ``mesc.step``; ``cs_s``, the part of
``mesc.schedule`` spent in context saves and restores; and ``syncs``,
its blocking device reads.  A program that keeps no such records gives
``None``, and the metrics that read them are left out.
"""
from __future__ import annotations

from typing import List, Optional


def window_steps(run, kind: Optional[str] = None) -> Optional[List]:
    """The records of the window's requests that lie inside the
    measured window (of ``kind``, if given), or ``None`` where the
    program keeps none."""
    w = run.window
    reqs = w.hi + w.lo
    if not any(hasattr(r, "steps") for r in reqs):
        return None
    t1 = w.t0 + w.window_s
    return [s for r in reqs for s in r.steps
            if w.t0 <= s.start and s.end <= t1
            and (kind is None or s.kind == kind)]
