"""Scheduler: mean self time of the program's ``mesc.schedule`` span
per in-window step, context saves and restores left out, in us
(``bench/spans.py``): ``_mode_tick``, ``_pick`` and the residency
decisions."""
from bench.spans import window_steps


def read(run):
    s = window_steps(run)
    if not s:
        return None
    return sum(x.launch - x.start - x.cs_s for x in s) / len(s) * 1e6
