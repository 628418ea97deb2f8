"""95th percentile, over every HI request that arrived in the window
(drained after it), of its last token's time less its due arrival
time, in ms (nearest rank), both stamped by ``bench/drive.py``."""
from bench.loadgen import nearest_rank


def read(run):
    w = run.window
    if not w.hi:
        return None
    inf = float("inf")
    return nearest_rank([(w.done_at.get(r.rid, inf) - w.due[r.rid]) * 1e3
                         for r in w.hi], 0.95)
