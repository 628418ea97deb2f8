"""Mean, over every HI request that arrived in the window (drained
after it), of its last token's time less its due arrival time, in ms,
both stamped by ``bench/drive.py``."""


def read(run):
    w = run.window
    if not w.hi:
        return None
    inf = float("inf")
    return sum(w.done_at.get(r.rid, inf) - w.due[r.rid]
               for r in w.hi) / len(w.hi) * 1e3
