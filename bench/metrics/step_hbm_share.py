"""Whole step against the chip's memory bandwidth: the bytes that every
instruction run in the window must move (``bench/counts.py``), over
the window's seconds times the peak HBM rate, in %."""
from bench import counts


def read(run):
    w = run.window
    steps = [s for s in w.steps if s.in_window]
    if not steps or w.window_s <= 0:
        return None
    moved = sum(counts.hbm_bytes(run.conf, s.kind, s.tokens, s.kv_len)
                for s in steps)
    return 100.0 * moved / (w.window_s * run.peaks["hbm_bytes_per_s"])
