"""Whole step against the chip's peak: the operations that every
instruction run in the window needs (``bench/counts.py``), over the
window's seconds times the peak bf16 rate, in %."""
from bench import counts


def read(run):
    w = run.window
    steps = [s for s in w.steps if s.in_window]
    if not steps or w.window_s <= 0:
        return None
    ops = sum(counts.flops(run.conf, s.kind, s.tokens, s.kv_len)
              for s in steps)
    return 100.0 * ops / (w.window_s * run.peaks["bf16_flops_per_s"])
