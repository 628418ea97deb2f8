"""Device: mean number of blocking device-to-host reads per in-window
step, as the program counts them (``bench/spans.py``)."""
from bench.spans import window_steps


def read(run):
    s = window_steps(run)
    return sum(x.syncs for x in s) / len(s) if s else None
