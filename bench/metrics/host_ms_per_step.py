"""Device: mean host time per in-window step in which the server has
no instruction in flight, in ms (``bench/spans.py``): the program's
``mesc.step`` span less its ``mesc.wait``."""
from bench.spans import window_steps


def read(run):
    s = window_steps(run)
    if not s:
        return None
    return sum((x.end - x.start) - (x.token - x.returned)
               for x in s) / len(s) * 1e3
