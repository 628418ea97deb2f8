"""Model step: mean of the program's ``mesc.wait`` span over in-window
decode steps, in ms (``bench/spans.py``): from the decode program's
launch returning to its token on the host."""
from bench.spans import window_steps


def read(run):
    s = window_steps(run, "decode")
    if not s:
        return None
    return sum(x.token - x.returned for x in s) / len(s) * 1e3
