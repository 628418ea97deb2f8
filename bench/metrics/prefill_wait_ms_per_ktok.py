"""Model step: the program's ``mesc.wait`` span over in-window
prefills, per thousand prompt tokens, in ms (``bench/spans.py``)."""
from bench.spans import window_steps


def read(run):
    s = window_steps(run, "prefill")
    toks = sum(x.tokens for x in s or ())
    if not toks:
        return None
    return sum(x.token - x.returned for x in s) / toks * 1e6
