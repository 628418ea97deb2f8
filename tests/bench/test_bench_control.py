"""The control of the model check, at a size a test run can hold.

The small CPU cell serves a fixed set of requests through the program
(LO requests, then HI requests that preempt them), untimed, so that
the readings do not depend on the CPU's speed.  The check's number is
read twice on the same sample: from the served tokens, and from the
tokens the reference computed in the precision below the
configuration's puts first.  The control must read above the limit on
every seed, by twice, and sound serving below it.  The same readings at the
cells' own sizes, on the chip (``python3 -m bench.control``), set each
configuration's limit (``PERF.md``).
"""
import numpy as np
import pytest

import benchkit
from bench import check, loadgen, run, spec
from repro.core.serving import Request
from repro.core.task import Crit

SEEDS = (1, 2, 3)

# served in bfloat16 as the cells are, so the control is float8; at
# this size (seeds 1-3, CPU) sound serving reads 0.0027-0.0068 and the
# control 0.020-0.056
BF16 = dict(benchkit.TINY_CONFIG,
            serving=dict(benchkit.TINY_CONFIG["serving"],
                         param_dtype="bfloat16", compute_dtype="bfloat16"),
            correct=dict(benchkit.TINY_CONFIG["correct"],
                         max_logit_gap=0.01))


def _served(cell, seed):
    """Ten LO requests, four HI ones after five steps; all finished."""
    params, srv = run.setup(cell, seed)
    deck = loadgen.Deck(seed, cell.mix["lo"], "LO")
    V = cell.config["vocab_size"]
    for j in range(10):
        s = deck.spec(j)
        srv.submit(Request(rid=100 + j, priority=1000 + j,
                           max_new_tokens=s.max_new_tokens, crit=Crit.LO,
                           prompt=loadgen.prompt_tokens(seed, "LO", j,
                                                        s.prompt_len, V)))
    for _ in range(5):
        srv.step()
    for s in loadgen.hi_arrivals(seed, cell.mix, 2.0)[:4]:
        srv.submit(Request(rid=s.index, priority=s.index, crit=Crit.HI,
                           max_new_tokens=s.max_new_tokens,
                           prompt=loadgen.prompt_tokens(seed, "HI", s.index,
                                                        s.prompt_len, V)))
    srv.run()
    return params, list(srv.requests.values())


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    benchkit.use_test_arch(mp)
    try:
        root = benchkit.tiny_root(tmp_path_factory.mktemp("control"), BF16)
        cell = spec.cell(benchkit.TINY_CELL, root)
        conf = cell.config
        rows = []
        for seed in SEEDS:
            params, reqs = _served(cell, seed)
            assert all(r.done for r in reqs)
            picked = check.sample(seed, reqs, 12, 400)
            rows.append({
                "program": check.model_gap(conf, params, picked),
                "control": check.model_gap(conf, params, picked,
                                           check.control_quant(conf)),
                "tokens": sum(len(r.generated) for r in picked),
                "preempted": sum(r.preemptions > 0 for r in picked)})
        yield rows, conf
    finally:
        mp.undo()


def test_control_fails_where_the_program_passes(readings):
    rows, conf = readings
    limit = conf["correct"]["max_logit_gap"]
    program = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows]
    assert max(program) <= limit < min(ctrl), (program, ctrl)
    assert min(ctrl) >= 2 * limit
    assert all(r["tokens"] >= 100 and r["preempted"] >= 1 for r in rows), rows
    assert np.isfinite(ctrl).all()
