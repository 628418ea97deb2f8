"""The metrics that read the program's own spans (``Request.steps``,
``Request.blocked_s``): reported on the small CPU cell, computed from
hand-made records as their docstrings say, and left out, without
an error, where the program keeps no such records.  Also the idle
split of ``tools/span_split.py``, on that run and on hand-made
intervals."""
import types

import pytest

import benchkit
from bench import spec
from bench.drive import Window
from repro.core.serving import Request, StepRecord
from repro.core.task import Crit
from tools import span_split

NEW = ("sched_us_per_step", "host_ms_per_step", "host_syncs_per_step",
       "decode_wait_ms", "prefill_wait_ms_per_ktok",
       "hi_blocked_by_lo_ms_p95")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    benchkit.use_test_arch(mp)
    try:
        root = benchkit.tiny_root(tmp_path_factory.mktemp("spans"))
        cell = spec.cell(benchkit.TINY_CELL, root)
        yield span_split.measure(cell, 2 ** 33 + 5, 2.0,
                                 dict(benchkit.CPU_DEVICE),
                                 benchkit.CPU_PEAKS, root=root)
    finally:
        mp.undo()


def test_the_tiny_cell_reports_every_span_metric(traced):
    result, out = traced
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(m)
    # two reads a step, one on a step that ends a request by length
    assert 1.0 < m["host_syncs_per_step"] <= 2.0
    assert 0 < m["decode_wait_ms"] < m["decode_step_ms"]
    assert 0 < m["prefill_wait_ms_per_ktok"]
    assert 0 <= m["sched_us_per_step"] < 1e3 * m["host_ms_per_step"]
    assert m["hi_blocked_by_lo_ms_p95"] >= 0
    assert result["correct"]
    assert out["span_metrics"] == pytest.approx({k: m[k] for k in NEW})


def test_the_split_of_a_cpu_trace_covers_the_window(traced):
    # the CPU has no device plane: the whole window is idle, and the
    # split is of host time alone
    sp = traced[1]["idle_split"]
    assert sp["idle_s"] == pytest.approx(sp["window_s"])
    inside, between = sp["inside_step"], sp["between_steps"]
    assert inside["total"] + between["total"] == pytest.approx(sp["idle_s"])
    assert inside["total"] == pytest.approx(sp["span_total_s"]["bench.step"])
    parts = [inside[k] for k in ("mesc.schedule", "mesc.launch",
                                 "mesc.wait", "mesc.finish",
                                 "mesc.step, no child",
                                 "bench.step, outside mesc.step")]
    assert min(parts) >= 0 and sum(parts) == pytest.approx(inside["total"])
    assert inside["mesc.wait"] > 0 and sp["steps"] > 0
    assert set(sp["busy_inside_s"].values()) == {0.0}
    means = traced[1]["step_means_ms"]
    assert means["prefill"]["steps"] > 0 and means["decode"]["steps"] > 0
    assert means["decode"]["mesc.wait"] == pytest.approx(
        traced[0]["metrics"]["decode_wait_ms"]["value"])


def test_split_of_hand_made_intervals():
    # window [0, 100); device busy [10, 20) and [50, 90), so idle 50;
    # two steps, idle 15 in each
    spans = {"bench.step": [(5, 30), (40, 95)],
             "mesc.step": [(6, 29), (41, 94)],
             "mesc.schedule": [(6, 8), (41, 45)],
             "mesc.restore": [(42, 44)],
             "mesc.launch": [(8, 10), (45, 50)],
             "mesc.wait": [(10, 25), (50, 92)],
             "mesc.finish": [(25, 29), (92, 94)],
             "bench.pump": [(31, 33)]}
    sp = span_split.split([(10, 20), (50, 90)], spans, (0, 100))
    ns = {k: v * 1e9 for k, v in sp["inside_step"].items()}
    assert ns == pytest.approx({
        "mesc.schedule": 2 + 4, "mesc.save": 0, "mesc.restore": 2,
        "mesc.launch": 2 + 5, "mesc.wait": 5 + 2, "mesc.finish": 4 + 2,
        "mesc.step, no child": 0, "bench.step, outside mesc.step": 2 + 2,
        "total": 30})
    assert sp["between_steps"]["bench.pump"] * 1e9 == pytest.approx(2)
    assert sp["between_steps"]["total"] * 1e9 == pytest.approx(20)
    assert sp["idle_s"] * 1e9 == pytest.approx(50)


def _rec(kind, tokens, start, syncs=2, cs_s=0.0):
    # schedule 1 ms (cs_s of it a context switch), launch 2 ms,
    # wait 10 ms (prefill 5 ms per token), finish 1 ms
    wait = 0.005 * tokens if kind == "prefill" else 0.010
    return StepRecord(kind, tokens, start, start + 0.001, start + 0.003,
                      start + 0.003 + wait, start + 0.004 + wait, syncs,
                      cs_s)


def _run(window):
    return types.SimpleNamespace(window=window)


def _window():
    w = Window(t0=100.0, window_s=10.0)
    lo = Request(rid=1, prompt=None, max_new_tokens=9, priority=9,
                 crit=Crit.LO)
    lo.steps = [_rec("prefill", 4, 99.0),           # before the window
                _rec("prefill", 2, 100.0, cs_s=0.0005),
                _rec("decode", 0, 101.0),
                _rec("decode", 0, 102.0, syncs=1),
                _rec("decode", 0, 109.995)]         # ends after it
    his = []
    for i, blocked in enumerate([0.0, 0.004, 0.012, 0.002]):
        h = Request(rid=10 + i, prompt=None, max_new_tokens=2, priority=i,
                    crit=Crit.HI)
        h.started_at, h.blocked_s = 101.5 + i, blocked
        his.append(h)
    w.lo, w.hi = [lo], his
    return w


def _read(name, window):
    return spec.reader(name)(_run(window))


def test_span_metrics_from_hand_made_records():
    w = _window()
    # in the window: one prefill of 2 tokens, two decodes
    assert _read("sched_us_per_step", w) == pytest.approx(
        (0.0005 + 0.001 + 0.001) / 3 * 1e6)
    assert _read("host_ms_per_step", w) == pytest.approx(4.0)
    assert _read("host_syncs_per_step", w) == pytest.approx(5 / 3)
    assert _read("decode_wait_ms", w) == pytest.approx(10.0)
    assert _read("prefill_wait_ms_per_ktok", w) == pytest.approx(5000.0)
    # nearest rank: the 4th of 4
    assert _read("hi_blocked_by_lo_ms_p95", w) == pytest.approx(12.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_records_gives_no_number(name):
    # requests of a program that keeps neither steps nor blocked_s
    w = Window(t0=0.0, window_s=1.0)
    w.lo = [types.SimpleNamespace(rid=1, crit=Crit.LO, started_at=0.1)]
    w.hi = [types.SimpleNamespace(rid=2, crit=Crit.HI, started_at=0.2)]
    assert _read(name, w) is None
