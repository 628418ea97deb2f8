"""The result line: its keys, the metrics each mode reports, the checks
last; and no result at all without a TPU."""
import json

import pytest

import benchkit
from bench import run, spec


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    benchkit.use_test_arch(mp)
    try:
        root = benchkit.tiny_root(tmp_path_factory.mktemp("schema"))
        yield root, {t: benchkit.run_tiny(root, seed=2 ** 31 + 9,
                                          trace=t)[0]
                     for t in (False, True)}
    finally:
        mp.undo()


def test_untraced_line_reports_the_end_to_end_metrics(results):
    root, res = results
    r = json.loads(json.dumps(res[False]))
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    cell = spec.cell(benchkit.TINY_CELL, root)
    assert set(r["metrics"]) == {m.name for m in cell.end_to_end}
    for name, m in r["metrics"].items():
        assert m["value"] > 0 and m["unit"], name
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"}
    assert r["diagnostics"]["window_compiles"] == 0


def test_traced_line_reports_the_per_layer_metrics(results):
    # the CPU has no device plane: the trace reduces to nothing, and the
    # metrics read from it are left out, never read from host work
    root, res = results
    r = json.loads(json.dumps(res[True]))
    cell = spec.cell(benchkit.TINY_CELL, root)
    assert set(r["metrics"]) == {m.name for m in cell.per_layer} \
        - {"device_idle_share"}
    assert "busy_s" not in r["device"] and "breakdown" not in r
    for name in ("step_mfu", "step_hbm_share"):
        assert 0 < r["metrics"][name]["value"] <= 100
    assert list(r)[-1] == "checks"


def test_traced_run_without_device_ops_prints_no_result(monkeypatch,
                                                         capsys):
    def fake_run_cell(*a, **k):
        return {"device": {"platform": "tpu"}}, {}
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    monkeypatch.setattr(run, "run_cell", fake_run_cell)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "olmo-1b.hi_over_lo_chat", "--seed", "1",
                  "--seconds", "1", "--trace", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_no_tpu_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "olmo-1b.hi_over_lo_chat", "--seed", "1",
                  "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""
