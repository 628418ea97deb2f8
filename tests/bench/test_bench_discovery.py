"""The benchmark finds every cell's parts by name, and a later change
can add a configuration, a mix, a metric and a cell with new files and
new ``BENCHMARK.json`` entries alone."""
import json
import re

import pytest

import benchkit
from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_contract_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:3] == ["python3", "-m", "bench.run"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]] \
        + [w["name"] for w in bench["workloads"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.config["name"] == w["config"]
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m.name))
    for m in bench["per_layer"]:
        for w in m.get("workloads", [x["name"] for x in bench["workloads"]]):
            assert m["moves"] in {e.name for e in spec.cell(w).end_to_end}


def test_config_files_name_their_cuts(bench):
    for c in bench["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in conf and not key.endswith(("_dim", "_rank",
                                                     "_size"))


def test_peaks_are_keyed_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_new_files_add_a_cell_a_config_a_mix_and_a_metric(tmp_path):
    root = benchkit.tiny_root(tmp_path)
    (root / "bench" / "metrics" / "hi_count.py").write_text(
        "def read(run):\n    return len(run.window.hi) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "hi_count", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "hi_ttft_p95_ms", "workloads": [benchkit.TINY_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell(benchkit.TINY_CELL, root)
    assert cell.config["arch"] == benchkit.TEST_ARCH
    assert cell.mix["hi"]["rate_per_s"] == benchkit.TINY_MIX["hi"][
        "rate_per_s"]
    assert "hi_count" in {m.name for m in cell.per_layer}
    read = spec.reader("hi_count", root)

    class W:
        hi = [1, 2, 3]

    class R:
        window = W()
    assert read(R()) == 3
    # the files the benchmark already had are untouched
    for p in (spec.ROOT / "bench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            q = root / p.relative_to(spec.ROOT)
            assert q.read_bytes() == p.read_bytes(), p
