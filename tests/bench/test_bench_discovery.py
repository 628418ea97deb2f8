"""The benchmark finds every cell's parts by name, and a later change
can add a configuration of any model family, a mix, a metric and a cell
with new files and new ``BENCHMARK.json`` entries alone."""
import dataclasses
import json
import re
from pathlib import Path

import jax
import numpy as np
import pytest

import benchkit
from bench import counts, reference, run, spec, weights

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


# a second family for the tests below: each function hands the call to
# the dense family and records it
TWIN = '''"""The dense family's functions under another name, each call
recorded."""
from bench import spec

CALLS = []


def _dense(name):
    def call(conf, *args):
        CALLS.append(name)
        return getattr(spec.family({}), name)(conf, *args)
    return call


for _name in ("dims", "program_config", "check_arch", "make", "logits_at",
              "flops", "hbm_bytes"):
    globals()[_name] = _dense(_name)
'''


def test_new_files_add_a_model_family(tmp_path, monkeypatch):
    benchkit.use_test_arch(monkeypatch)
    conf = dict(benchkit.TINY_CONFIG, family="twin")
    root = benchkit.tiny_root(tmp_path, conf)
    (root / "bench" / "families" / "twin.py").write_text(TWIN)
    cell = spec.cell(benchkit.TINY_CELL, root)
    assert cell.config["family"] == "twin"
    twin = spec.family(cell.config, root)
    dense = spec.family(benchkit.TINY_CONFIG)
    assert twin is not dense and twin.CALLS == []
    assert spec.family(cell.config, root) is twin

    assert weights.dims(cell.config, root) == \
        weights.dims(benchkit.TINY_CONFIG)
    got = weights.make_params(cell.config, 3, root)
    want = weights.make_params(benchkit.TINY_CONFIG, 3)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()), got, want))
    toks, rows = np.arange(40, dtype=np.int32), np.array([0, 17, 39])
    np.testing.assert_array_equal(
        reference.logits_at(cell.config, got, toks, rows, root=root),
        reference.logits_at(benchkit.TINY_CONFIG, want, toks, rows))
    for kind, n in (("prefill", 32), ("decode", 1)):
        assert counts.flops(cell.config, kind, n, 40, root) == \
            counts.flops(benchkit.TINY_CONFIG, kind, n, 40)
        assert counts.hbm_bytes(cell.config, kind, n, 40, root) == \
            counts.hbm_bytes(benchkit.TINY_CONFIG, kind, n, 40)
    cfg = twin.program_config(cell.config)
    run.check_arch(cell.config, cfg, root)
    with pytest.raises(ValueError, match="n_layers"):
        run.check_arch(cell.config, dataclasses.replace(cfg, n_layers=3),
                       root)
    assert set(twin.CALLS) == {"dims", "make", "logits_at", "flops",
                               "hbm_bytes", "program_config", "check_arch"}
    # the files the benchmark already had are untouched
    for p in (spec.ROOT / "bench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            q = root / p.relative_to(spec.ROOT)
            assert q.read_bytes() == p.read_bytes(), p


def test_configs_without_a_family_are_dense(bench):
    dense = spec.family({})
    assert Path(dense.__file__) == spec.ROOT / "bench/families/dense.py"
    for c in bench["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert "family" not in conf and spec.family(conf) is dense


def test_an_unknown_family_fails_at_setup_with_the_file_named(tmp_path):
    root = benchkit.tiny_root(
        tmp_path, dict(benchkit.TINY_CONFIG, family="nosuch"))
    with pytest.raises(KeyError, match=r"bench/families/nosuch\.py") as e:
        benchkit.run_tiny(root)
    assert "'dense'" in str(e.value)
