"""Find a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by name:

* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: the mix's parameters;
* ``bench/metrics/<metric>.py``: the metric's reader, a ``read(run)``
  that returns the number, or ``None`` where the run holds nothing to
  read;
* ``bench/families/<family>.py``: the weights, plain reference, shape
  check and counts of a configuration's model family, named by the
  file's ``"family"`` (``"dense"`` where it names none); the functions
  it defines are listed in ``bench/families/__init__.py``.

So a later change adds a cell, a configuration of any family, a mix or
a metric with new files and new entries in ``BENCHMARK.json``, and
edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    kind: str                     # "end_to_end" or "per_layer"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    root = Path(root)
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    conf = json.loads(
        (root / "bench" / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def metrics(kind):
        return [Metric(m["name"], m["unit"], m["better"], kind)
                for m in bench[kind] if _applies(m, name)]
    return Cell(name, int(w["chips"]), conf, mix,
                metrics("end_to_end"), metrics("per_layer"))


def reader(metric: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(conf: dict, root: Path = ROOT):
    """The module ``bench/families/<family>.py`` of a configuration;
    an unknown family is an error that names the file looked for."""
    return _family(str(root), conf.get("family", "dense"))


@functools.lru_cache(maxsize=None)
def _family(root: str, name: str):
    # loaded once a checkout: its jitted reference functions then
    # compile once, and the counts are read at every step
    path = Path(root) / "bench" / "families" / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in path.parent.glob("*.py")
                      if p.stem != "__init__")
        raise KeyError(f"no family {name!r}: there is no {path}; the "
                       f"families are {have}")
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The peaks of device ``kind``; an unknown device is an error."""
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][kind]
