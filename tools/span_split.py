"""Split a benchmark cell's idle device time by the server's own spans.

    python3 -m tools.span_split --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

On the chip, from the root of a checkout.  It makes one traced run of
the cell as ``python3 -m bench.run ... --trace 1`` does, prints the same
result line, and keeps the profiler trace that ``bench.run`` deletes.
From that trace it splits the device's idle time in the window:

* inside ``bench.step`` (one ``MESCServer.step()`` call) by the
  program's spans ``mesc.schedule`` (with ``mesc.save`` and
  ``mesc.restore`` inside it), ``mesc.launch``, ``mesc.wait`` and
  ``mesc.finish``, and what of the step lies in none of them;
* between steps by ``bench/drive.py``'s ``bench.pump``, ``bench.wait`` and
  the rest (``host``).

It also reads every metric of ``Request.steps`` and
``Request.blocked_s`` from the run's window, whether or not the cell
lists it, and each span's mean host time by kind of instruction.
The last line of standard output is one JSON object, also written to
``--out``.
"""
from __future__ import annotations

import argparse
import collections
import json
import shutil
import types
from pathlib import Path
from typing import Dict, List, Tuple
from unittest import mock

Intervals = List[Tuple[int, int]]

STEP = "bench.step"
BETWEEN = ("bench.pump", "bench.wait")
INSIDE = ("mesc.schedule", "mesc.save", "mesc.restore", "mesc.launch",
          "mesc.wait", "mesc.finish")
SPAN_METRICS = ("sched_us_per_step", "host_ms_per_step",
                "host_syncs_per_step", "decode_wait_ms",
                "prefill_wait_ms_per_ktok", "hi_blocked_by_lo_ms_p95")


def _and(a: Intervals, b: Intervals) -> Intervals:
    """The intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _len(iv: Intervals) -> int:
    return sum(b - a for a, b in iv)


def split(busy: Intervals, spans: Dict[str, Intervals],
          window: Tuple[int, int]) -> dict:
    """Seconds of idle device time in ``window`` by span; ``busy`` and
    every list of ``spans`` sorted and disjoint, in nanoseconds."""
    from bench.trace import _clip
    w0, w1 = window
    busy = _clip(busy, w0, w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    step = _and(idle, spans.get(STEP, []))
    in_step = {k: _len(_and(step, spans.get(k, []))) for k in INSIDE}
    in_mesc = _len(_and(step, spans.get("mesc.step", [])))
    children = sum(in_step[k] for k in INSIDE
                   if k not in ("mesc.save", "mesc.restore"))
    between = _len(idle) - _len(step)
    out_between = {k: _len(_and(idle, spans.get(k, []))) for k in BETWEEN}

    def s(ns):
        return ns / 1e9
    return {
        "window_s": s(w1 - w0),
        "idle_s": s(_len(idle)),
        "steps": len(spans.get(STEP, [])),
        "inside_step": dict(
            {k: s(v) for k, v in in_step.items()},
            **{"mesc.step, no child": s(in_mesc - children),
               "bench.step, outside mesc.step": s(_len(step) - in_mesc),
               "total": s(_len(step))}),
        "between_steps": dict(
            {k: s(v) for k, v in out_between.items()},
            **{"host": s(between - sum(out_between.values())),
               "total": s(between)}),
        "span_total_s": {k: s(_len(_clip(v, w0, w1)))
                         for k, v in sorted(spans.items())},
        # the server is synchronous: nothing of its runs on the device
        # in these spans, so busy time there is the offset between the
        # trace's host and device clocks
        "busy_inside_s": {k: s(_len(_and(busy, spans.get(k, []))))
                          for k in ("mesc.schedule", "mesc.finish")},
    }


def step_means(steps) -> dict:
    """Mean host ms of each span of ``Request.steps`` records, by kind
    of instruction."""
    out = {}
    for kind in ("prefill", "decode"):
        s = [x for x in steps if x.kind == kind]
        if s:
            out[kind] = {"steps": len(s), **{
                name: sum(f(x) for x in s) / len(s) * 1e3 for name, f in (
                    ("mesc.schedule", lambda x: x.launch - x.start - x.cs_s),
                    ("mesc.launch", lambda x: x.returned - x.launch),
                    ("mesc.wait", lambda x: x.token - x.returned),
                    ("mesc.finish", lambda x: x.end - x.token))}}
    return out


def read_trace(path: str) -> Tuple[Intervals, Dict[str, Intervals],
                                   Tuple[int, int]]:
    """``(device busy, host spans by name, window)`` of a trace, in
    nanoseconds; the busy time of the first device plane."""
    import numpy as np
    from bench.trace import WINDOW, _union, load
    names = set(INSIDE) | set(BETWEEN) | {STEP, "mesc.step", WINDOW}
    host: Dict[str, list] = collections.defaultdict(list)
    busy: Intervals = []
    for plane in load(path).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops" and not busy:
                    ev = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                          for e in line.events]
                    if ev:
                        busy = _union(*map(np.asarray, zip(*ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        s = int(e.start_ns)
                        host[e.name].append((s, s + int(e.duration_ns)))
    spans = {k: _union(*map(np.asarray, zip(*v))) for k, v in host.items()}
    window = spans.pop(WINDOW)[0]
    return busy, spans, window


def measure(cell, seed: int, seconds: float, device: dict, peaks: dict,
            root: Path = None) -> Tuple[dict, dict]:
    """One traced run of ``cell``: ``(result line, span split)``."""
    from bench import run as bench_run
    from bench import spec
    kept, windows = [], []
    drive = bench_run.drive.drive

    def keep_window(*a, **k):
        windows.append(drive(*a, **k))
        return windows[-1]
    with mock.patch.object(bench_run, "shutil", types.SimpleNamespace(
            rmtree=lambda path, **k: kept.append(path))), \
            mock.patch.object(bench_run.drive, "drive", keep_window):
        result, _ = bench_run.run_cell(cell, seed, seconds, True, device,
                                       peaks, root=root or spec.ROOT)
    run = types.SimpleNamespace(window=windows[-1])
    from bench.spans import window_steps
    try:
        trace = sorted(Path(kept[-1]).rglob("*.xplane.pb"))[-1]
        idle = split(*read_trace(str(trace)))
    finally:
        shutil.rmtree(kept[-1], ignore_errors=True)
    return result, {"workload": cell.name, "seed": seed,
                    "span_metrics": {m: spec.reader(m)(run)
                                     for m in SPAN_METRICS},
                    "step_means_ms": step_means(window_steps(run)),
                    "idle_split": idle}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    from bench import run as bench_run
    from bench import spec
    cell = spec.cell(args.workload)
    device = bench_run.device_info(cell.chips)
    bench_run.configure_cache()
    result, out = measure(cell, args.seed, args.seconds, device,
                          spec.peaks(device["kind"]))
    print(json.dumps(result), flush=True)
    text = json.dumps(out)
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(text + "\n")
    print(text, flush=True)


if __name__ == "__main__":
    main()
