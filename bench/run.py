"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout: the cell's configuration, traffic mix and
metrics are found by name through ``BENCHMARK.json`` (``bench/spec.py``).
Set-up asks the configuration's family (``bench/families/``) for the
program's architecture, the shape check and the weights, made from the
seed; it builds ``MESCServer`` with ``Policy.mesc()`` and the
configuration's ``max_len`` and slots, and serves one request of every
prompt length the mix sends, so that every program is compiled (or
read from the persistent cache) before the window opens; ``setup_s``
counts all of it.  The window then serves the mix for ``--seconds``
(``bench/drive.py``).  Once it has closed, the peak device memory is
read, the server's state is freed and the output is checked against
the plain reference (``bench/check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and reports the per-layer
metrics, with the device's busy time and a breakdown.  The last line
of standard output is one JSON object; the numbers compared, each with
its limit, are the last lines of standard error and the last key of
that object.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from bench import check, drive, loadgen, spec, weights  # noqa: E402

ROOT = spec.ROOT


def device_info(chips: int) -> dict:
    """The devices JAX found; exits unless they are ``chips`` TPUs or
    more.  Nothing falls back to the CPU."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is {dev}")
    if dev["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chips, "
                         f"found {dev}")
    return dev


def check_arch(conf: dict, cfg, root: Path = ROOT) -> None:
    """Raise unless the program's configuration is the file's shape."""
    spec.family(conf, root).check_arch(conf, cfg)


def configure_cache() -> str:
    from repro.runtime.device_config import configure_compile_cache
    path = configure_compile_cache()
    # keep every program, however quick to compile, so that a second
    # run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def setup(cell: spec.Cell, seed: int, root: Path = ROOT):
    """Weights from the seed, and a warmed server: ``(params, server)``."""
    from repro.models import lm
    from repro.models.common import RuntimeConfig
    conf = cell.config
    cfg = spec.family(conf, root).program_config(conf)
    check_arch(conf, cfg, root)
    s = conf["serving"]
    rc = RuntimeConfig(compute_dtype=weights.DTYPES[s["compute_dtype"]],
                       param_dtype=weights.DTYPES[s["param_dtype"]])
    weights.check_layout(conf, jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0), rc)), root)
    if loadgen.max_request_len(cell.mix) > s["max_len"]:
        raise ValueError(f"{cell.name}: the mix sends requests longer "
                         f"than max_len {s['max_len']}")
    params = jax.block_until_ready(weights.make_params(conf, seed, root))
    srv = drive.build_server(conf, cfg, params, rc)
    drive.warm(srv, loadgen.prompt_lens(cell.mix))
    return params, srv


def memory_peak() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def free(srv, window) -> None:
    """Drop the server's state: caches, requests, compiled steps."""
    for r in window.hi + window.lo:
        r.cache = None
    srv.requests.clear()
    gc.collect()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, root: Path = ROOT,
             t_start: float = T_START):
    """One run of ``cell``: ``(result, checks)``."""
    params, srv = setup(cell, seed, root)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    window = drive.drive(srv, cell.config, cell.mix, seed, seconds,
                         annotate=trace)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_lib
        paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        reduced = trace_lib.reduce(str(paths[-1])) if paths else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = dict(device, memory_peak_bytes=memory_peak())
    free(srv, window)
    del srv
    checks = check.numbers(cell.config, params, window, seed)
    run = types.SimpleNamespace(conf=cell.config, mix=cell.mix,
                                setup_s=setup_s, window=window,
                                trace=reduced, peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(m.name, root)(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    failed = window.hi_unfinished + checks["short_answers"]["value"]
    result = {
        "correct": check.passed(checks),
        "attempted": len(window.hi) + window.lo_sent,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    late = loadgen.nearest_rank(window.admit_late_s, 0.95)
    steps = [s for s in window.steps if s.in_window]
    longest = max(steps, key=lambda s: s.t1 - s.t0, default=None)
    result["diagnostics"] = {
        "window_compiles": window.compiles,
        "steps_in_window": len(steps),
        # where a stall lies: inside one step(), or between steps
        "longest_step_ms": longest and (longest.t1 - longest.t0) * 1e3,
        "longest_step_kind": longest and longest.kind,
        "longest_between_steps_ms": max(
            ((b.t0 - a.t1) * 1e3 for a, b in zip(steps, steps[1:])),
            default=None),
        "gc_in_window_s": sum(window.gc_s),
        "gc_longest_ms": max(window.gc_s, default=0.0) * 1e3,
        "hi_arrivals": len(window.hi),
        "lo_sent": window.lo_sent,
        "hi_admit_late_p95_ms": None if late is None else late * 1e3,
        "hbm_bytes_limit": int((jax.devices()[0].memory_stats() or {})
                               .get("bytes_limit", 0)),
    }
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    return result, checks


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    device = device_info(cell.chips)
    peaks = spec.peaks(device["kind"])
    configure_cache()
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), device, peaks)
    if args.trace and "busy_s" not in result["device"]:
        raise SystemExit("bench: the profiler trace holds no device "
                         "operation in the window")
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
