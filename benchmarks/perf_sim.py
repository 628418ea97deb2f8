"""Simulation-engine performance harness: points/sec for the
event-driven, vectorized and fully-compiled (jit) backends on a fixed
fig8-style corpus.

The corpus is MESC over the fig8 utilisation band (fig8's task-set
recipe: 10-task UUnifast sets, CF=2, duration 2e8 cycles), 512
``(taskset, seed)`` points — the unit every paper figure is built from.
All engines simulate the *identical* corpus from one process, so the
ratios are engine-vs-engine numbers, not parallelism artefacts.  The
jit engine is additionally timed at logical device counts 1/2/4
(``--devices``, ``REPRO_DEVICES``, see repro.runtime.device_config) —
the ``device_scaling`` rows — and every sharded run is asserted
bit-identical to the single-device run in the same process, so a
scaling number can never come from semantically divergent work.

Because container timing is noisy run-to-run, every engine is measured
**median-of-3 after a warmup run** (the warmup also absorbs the jit
engine's XLA compilation); the per-repeat samples and their spread are
recorded so baseline deltas can be read against the measured noise.

The harness also verifies the engine-equivalence contracts on the
corpus (see docs/performance.md):

  * ``vec`` is bit-exact against ``event`` on every point;
  * ``jit`` matches ``vec`` bit-exactly on the zero-jitter
    (``demand_profile="nominal"``) corpus, where no in-loop RNG draws
    exist;
  * ``jit`` matches ``vec`` statistically on the sampled corpus
    (success rates within binomial sampling error; counter-based RNG,
    see core/simulator_jit.py);
  * sharded ``jit`` (``--devices N > 1``) is bit-exact against the
    single-device jit run on the sampled corpus (the CI device-matrix
    gate).

An empty corpus or comparison set is a hard error naming the section —
a vacuous equivalence pass must never gate green.

Results are written to ``BENCH_sim.json`` at the repo root — the
committed copy is the perf baseline every future PR is compared
against (CI job ``perf-smoke`` prints the delta and *gates* on the
equivalence checks).

    PYTHONPATH=src python -m benchmarks.perf_sim [--smoke]
        [--check-equivalence] [--devices N] [--out BENCH_sim.json]
        [--baseline BENCH_sim.json]

``--smoke`` runs a reduced corpus (32 points, shorter horizon) sized
for CI; it updates only the ``smoke`` section of the JSON so the
committed ``full`` numbers survive.  ``--check-equivalence`` runs only
the (gating) equivalence checks, no timing repeats.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time
from pathlib import Path

SCHEMA_VERSION = 3
REPEATS = 3
DEVICE_COUNTS = (1, 2, 4)
REPO_ROOT = Path(__file__).resolve().parent.parent
DEFAULT_OUT = REPO_ROOT / "BENCH_sim.json"

FULL = dict(utils=(0.6, 0.7, 0.8, 0.9), n_sets=128, duration=2e8,
            n_tasks=10)
SMOKE = dict(utils=(0.7, 0.9), n_sets=16, duration=2e7, n_tasks=10)

ENGINES = ("event", "vec", "jit")


def build_corpus(spec):
    from repro.core import Policy, generate_taskset
    from repro.experiments.runner import cached_library
    lib = cached_library("sim")
    tasksets, seeds = [], []
    for u in spec["utils"]:
        for s in range(spec["n_sets"]):
            tasksets.append(generate_taskset(
                u, seed=s, n_tasks=spec["n_tasks"], programs=lib))
            seeds.append(s)
    return lib, Policy.mesc(), tasksets, seeds


def _engine_fn(engine, lib, policy, tasksets, seeds, duration,
               devices=None):
    from repro.core.simulator import simulate
    from repro.core.simulator_vec import simulate_vbatch
    if engine == "event":
        return lambda: [simulate(ts, lib, policy, duration=duration,
                                 seed=s)
                        for ts, s in zip(tasksets, seeds)]
    backend = "numpy" if engine == "vec" else "jit"
    return lambda: simulate_vbatch(tasksets, lib, policy, seeds=seeds,
                                   duration=duration, batch_size=512,
                                   select_backend=backend,
                                   devices=devices)


def _timed(fn):
    """Warmup + median-of-REPEATS timing; returns (result, samples)."""
    result = fn()                       # warmup (jit: compilation)
    samples = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        result = fn()
        samples.append(time.perf_counter() - t0)
    return result, samples


def _stats(samples, n):
    med = sorted(samples)[len(samples) // 2]
    spread = 100.0 * (max(samples) - min(samples)) / med if med else 0.0
    return {"points": n, "seconds": round(med, 3),
            "points_per_sec": round(n / med, 2),
            "samples": [round(s, 3) for s in samples],
            "spread_pct": round(spread, 1)}


def binomial_bound(pbar: float, n: int) -> float:
    """4-sigma bound on the difference of two success proportions over
    n points each — the jit-vs-vec statistical gate (shared with
    tests/test_simulator_jit.py)."""
    return 4.0 * math.sqrt(max(pbar * (1 - pbar), 1e-12) * 2 / n) \
        + 2.0 / n


def check_equivalence(spec, results=None, devices=None,
                      section="full") -> dict:
    """The cross-engine contracts on the corpus (see module
    docstring).  Returns the equivalence report; raises SystemExit on
    any violation — including an *empty* corpus or comparison set,
    which would otherwise vacuously pass every gate.  ``results`` may
    carry already-simulated ``{engine: [RunMetrics]}`` sampled-corpus
    outputs (measure() hands its timed runs over; the jit entry must
    have run at ``devices``) — only the missing pieces are simulated.
    ``devices > 1`` additionally gates sharded-vs-single-device jit
    bit-exactness."""
    from repro.core.simulator import simulate
    from repro.core.simulator_vec import simulate_vbatch
    from repro.experiments.metrics import metrics_row
    from repro.runtime.device_config import default_device_count
    lib, policy, tasksets, seeds = build_corpus(spec)
    n = len(tasksets)
    if n == 0:
        raise SystemExit(
            f"check-equivalence: corpus section {section!r} is empty "
            f"(utils={spec.get('utils')!r}, "
            f"n_sets={spec.get('n_sets')!r}) — an empty comparison set "
            "would vacuously pass every gate; refusing to report "
            "success")

    def _require(name, lst):
        """Comparison sets must cover the corpus, 1:1 — an empty or
        truncated set silently weakens every zip()-based gate below."""
        if len(lst) != n:
            raise SystemExit(
                f"check-equivalence: section {section!r} comparison "
                f"set {name!r} has {len(lst)} results for {n} corpus "
                "points — refusing to gate on a partial comparison")
        return lst

    duration = spec["duration"]
    devices = default_device_count() if devices is None else devices
    results = results or {}

    ev = _require("event", results.get("event") or [
        simulate(ts, lib, policy, duration=duration, seed=s)
        for ts, s in zip(tasksets, seeds)])
    vc = _require("vec", results.get("vec") or simulate_vbatch(
        tasksets, lib, policy, seeds=seeds, duration=duration,
        batch_size=512))
    vec_mismatch = sum(metrics_row(a) != metrics_row(b)
                       for a, b in zip(ev, vc))

    # zero-jitter corpus: no in-loop draws exist, jit must equal vec
    # bit-for-bit
    vc_nom = _require("vec_nominal", simulate_vbatch(
        tasksets, lib, policy, seeds=seeds, duration=duration,
        batch_size=512, demand_profile="nominal"))
    jt_nom = _require("jit_nominal", simulate_vbatch(
        tasksets, lib, policy, seeds=seeds, duration=duration,
        batch_size=512, demand_profile="nominal",
        select_backend="jit", devices=devices))
    nom_mismatch = sum(metrics_row(a) != metrics_row(b)
                       for a, b in zip(vc_nom, jt_nom))

    # sampled corpus: jit draws from counter-based streams — success
    # rates must agree within binomial sampling error
    jt = _require("jit", results.get("jit") or simulate_vbatch(
        tasksets, lib, policy, seeds=seeds, duration=duration,
        batch_size=512, select_backend="jit", devices=devices))

    # sharded vs single-device jit: per-point keyed RNG draws make the
    # device count pure execution placement — bit-exact, not just
    # statistically equivalent (the CI device-matrix gate)
    sharded_mismatch = None
    if devices > 1:
        jt_1 = _require("jit_devices1", simulate_vbatch(
            tasksets, lib, policy, seeds=seeds, duration=duration,
            batch_size=512, select_backend="jit", devices=1))
        sharded_mismatch = sum(metrics_row(a) != metrics_row(b)
                               for a, b in zip(jt, jt_1))

    rows_v = [metrics_row(m) for m in vc]
    rows_j = [metrics_row(m) for m in jt]
    statistical = {}
    stat_ok = True
    for field in ("success_all", "success_hi"):
        pv = sum(r[field] for r in rows_v) / n
        pj = sum(r[field] for r in rows_j) / n
        bound = binomial_bound(0.5 * (pv + pj), n)
        ok = abs(pv - pj) <= bound
        stat_ok = stat_ok and ok
        statistical[field] = {"vec": round(pv, 4), "jit": round(pj, 4),
                              "bound": round(bound, 4), "ok": ok}

    report = {
        "vec_exact_match_points": n - vec_mismatch,
        "vec_mismatched_points": vec_mismatch,
        "jit_nominal_exact_match_points": n - nom_mismatch,
        "jit_nominal_mismatched_points": nom_mismatch,
        "jit_statistical": statistical,
        "jit_statistical_ok": stat_ok,
        "jit_devices": devices,
        "sharded_exact_match_points":
            None if sharded_mismatch is None else n - sharded_mismatch,
        "sharded_mismatched_points": sharded_mismatch,
    }
    if vec_mismatch:
        raise SystemExit(f"{vec_mismatch}/{n} corpus points diverged "
                         "between event and vec — exactness contract "
                         "violated")
    if nom_mismatch:
        raise SystemExit(f"{nom_mismatch}/{n} zero-jitter corpus points "
                         "diverged between vec and jit — nominal "
                         "exact-equivalence contract violated")
    if sharded_mismatch:
        raise SystemExit(
            f"{sharded_mismatch}/{n} corpus points diverged between "
            f"jit at devices={devices} and devices=1 — sharded "
            "bit-exactness contract violated")
    if not stat_ok:
        raise SystemExit("jit-vs-vec statistical equivalence violated: "
                         f"{statistical}")
    return report


def measure(spec, skip_equivalence: bool = False, devices=None,
            section="full"):
    from repro.experiments.metrics import metrics_row
    from repro.runtime.device_config import default_device_count
    lib, policy, tasksets, seeds = build_corpus(spec)
    n = len(tasksets)
    devices = default_device_count() if devices is None else devices
    engines = {}
    results = {}
    for engine in ENGINES:
        fn = _engine_fn(engine, lib, policy, tasksets, seeds,
                        spec["duration"],
                        devices=devices if engine == "jit" else None)
        results[engine], samples = _timed(fn)
        engines[engine] = _stats(samples, n)
    # per-step XLA kernel count of the compiled lockstep body — the
    # grouped-carry refactor's tracked metric.  Sourced from the
    # graph-lint budget manifest (tools/graphlint/budgets.json), which
    # pins it at the canonical corpus shape and re-verifies the pin
    # against a live compile here: the perf log and the ir-budget-drift
    # gate quote one number by construction.  kernel_budget() also
    # enforces that the neutral scenario (faults@0 — every component
    # statically off) compiled to the identical body as the
    # scenario-free graph, so the timed rows above (scenario=None) and
    # the print_delta rows against the committed baseline measure the
    # scenario-off throughput cost the scenario layer is gated on.
    from tools.graphlint import kernel_budget
    engines["jit"].update(kernel_budget())

    # jit pts/s per logical device count, every sharded run asserted
    # bit-identical to the devices=1 rows *from the same process* — a
    # scaling number can never come from semantically divergent work
    import jax
    have = jax.local_device_count()
    scaling = {}
    rows_1 = None
    for d in DEVICE_COUNTS:
        if d > have:
            scaling[str(d)] = {"skipped": f"only {have} devices in pool"}
            continue
        fn = _engine_fn("jit", lib, policy, tasksets, seeds,
                        spec["duration"], devices=d)
        res, samples = _timed(fn)
        st = _stats(samples, n)
        rows = [metrics_row(m) for m in res]
        if rows_1 is None:            # DEVICE_COUNTS starts at 1
            rows_1 = rows
        st["bit_exact_vs_devices1"] = rows == rows_1
        if not st["bit_exact_vs_devices1"]:
            raise SystemExit(
                f"sharded jit (devices={d}) diverged from devices=1 "
                f"on the {section!r} corpus — bit-exactness contract "
                "violated")
        scaling[str(d)] = st
    engines["jit"]["device_scaling"] = scaling

    # reuse the timed sampled-corpus runs; only the two nominal-profile
    # runs inside the check are freshly simulated
    equivalence = None if skip_equivalence \
        else check_equivalence(spec, results, devices=devices,
                               section=section)
    sec = {e: engines[e]["seconds"] for e in ENGINES}
    return {
        "corpus": {"style": "fig8", "policy": policy.name,
                   "utils": list(spec["utils"]), "n_sets": spec["n_sets"],
                   "n_tasks": spec["n_tasks"], "duration": spec["duration"],
                   "points": n},
        "engines": engines,
        "speedup_vec_vs_event": round(sec["event"] / sec["vec"], 2),
        "speedup_jit_vs_vec": round(sec["vec"] / sec["jit"], 2),
        "speedup_jit_vs_event": round(sec["event"] / sec["jit"], 2),
        "equivalence": equivalence,
    }


def load(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (FileNotFoundError, json.JSONDecodeError):
        return {"schema_version": SCHEMA_VERSION, "sections": {}}


def print_delta(section: str, new: dict, baseline: dict) -> None:
    base_schema = baseline.get("schema_version")
    if base_schema != SCHEMA_VERSION:
        # an old-schema baseline (e.g. the v1 layout without samples/
        # spread) must not KeyError the delta report — warn and skip
        print(f"# baseline schema v{base_schema} != v{SCHEMA_VERSION} "
              "— skipping perf delta (refresh the baseline by "
              "committing this run's BENCH_sim.json)")
        return
    base = baseline.get("sections", {}).get(section)
    if not base:
        print(f"# no committed baseline for section {section!r}")
        return
    for eng in ENGINES:
        old = base.get("engines", {}).get(eng)
        if not old:                       # e.g. schema-v1 baseline
            print(f"# no baseline for engine {eng!r}")
            continue
        old_pps = old["points_per_sec"]
        new_pps = new["engines"][eng]["points_per_sec"]
        delta = 100.0 * (new_pps - old_pps) / old_pps if old_pps else 0.0
        spread = new["engines"][eng].get("spread_pct", 0.0)
        print(f"perf_delta,{section},{eng},{old_pps},{new_pps},"
              f"{delta:+.1f}%,spread={spread}%")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI-sized corpus (updates 'smoke' only)")
    ap.add_argument("--check-equivalence", action="store_true",
                    help="run only the cross-engine equivalence checks "
                         "(the CI gate); no timing, no JSON update")
    ap.add_argument("--skip-equivalence", action="store_true",
                    help="measure timings only (CI's measure step — its "
                         "gating sibling already ran the checks)")
    ap.add_argument("--devices", type=int, default=None,
                    help="devices for the jit engine: TPU chips, or "
                         "logical CPU devices (default: REPRO_DEVICES "
                         "or 1); the "
                         "device_scaling rows always cover "
                         f"{DEVICE_COUNTS}")
    ap.add_argument("--out", default=str(DEFAULT_OUT),
                    help="where to write the updated BENCH_sim.json")
    ap.add_argument("--baseline", default=str(DEFAULT_OUT),
                    help="committed baseline to diff against")
    args = ap.parse_args()

    section = "smoke" if args.smoke else "full"
    spec = SMOKE if args.smoke else FULL

    # the logical-device pool must be forced before the first jax
    # computation (XLA reads the flag once); cover the scaling rows too
    from repro.runtime.device_config import (configure_compile_cache,
                                             configure_host_devices,
                                             default_device_count)
    configure_compile_cache()
    devices = default_device_count() if args.devices is None \
        else args.devices
    configure_host_devices(max(devices, max(DEVICE_COUNTS)))

    if args.check_equivalence:
        report = check_equivalence(spec, devices=devices,
                                   section=section)
        sharded = report["sharded_exact_match_points"]
        print(f"equivalence,{section},"
              f"vec_exact={report['vec_exact_match_points']},"
              f"jit_nominal_exact="
              f"{report['jit_nominal_exact_match_points']},"
              f"jit_statistical_ok={report['jit_statistical_ok']},"
              f"devices={report['jit_devices']},"
              f"sharded_exact="
              f"{'n/a' if sharded is None else sharded}")
        return

    baseline = load(Path(args.baseline))
    result = measure(spec, skip_equivalence=args.skip_equivalence,
                     devices=devices, section=section)
    if result["equivalence"] is None:
        # timings-only run: carry the baseline's last verified block
        result["equivalence"] = baseline.get("sections", {}).get(
            section, {}).get("equivalence")

    import jax
    doc = load(Path(args.out))
    doc["schema_version"] = SCHEMA_VERSION
    doc.setdefault("sections", {})
    # keep the other section's committed numbers intact
    for k, v in baseline.get("sections", {}).items():
        doc["sections"].setdefault(k, v)
    doc["sections"][section] = result
    doc["host"] = {"cpus": os.cpu_count(), "devices": devices,
                   "logical_devices": jax.local_device_count()}

    Path(args.out).write_text(json.dumps(doc, indent=1, sort_keys=True)
                              + "\n")
    print(f"corpus,{section},points={result['corpus']['points']}")
    for eng in ENGINES:
        e = result["engines"][eng]
        print(f"{eng},{e['seconds']}s,{e['points_per_sec']}pts/s,"
              f"spread={e['spread_pct']}%")
    print(f"jit_kernels,{section},"
          f"{result['engines']['jit']['xla_kernels']},"
          f"neutral_scenario="
          f"{result['engines']['jit']['xla_kernels_neutral_scenario']}")
    for d, st in result["engines"]["jit"]["device_scaling"].items():
        if "points_per_sec" in st:
            print(f"jit_devices,{d},{st['points_per_sec']}pts/s,"
                  f"bit_exact={st['bit_exact_vs_devices1']}")
        else:
            print(f"jit_devices,{d},{st['skipped']}")
    print(f"speedup,vec_vs_event,{result['speedup_vec_vs_event']}x")
    print(f"speedup,jit_vs_vec,{result['speedup_jit_vs_vec']}x")
    eq = result["equivalence"]
    if eq is not None and not args.skip_equivalence:
        print(f"equivalence,vec_exact={eq['vec_exact_match_points']}/"
              f"{result['corpus']['points']},"
              f"jit_nominal_exact={eq['jit_nominal_exact_match_points']}/"
              f"{result['corpus']['points']},"
              f"jit_statistical_ok={eq['jit_statistical_ok']}")
    print_delta(section, result, baseline)


if __name__ == "__main__":
    main()
