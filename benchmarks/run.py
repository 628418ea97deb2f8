"""Benchmark entry point: one function per paper table/figure.

Every figure is a declaration over the campaign engine
(``repro.experiments``): points fan out across worker processes and are
cached on disk by content hash, so a re-run of an unchanged figure is
pure cache replay.  Prints ``name,us_per_call,derived`` CSV summary
lines (plus the per-figure CSV blocks above them).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig7,fig8]
        [--engine event|vec|jit] [--devices N] [--scenario NAME]
        [--workers N] [--cache-dir DIR] [--no-cache] [--smoke]

``--full`` uses the paper's 1000 task sets per point (slow); default is
a statistically-meaningful reduction.  ``--engine vec`` routes the
single-accelerator simulation sweeps through the vectorized batch
backend (``core.simulator_vec``); ``--engine jit`` through the fully-
compiled ``jax.lax.while_loop`` backend (``core.simulator_jit``,
statistically equivalent RNG contract).  ``--devices N`` shards the
jit engine's point axis over N devices: TPU chips on a TPU host (more
than the host has is an error), logical host devices on the CPU.  On
one platform the rows and cache entries are identical at any count —
a pure throughput knob.  Across platforms only nominal-profile rows
are: a TPU's f64 is not IEEE, so sampled and scenario jit points run
there are cached under keys that name the platform (see
docs/performance.md).  jit chunks run in this process, which holds the
device; workers get the CPU only.  Each engine has its own cache
namespace, see docs/performance.md.
``--scenario NAME`` runs the scenario-capable sim figures (fig8/fig9/
fig10) under a declarative fault/demand scenario (``repro.scenarios``,
e.g. ``heavy_tail`` or ``faults@0.5``; see docs/scenarios.md) — fig13
sweeps the whole ``faults@<x>`` family itself.  ``--smoke`` runs a
2-point sweep end-to-end (used by CI).
"""
from __future__ import annotations

import argparse
import sys


def smoke(engine: str = "event", devices=None, scenario=None,
          **campaign_kw) -> None:
    """Tiny end-to-end campaign: 2 points through the full engine path."""
    from repro.core import Policy
    from repro.experiments import Campaign, Sweep
    sweep = Sweep(name="smoke", policies=(Policy.mesc(),), utils=(0.7,),
                  n_sets=2, duration=2e6, engine=engine,
                  devices=devices, scenario=scenario)
    camp = Campaign(sweep, **campaign_kw)
    rows = camp.collect()
    print("point,policy,u,seed,jobs,success_all")
    for r in rows:
        print(f"{r['set_index']},{r['policy']},{r['u']},{r['seed']},"
              f"{r['jobs_lo'] + r['jobs_hi']},{r['success_all']}")
    print(f"smoke,0.0,points={len(rows)};hits={camp.stats['hits']};"
          f"misses={camp.stats['misses']}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale experiment sizes (1000 task sets)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (fig2,fig6,fig7,fig8,"
                         "fig9,fig10,fig11,fig12,fig13,overhead)")
    ap.add_argument("--workers", type=int, default=None,
                    help="worker processes per campaign "
                         "(default: CPU count / $REPRO_WORKERS)")
    ap.add_argument("--cache-dir", default=None,
                    help="result-cache root (default: results/campaigns "
                         "/ $REPRO_CACHE_DIR)")
    ap.add_argument("--no-cache", action="store_true",
                    help="always re-simulate; write nothing to disk")
    ap.add_argument("--smoke", action="store_true",
                    help="run a tiny 2-point campaign and exit (CI)")
    ap.add_argument("--engine", default="event",
                    choices=("event", "vec", "jit"),
                    help="simulation backend for the sim sweeps "
                         "(vec = vectorized batch engine, jit = fully-"
                         "compiled jax.lax.while_loop backend)")
    ap.add_argument("--devices", type=int, default=None,
                    help="devices the jit engine shards points over: "
                         "TPU chips, or logical CPU devices (requires "
                         "--engine jit; on one platform results and "
                         "cache entries are identical at any count)")
    ap.add_argument("--scenario", default=None,
                    help="declarative fault/demand scenario for the "
                         "scenario-capable sim figures (fig8/fig9/"
                         "fig10) and --smoke; a registry name like "
                         "'heavy_tail' or 'faults@<intensity>' — "
                         "unknown names fail loudly")
    args = ap.parse_args()
    if args.devices is not None and args.engine != "jit":
        ap.error("--devices requires --engine jit")
    from repro.runtime.device_config import configure_compile_cache
    configure_compile_cache()
    if args.scenario is not None:      # fail loudly before any campaign
        from repro.scenarios import get_scenario
        try:
            get_scenario(args.scenario)
        except ValueError as e:
            ap.error(str(e))
    campaign_kw = dict(workers=args.workers, cache_dir=args.cache_dir,
                       use_cache=not args.no_cache)

    if args.smoke:
        smoke(engine=args.engine, devices=args.devices,
              scenario=args.scenario, **campaign_kw)
        return

    from benchmarks import (fig2_instruction_costs, fig6_banks,
                            fig7_blocking, fig8_success, fig9_hi_success,
                            fig10_survivability, fig11_multiacc,
                            fig12_serving_slo, fig13_fault_survivability,
                            tbl_overhead)
    table = {
        "fig2": fig2_instruction_costs.main,
        "fig6": fig6_banks.main,
        "fig7": fig7_blocking.main,
        "fig8": fig8_success.main,
        "fig9": fig9_hi_success.main,
        "fig10": fig10_survivability.main,
        "fig11": fig11_multiacc.main,
        "fig12": fig12_serving_slo.main,
        "fig13": fig13_fault_survivability.main,
        "overhead": tbl_overhead.main,
    }
    # sim figures that take a scenario axis (the rest are scenario-free
    # analyses; --scenario leaves them untouched)
    scenario_figs = {"fig8", "fig9", "fig10"}
    only = args.only.split(",") if args.only else list(table)
    print("name,us_per_call,derived")
    for name in only:
        print(f"# === {name} ===", file=sys.stderr)
        kw = dict(campaign_kw)
        if args.scenario is not None and name in scenario_figs:
            kw["scenario"] = args.scenario
        try:
            table[name](full=args.full, engine=args.engine,
                        devices=args.devices, **kw)
        except Exception as e:  # repro-lint: disable=except-breadth (CLI boundary: one broken figure must not kill the sweep; the error lands in the CSV row)
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")


if __name__ == "__main__":
    main()
