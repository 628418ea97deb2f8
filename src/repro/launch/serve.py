"""Mixed-criticality serving driver (the paper's system end-to-end).

Serves a small model with batched requests of mixed priority/criticality
under the MESC scheduler (instruction-level = decode-step preemption,
bank-pool cache residency, LO-budget mode switching), and compares
against a non-preemptive (FIFO/run-to-completion) baseline.  With
``--lanes N`` the requests are partitioned across N virtual accelerator
dispatch lanes sharing one KV-slot arena (``core.serving.MultiLaneServer``,
see docs/scheduling.md).

``--arrivals`` switches from the legacy batch drive to the open-loop
traffic layer (``repro.serving``): requests arrive per a CRN arrival
process (poisson / heavy_tail / diurnal / a replayed ``--trace`` file)
through the admission front door, and the run is summarized as SLO
metrics (``docs/serving.md``).  Add ``--virtual`` to run the whole
thing on the deterministic virtual clock + service model (no model
weights, byte-reproducible — the fig12 path); without it the real
model serves the trace in wall-clock time.

The real model runs with the bf16 runtime config on a TPU and in f32
elsewhere; ``--arch`` defaults to TinyLlama 1.1B at full width on a TPU
and to its smoke reduction elsewhere.  Weights are random, from a fixed
seed.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b-smoke
  PYTHONPATH=src python -m repro.launch.serve --lanes 2 --heuristic crit_aware
  PYTHONPATH=src python -m repro.launch.serve --arrivals poisson --virtual
  PYTHONPATH=src python -m repro.launch.serve --arrivals trace --trace t.json
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from typing import Optional

import numpy as np

from repro.core.scheduler import Policy
from repro.core.serving import MESCServer, MultiLaneServer, Request
from repro.core.task import Crit
from repro.runtime.device_config import configure_compile_cache
from repro.serving import (FrontDoor, PROCESS_KINDS, build_workload,
                           make_process, run_virtual_serving, slo_summary)


def load_model(arch: Optional[str]):
    """Real-model setup: ``(cfg, params, rc)``.  Imported lazily so
    ``--virtual`` runs stay free of jax/model-weight start-up cost."""
    import jax
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.common import CPU_RC, DEFAULT_RC
    on_tpu = jax.default_backend() == "tpu"
    rc = DEFAULT_RC if on_tpu else CPU_RC
    cfg = get_config(arch or ("tinyllama-1.1b" if on_tpu
                              else "tinyllama-1.1b-smoke"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), rc)
    return cfg, params, rc


def make_requests(cfg, rng, n_lo: int = 4, n_hi: int = 2,
                  lo_len: int = 24, hi_len: int = 6,
                  prompt_len: int = 8):
    reqs = []
    rid = 0
    for _ in range(n_lo):
        reqs.append(Request(rid=rid, priority=10 + rid,
                            prompt=rng.integers(0, cfg.vocab, prompt_len,
                                                dtype=np.int32),
                            max_new_tokens=lo_len, crit=Crit.LO))
        rid += 1
    for _ in range(n_hi):
        reqs.append(Request(rid=rid, priority=rid - n_lo,
                            prompt=rng.integers(0, cfg.vocab, prompt_len,
                                                dtype=np.int32),
                            max_new_tokens=hi_len, crit=Crit.HI))
        rid += 1
    return reqs


def _make_server(cfg, params, policy, *, rc, max_len: int, lanes: int,
                 heuristic: str):
    if lanes > 1:
        srv = MultiLaneServer(cfg, params, policy=policy, rc=rc,
                              max_len=max_len, n_lanes=lanes,
                              heuristic=heuristic)
    else:
        srv = MESCServer(cfg, params, policy=policy, rc=rc, max_len=max_len)
    print(f"  weights cast to the compute dtype at build: "
          f"{srv.cast_bytes} bytes")
    return srv


def _warm(srv, prompt_lens) -> None:
    """Compile prefill (one per prompt length) and decode outside the
    measured window, then forget the warm-up requests."""
    for i, n in enumerate(sorted(set(prompt_lens))):
        srv.submit(Request(rid=-1 - i, priority=99,
                           prompt=np.zeros(n, np.int32), max_new_tokens=2,
                           crit=Crit.LO))
    srv.run()
    for ln in getattr(srv, "lanes", [srv]):
        ln.requests.clear()


def run(cfg, params, policy, reqs, hi_delay_steps: int = 3,
        lanes: int = 1, heuristic: str = "crit_aware", *, rc,
        max_len: int):
    """LO requests submitted first; HI requests arrive mid-flight."""
    srv = _make_server(cfg, params, policy, rc=rc, max_len=max_len,
                       lanes=lanes, heuristic=heuristic)
    _warm(srv, [len(r.prompt) for r in reqs])
    lo = [r for r in reqs if r.crit == Crit.LO]
    hi = [r for r in reqs if r.crit == Crit.HI]
    for r in lo:
        srv.submit(r)
    for _ in range(hi_delay_steps):
        srv.step()
    for r in hi:
        srv.submit(r)
    srv.run()
    return srv.requests


def summarize(name, reqs):
    out = {}
    for crit in (Crit.HI, Crit.LO):
        rs = [r for r in reqs.values() if r.crit == crit and r.finished_at]
        if not rs:
            continue
        ttft = [r.first_token_at - r.submitted_at for r in rs]
        lat = [r.finished_at - r.submitted_at for r in rs]
        out[crit.value] = (np.mean(ttft), np.mean(lat))
        print(f"  {name:12s} {crit.value}: ttft={np.mean(ttft)*1e3:7.1f} ms "
              f"latency={np.mean(lat)*1e3:7.1f} ms  n={len(rs)} "
              f"saves={sum(r.saves for r in rs)} "
              f"save_ms={sum(r.save_s for r in rs)*1e3:.1f} "
              f"save_bytes={sum(r.save_bytes for r in rs)} "
              f"restore_ms={sum(r.restore_s for r in rs)*1e3:.1f} "
              f"restore_bytes={sum(r.restore_bytes for r in rs)}")
    return out


def run_traffic_real(cfg, params, policy, workload, *, rc, max_len: int,
                     lanes: int = 1, heuristic: str = "crit_aware",
                     max_live_lo=None, prompt_len: int = 8):
    """Open-loop wall-clock drive: the real model serves a CRN arrival
    realization in real time through the admission front door."""
    srv = _make_server(cfg, params, policy, rc=rc, max_len=max_len,
                       lanes=lanes, heuristic=heuristic)
    _warm(srv, [prompt_len])

    rng = np.random.default_rng(0)
    t0 = time.monotonic()

    def make_real(spec):
        # pre-stamp the true arrival instant so front-door queueing is
        # inside measured latency (same contract as the virtual path)
        return Request(rid=spec.rid, priority=spec.priority,
                       prompt=rng.integers(0, cfg.vocab, prompt_len,
                                           dtype=np.int32),
                       max_new_tokens=spec.max_new_tokens,
                       crit=spec.crit, lo_budget_s=spec.lo_budget_s,
                       submitted_at=t0 + spec.t)

    front = FrontDoor(srv, max_live_lo=max_live_lo,
                      make_request_fn=make_real)
    pending = deque(sorted(workload, key=lambda s: (s.t, s.rid)))
    while pending or front.queued or front.live():
        now = time.monotonic() - t0
        while pending and pending[0].t <= now:
            front.arrive(pending.popleft())
        front.pump()
        if front.live():
            srv.step()
        elif pending:                      # idle: sleep to next arrival
            time.sleep(max(0.0, min(pending[0].t - now, 0.05)))
    front.check_conservation()
    return srv.requests


def print_slo(name, row):
    def f(v, scale=1e3, unit="ms"):
        return "   n/a" if v is None else f"{v * scale:7.1f} {unit}"
    print(f"  {name:6s} HI: p50={f(row['hi_p50_latency_s'])} "
          f"p99={f(row['hi_p99_latency_s'])} "
          f"miss={row['hi_miss_rate'] if row['hi_miss_rate'] is not None else 'n/a'}  "
          f"LO: p50={f(row['lo_p50_latency_s'])}  "
          f"goodput={row['goodput_rps']:.2f} rps")


def main_traffic(args):
    """--arrivals != batch: the open-loop traffic front end."""
    lo_process = make_process(args.arrivals, args.rate,
                              trace_path=args.trace)
    hi_process = make_process("poisson", args.hi_rate)
    workload = build_workload(seed=args.seed, lo_process=lo_process,
                              hi_process=hi_process, n_lo=args.n_lo,
                              n_hi=args.n_hi, lo_tokens=args.lo_tokens,
                              hi_tokens=args.hi_tokens)
    mode = "virtual clock" if args.virtual else "wall clock"
    print(f"open-loop {args.arrivals} arrivals ({mode}, "
          f"lanes={args.lanes}, n_lo={args.n_lo}, n_hi={args.n_hi}, "
          f"lo_rate={args.rate}/s, hi_rate={args.hi_rate}/s)")
    if not args.virtual:
        cfg, params, rc = load_model(args.arch)
    rows = {}
    for name, policy in (("mesc", Policy.mesc()),
                         ("np", Policy.non_preemptive())):
        if args.virtual:
            reqs = run_virtual_serving(
                workload, lanes=args.lanes, policy=policy,
                seed=args.seed, heuristic=args.heuristic,
                max_live_lo=args.max_live_lo)
        else:
            reqs = run_traffic_real(
                cfg, params, policy, workload, rc=rc,
                max_len=args.max_len, lanes=args.lanes,
                heuristic=args.heuristic, max_live_lo=args.max_live_lo)
        rows[name] = slo_summary(reqs.values(),
                                 hi_deadline_s=args.hi_deadline)
        print_slo(name, rows[name])
    m, b = rows["mesc"], rows["np"]
    if m["hi_p99_latency_s"] and b["hi_p99_latency_s"]:
        print(f"HI p99 latency np/mesc: "
              f"{b['hi_p99_latency_s'] / m['hi_p99_latency_s']:.1f}x")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="model config (default: tinyllama-1.1b on a "
                         "TPU, tinyllama-1.1b-smoke elsewhere)")
    ap.add_argument("--max-len", type=int, default=512,
                    help="KV-cache length per request: prompt plus "
                         "generated tokens")
    ap.add_argument("--lanes", type=int, default=1,
                    help="virtual accelerator dispatch lanes (partitioned "
                         "MESC when > 1)")
    ap.add_argument("--heuristic", default="crit_aware",
                    choices=("first_fit", "worst_fit", "crit_aware"),
                    help="request -> lane partition heuristic")
    ap.add_argument("--arrivals", default="batch",
                    choices=("batch",) + PROCESS_KINDS,
                    help="batch = legacy closed-batch drive; anything "
                         "else selects the open-loop traffic layer")
    ap.add_argument("--trace", default=None,
                    help="arrival-trace JSON for --arrivals trace "
                         "(see repro.serving.save_trace)")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="LO arrival rate, requests/s")
    ap.add_argument("--hi-rate", type=float, default=0.5,
                    help="HI arrival rate, requests/s")
    ap.add_argument("--n-lo", type=int, default=16)
    ap.add_argument("--n-hi", type=int, default=6)
    ap.add_argument("--lo-tokens", type=int, default=24)
    ap.add_argument("--hi-tokens", type=int, default=6)
    ap.add_argument("--hi-deadline", type=float, default=0.5,
                    help="HI deadline for miss-rate accounting, seconds")
    ap.add_argument("--max-live-lo", type=int, default=None,
                    help="admission cap on concurrently-live LO "
                         "requests (None = open throttle)")
    ap.add_argument("--virtual", action="store_true",
                    help="serve on the deterministic virtual clock + "
                         "service model (no weights; byte-reproducible)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.arrivals == "trace" and not args.trace:
        ap.error("--arrivals trace requires --trace PATH")
    configure_compile_cache()
    if args.arrivals != "batch":
        main_traffic(args)
        return

    cfg, params, rc = load_model(args.arch)
    rng = np.random.default_rng(0)
    lane_kw = dict(lanes=args.lanes, heuristic=args.heuristic, rc=rc,
                   max_len=args.max_len)
    print(f"MESC (instruction-level preemption, lanes={args.lanes}):")
    mesc = summarize("mesc", run(cfg, params, Policy.mesc(),
                                 make_requests(cfg, rng), **lane_kw))
    print("non-preemptive baseline:")
    rng = np.random.default_rng(0)
    base = summarize("np", run(cfg, params, Policy.non_preemptive(),
                               make_requests(cfg, rng), **lane_kw))
    if "HI" in mesc and "HI" in base:
        sp = base["HI"][0] / max(mesc["HI"][0], 1e-9)
        print(f"HI time-to-first-token speedup: {sp:.1f}x")


if __name__ == "__main__":
    main()
