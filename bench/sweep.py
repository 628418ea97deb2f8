"""Find the highest HI rate a mixed cell sustains, by a sweep on the chip.

    python3 -m bench.sweep --workload <cell> --rates 4,8,12 --seconds 15 \
        [--seeds 1,2,3]

One process: the cell's weights once (from the first seed), then for
each HI rate and each seed a fresh server and a window of the cell's
traffic at that rate.  Per point one JSON line: HI time to first token
and latency (mean and percentiles), the HI requests still unfinished
when the window closed (a backlog that grows with the window means the
rate is not sustained), and LO tokens per second.  Several seeds at a
rate show how widely its tails spread from seed to seed.  The cell's
rate is fixed in its mix file from this; the benchmark's own runs never
sweep.
"""
from __future__ import annotations

import argparse
import copy
import json
import statistics

from bench import drive, loadgen, run, spec


def point(cell: spec.Cell, params, srv_args, rate: float, seed: int,
          seconds: float) -> dict:
    mix = copy.deepcopy(cell.mix)
    mix["hi"]["rate_per_s"] = rate
    srv = drive.build_server(cell.config, *srv_args)
    drive.warm(srv, loadgen.prompt_lens(mix))
    w = drive.drive(srv, cell.config, mix, seed, seconds)
    close = w.t0 + w.window_s
    inf = float("inf")
    backlog = sum(1 for r in w.hi if w.done_at.get(r.rid, inf) > close)
    ttft = [(w.first_at.get(r.rid, inf) - w.due[r.rid]) * 1e3 for r in w.hi]
    lat = [(w.done_at.get(r.rid, inf) - w.due[r.rid]) * 1e3 for r in w.hi]
    steps = [s for s in w.steps if s.in_window]
    run.free(srv, w)
    q = loadgen.nearest_rank
    return {"rate": rate, "seed": seed, "hi": len(w.hi),
            "ttft_mean_ms": statistics.fmean(ttft),
            "ttft_p50_ms": q(ttft, 0.5), "ttft_p90_ms": q(ttft, 0.9),
            "ttft_p95_ms": q(ttft, 0.95),
            "latency_mean_ms": statistics.fmean(lat),
            "latency_p95_ms": q(lat, 0.95),
            "unfinished_at_close": backlog,
            "lo_tokens_per_s": w.lo_tokens / w.window_s,
            "steps": len(steps),
            "longest_step_ms": max((s.t1 - s.t0 for s in steps),
                                   default=0.0) * 1e3,
            "gc_in_window_s": sum(w.gc_s)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    device = run.device_info(cell.chips)
    run.configure_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    params, srv = run.setup(cell, seeds[0])
    srv_args = (srv.cfg, params, srv.rc)
    del srv
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            out = point(cell, params, srv_args, rate, seed, args.seconds)
            print(json.dumps(dict(out, workload=cell.name, device=device)),
                  flush=True)


if __name__ == "__main__":
    main()
