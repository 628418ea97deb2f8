"""The control of the correctness check, read on the chip.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 8

For each seed, in one process: set-up and a short window of the cell's
own traffic at its own sizes, exactly as ``bench.run`` serves it, then
the check's sample of finished requests, read twice against the
float32 reference: the served tokens (the program's reading of
``max_logit_gap``), and the token that the reference computed in the
precision below the configuration's (float8 below bfloat16) puts first
at each of the same positions (the control's reading).  The limit in
the configuration file lies between the largest program reading and
the smallest control reading.  The benchmark's own runs never run
this.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

from bench import check, drive, run, spec


def readings(cell: spec.Cell, seed: int, seconds: float) -> dict:
    params, srv = run.setup(cell, seed)
    window = drive.drive(srv, cell.config, cell.mix, seed, seconds)
    run.free(srv, window)
    del srv
    c = cell.config["correct"]
    finished = [r for r in window.hi + window.lo if r.done]
    picked = check.sample(seed, finished, c["check_requests"],
                          c["check_tokens"])
    t = time.perf_counter()
    program = check.model_gap(cell.config, params, picked)
    ref_s = time.perf_counter() - t
    control = check.model_gap(cell.config, params, picked,
                              quant=check.control_quant(cell.config))
    return {"seed": seed, "program_gap": program, "control_gap": control,
            "requests": len(picked),
            "tokens": sum(len(r.generated) for r in picked),
            "preempted": sum(r.preemptions > 0 for r in picked),
            "reference_s": ref_s, "lo_while_hi": window.lo_while_hi}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    device = run.device_info(cell.chips)
    run.configure_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = readings(cell, seed, args.seconds)
        print(json.dumps(dict(out, workload=cell.name, device=device)),
              flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
