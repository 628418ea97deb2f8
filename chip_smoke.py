"""Bring-up check on TPU: the serving path and the compiled simulator,
each driven once through its own entry point, with results checked.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: phase (c) only

(a) Serving.  ``repro.launch.serve.run`` serves TinyLlama 1.1B at full
    width (random weights from a fixed seed, bf16 runtime config) under
    MESC and under the non-preemptive baseline: four 64-token LO
    requests, then two HI requests arriving mid-flight.  Every request
    must finish; under MESC the HI requests preempt the running LO
    request, under the baseline nothing is preempted.  One preempted
    LO request is replayed through the same prefill/decode functions
    and its logits are compared with a plain f32 forward pass
    (``matmul precision "highest"``) over its prompt and served tokens.
(b) Simulator.  ``benchmarks.perf_sim.check_equivalence`` on a
    64-point fig8-style corpus, with the jit lockstep engine on the
    chip against the NumPy engines on the host: row by row, bit for
    bit, on the nominal (zero-jitter) profile; success rates within
    its binomial bound on the sampled profile.
(c) Sharded simulator (``--chips 4``).  The same gate with the jit
    engine at devices=4, which adds: rows identical to devices=1.

Every number printed is labelled with the device it ran on.  The
script exits non-zero, and prints no result line, unless JAX's first
device is a TPU.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for _p in (str(ROOT / "src"), str(ROOT)):      # repro and benchmarks
    if _p not in sys.path:
        sys.path.insert(0, _p)

ARCH = "tinyllama-1.1b"
MAX_LEN = 512
PROMPT_LEN = 64
N_LO, LO_TOKENS = 4, 64
N_HI, HI_TOKENS = 2, 16

# bf16 serving vs the f32 reference, on logits of O(1) size: the
# largest |difference| over the replayed positions may be this share
# of the reference's largest |logit|.  A reference top-2 margin above
# twice that is "clear": there the served argmax must agree.
LOGIT_RTOL = 0.05

# the nominal fig8 corpus: 2 utilisations x 32 task sets = 64 points
SIM_SPEC = dict(utils=(0.7, 0.9), n_sets=32, duration=2e7, n_tasks=10)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def tpu_device(chips: int) -> dict:
    """The device JAX found, as printed in the result line; exits
    unless it is a TPU host with at least ``chips`` chips."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        fail(f"no TPU: JAX's first device is {dev}")
    if dev["count"] < chips:
        fail(f"--chips {chips} needs {chips} TPU devices, found {dev}")
    return dev


def label(dev: dict) -> str:
    return f"{dev['platform']}:{dev['kind']}x{dev['count']}"


# ---------------------------------------------------------------------
# (a) serving
# ---------------------------------------------------------------------

def serve_phase(dev: dict, arch: str = ARCH) -> None:
    import numpy as np

    from repro.core.scheduler import Policy
    from repro.core.task import Crit
    from repro.launch import serve

    t0 = time.perf_counter()
    cfg, params, rc = serve.load_model(arch)
    print(f"serve,{label(dev)},model={cfg.name},layers={cfg.n_layers},"
          f"d_model={cfg.d_model},compute={rc.compute_dtype.__name__},"
          f"init_s={time.perf_counter() - t0}")
    served = {}
    for name, policy in (("mesc", Policy.mesc()),
                         ("np", Policy.non_preemptive())):
        reqs = serve.make_requests(
            cfg, np.random.default_rng(0), n_lo=N_LO, n_hi=N_HI,
            lo_len=LO_TOKENS, hi_len=HI_TOKENS, prompt_len=PROMPT_LEN)
        t0 = time.perf_counter()
        out = serve.run(cfg, params, policy, reqs, rc=rc, max_len=MAX_LEN)
        wall = time.perf_counter() - t0
        require(len(out) == N_LO + N_HI and all(
            r.done and len(r.generated) == r.max_new_tokens
            for r in out.values()), f"{name}: a request did not finish")
        lo = [r for r in out.values() if r.crit == Crit.LO]
        hi = [r for r in out.values() if r.crit == Crit.HI]
        tokens = sum(len(r.generated) for r in out.values())
        per_tok = sum(r.exec_s for r in out.values()) / tokens
        ttft = max(r.first_token_at - r.submitted_at for r in hi)
        print(f"serve,{label(dev)},{name},requests={len(out)},"
              f"tokens={tokens},wall_s={wall},"
              f"exec_ms_per_token={per_tok * 1e3},"
              f"hi_ttft_max_ms={ttft * 1e3},"
              f"lo_preemptions={sum(r.preemptions for r in lo)},"
              f"lo_saves={sum(r.saves for r in lo)}")
        served[name] = out
    # rid 0 is the LO request running when the HI requests arrive
    for name, out in served.items():
        first_lo = out[0]
        hi = [r for r in out.values() if r.crit == Crit.HI]
        if name == "mesc":
            require(first_lo.preemptions > 0,
                    "mesc: the HI arrivals did not preempt LO")
            require(all(r.first_token_at < first_lo.finished_at
                        for r in hi),
                    "mesc: a HI request waited for the running LO one")
        else:
            require(sum(r.preemptions for r in out.values()) == 0,
                    "np: a request was preempted")
            require(all(r.started_at >= first_lo.finished_at for r in hi),
                    "np: a HI request started before the LO one ended")
    reference_check(dev, cfg, params, rc, served["mesc"][0])


def reference_check(dev: dict, cfg, params, rc, req) -> None:
    """Replay ``req`` through the serving path's prefill and decode and
    compare with a plain f32 forward pass over the same tokens.

    The served tokens continue the prompt greedily: the prefill's
    last-position logits gave ``generated[0]`` and decoding
    ``generated[i]`` gave ``generated[i + 1]``.  One forward pass over
    ``prompt + generated[:-1]`` therefore holds, from position
    ``len(prompt) - 1`` on, the logits behind every served token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.models.common import CPU_RC

    S, n = len(req.prompt), len(req.generated)
    fed = np.concatenate([req.prompt,
                          np.asarray(req.generated[:-1], np.int32)])
    prefill = jax.jit(lambda p, b: lm.prefill(cfg, p, b, rc,
                                              max_len=MAX_LEN))
    decode = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c, rc))
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": jnp.asarray(fed[None, :S])})
    rows = [logits[0]]
    logits, cache = decode(params, jnp.asarray(fed[S:S + 1]), cache)
    rows.append(logits[0])
    jax.block_until_ready(logits)
    first_s = time.perf_counter() - t0       # both compiles included
    t0 = time.perf_counter()
    for i in range(S + 1, S + n - 1):
        logits, cache = decode(params, jnp.asarray(fed[i:i + 1]), cache)
        rows.append(logits[0])
    jax.block_until_ready(rows[-1])
    replay_ms = (time.perf_counter() - t0) / (n - 2) * 1e3
    got = np.asarray(jnp.stack(rows), np.float32)

    def f32_forward(p, toks):
        with jax.default_matmul_precision("highest"):
            return lm.forward(cfg, p, {"tokens": toks}, CPU_RC)[0]
    ref = np.asarray(jax.jit(f32_forward)(params, jnp.asarray(fed[None])),
                     np.float32)[0, S - 1:S - 1 + n]

    require(np.isfinite(got).all() and np.isfinite(ref).all(),
            "non-finite logits")
    require(got.shape == ref.shape == (n, cfg.vocab),
            f"logits shape {got.shape} vs reference {ref.shape}")
    require(list(got.argmax(-1)) == list(req.generated),
            "the replay did not reproduce the served tokens")
    err = float(np.abs(got - ref).max())
    tol = LOGIT_RTOL * float(np.abs(ref).max())
    top2 = np.sort(ref, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * tol
    agree = ref.argmax(-1) == got.argmax(-1)
    print(f"reference,{label(dev)},rid={req.rid},positions={n},"
          f"max_abs_err={err},tol={tol},"
          f"clear_margin_positions={int(clear.sum())},"
          f"argmax_agree_clear={int((agree & clear).sum())},"
          f"argmax_agree_all={int(agree.sum())},"
          f"first_prefill_decode_s={first_s},"
          f"replay_decode_ms_per_token={replay_ms}")
    require(err <= tol, f"logits differ from the f32 reference by {err} "
                        f"> {tol}")
    require(bool(agree[clear].all()),
            "served argmax differs from the reference at a clear margin")


# ---------------------------------------------------------------------
# (b) / (c) simulator
# ---------------------------------------------------------------------

def sim_phase(dev: dict, devices: int, spec=SIM_SPEC) -> None:
    """``benchmarks.perf_sim``'s equivalence gate with the jit engine on
    the chip at ``devices`` devices, then its warm jit timing."""
    from benchmarks.perf_sim import (_engine_fn, _timed, build_corpus,
                                     check_equivalence)
    t0 = time.perf_counter()
    report = check_equivalence(spec, devices=devices, section="chip_smoke")
    check_s = time.perf_counter() - t0
    corpus = build_corpus(spec)
    n = len(corpus[2])
    _, samples = _timed(_engine_fn("jit", *corpus, spec["duration"],
                                   devices=devices))
    med = sorted(samples)[len(samples) // 2]
    print(f"sim,{label(dev)},devices={devices},points={n},"
          f"duration={spec['duration']},check_s={check_s},"
          f"jit_s={med},jit_points_per_s={n / med},"
          f"report={json.dumps(report, sort_keys=True)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-simulator phase")
    args = ap.parse_args()
    from repro.runtime.device_config import configure_compile_cache
    cache = configure_compile_cache()
    dev = tpu_device(args.chips)
    print(f"device,{label(dev)},compile_cache={cache}")
    if args.chips == 4:
        sim_phase(dev, devices=4)
    else:
        serve_phase(dev)
        sim_phase(dev, devices=1)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
