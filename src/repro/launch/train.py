"""Training driver: data pipeline -> train_step -> checkpoints.

Runs at smoke scale on the CPU, and on one accelerator at any size that
fits it.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b-smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpointing import CheckpointManager
from repro.configs import get_config
from repro.data import batch_for_arch
from repro.models import lm
from repro.models.common import RuntimeConfig, CPU_RC
from repro.optim import OptConfig, init_opt_state
from repro.runtime.trainer import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b-smoke")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    rc = CPU_RC if jax.default_backend() == "cpu" else RuntimeConfig()
    opt_cfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                        decay_steps=args.steps)
    step_fn = jax.jit(make_train_step(cfg, rc, opt_cfg,
                                      microbatches=args.microbatches))

    def init():
        params = lm.init_params(cfg, jax.random.PRNGKey(args.seed), rc)
        return {"params": params, "opt": init_opt_state(params, opt_cfg)}

    start = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, interval=args.ckpt_every)
        state, start, _ = mgr.restore_or_init(
            jax.eval_shape(init), init)
        if start:
            print(f"resumed from step {start}")
    else:
        mgr = None
        state = init()

    params, opt = state["params"], state["opt"]
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n/1e6:.2f}M backend={jax.default_backend()}")
    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 batch_for_arch(cfg, args.seq, args.batch, step,
                                seed=args.seed).items()}
        params, opt, m = step_fn(params, opt, batch)
        if mgr:
            mgr.maybe_save(step + 1, {"params": params, "opt": opt},
                           extra={"data_step": step + 1})
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = (time.time() - t0) / max(step - start + 1, 1)
            print(f"step {step:5d} loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} "
                  f"lr={float(m['lr']):.2e} {dt*1e3:.0f} ms/step",
                  flush=True)
    print("done")


if __name__ == "__main__":
    main()
