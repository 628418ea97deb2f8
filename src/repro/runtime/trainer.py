"""The train_step factory.

``make_train_step`` builds the jit-able step:
  grads = grad(loss);  optional microbatch accumulation (lax.scan);
  AdamW update.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import lm
from repro.models.common import RuntimeConfig, DEFAULT_RC
from repro.optim import OptConfig, adamw_update, init_opt_state


def make_train_step(cfg: ArchConfig, rc: RuntimeConfig = DEFAULT_RC,
                    opt_cfg: OptConfig = OptConfig(), *,
                    microbatches: int = 1, accum_dtype=jnp.float32):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum_dtype``: microbatch gradient-accumulator dtype; bf16 halves the
    accumulator HBM for very large models (documented precision trade)."""

    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: lm.loss_fn(cfg, p, batch, rc), has_aux=True)(params)

    def train_step(params, opt_state, batch):
        if microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            k = microbatches

            def resh(a):
                assert a.shape[0] % k == 0, (a.shape, k)
                return a.reshape((k, a.shape[0] // k) + a.shape[1:])

            mbatches = jax.tree_util.tree_map(resh, batch)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)

            def mb_step(carry, b):
                g_acc, loss_acc = carry
                (loss, m), g = grad_fn(params, b)
                g_acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(accum_dtype), g_acc, g)
                return (g_acc, loss_acc + loss), m

            (grads, loss), ms = jax.lax.scan(mb_step, (g0, 0.0), mbatches)
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            loss = loss / k
            metrics = jax.tree_util.tree_map(lambda a: a[-1], ms)

        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def init_train_state(cfg: ArchConfig, key, rc: RuntimeConfig = DEFAULT_RC,
                     opt_cfg: OptConfig = OptConfig()):
    params = lm.init_params(cfg, key, rc)
    return params, init_opt_state(params, opt_cfg)
