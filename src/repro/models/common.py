"""Shared model components: norms, RoPE, initialisers, runtime config."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """The dtypes the weights are held in and computed in."""
    compute_dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32


DEFAULT_RC = RuntimeConfig()
CPU_RC = RuntimeConfig(compute_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale=None, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(dtype)


def layernorm(x, scale=None, bias=None, eps: float = 1e-5):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dtype)


def apply_norm(kind: str, x, params: Optional[dict]):
    if kind == "rmsnorm":
        return rmsnorm(x, params.get("scale") if params else None)
    if kind == "layernorm":
        return layernorm(x, params.get("scale") if params else None,
                         params.get("bias") if params else None)
    if kind == "layernorm_nonparam":
        return layernorm(x, None, None)
    raise ValueError(f"unknown norm {kind}")


def norm_params(kind: str, d: int, dtype) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {}  # non-parametric


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_cos_sin(positions, dim: int, theta: float):
    """positions (...,) -> cos/sin (..., dim/2), fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin broadcastable (..., S, 1, D/2)."""
    dtype = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def keygen(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_xent_sums(logits, labels, z_loss_coef: float = 1e-4):
    """Sum-reduced xent pieces for chunked accumulation.

    Returns (sum nll+z, sum nll, n_valid) as fp32 scalars."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = lse - ll
    z = z_loss_coef * jnp.square(lse)
    return (jnp.sum(jnp.where(valid, nll + z, 0.0)),
            jnp.sum(jnp.where(valid, nll, 0.0)),
            jnp.sum(valid))


def softmax_xent(logits, labels, z_loss_coef: float = 1e-4, mask=None):
    """Causal-LM cross-entropy with z-loss; labels<0 are ignored.

    logits (..., V) fp-any; labels (...,) int32.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    valid = labels >= 0
    if mask is not None:
        valid = jnp.logical_and(valid, mask.astype(bool))
    safe = jnp.where(valid, labels, 0)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = lse - ll
    z = z_loss_coef * jnp.square(lse)
    per_tok = jnp.where(valid, nll + z, 0.0)
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(per_tok) / n, {"nll": jnp.sum(jnp.where(valid, nll, 0.0)) / n,
                                  "ntokens": n}
