"""Plain float32 reference of a model, for the correctness check.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``, read from the
configuration file alone and importing nothing of the program.  The
layers themselves are the configuration's family's
(``bench/families/<family>.py``); this module holds what every family
builds them from: a matrix product at the reference's or the control's
precision (:func:`_mm`), the norms, rotary positions, causal attention
in blocks of query rows, and the padded length a sequence runs at.

``quant`` makes the control: the same arithmetic with every matrix
product taken in the precision below the one the configuration serves
in -- ``"fp8"`` (e4m3, weights scaled per output column, activations
per row) below bfloat16, ``"bf16"`` below float32.  Its answers must
fail the check that the program passes.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8_e4m3fn
Q_BLOCK = 1024                       # query rows per attention block


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n), both upcast to float32 (after rounding to
    the control's precision, where there is one)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    elif quant == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(conf, x, p):
    if conf.get("layer_norm_type") == "nonparametric":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + conf["layer_norm_eps"])
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                          + conf["rms_norm_eps"])
    return y * (1.0 + p["scale"].astype(jnp.float32))


def _rope(conf, x, pos):
    """Rotate-half rotary over the first ``partial_rotary_factor`` of
    each head; x (S, heads, dh)."""
    dh = x.shape[-1]
    rot = int(dh * conf.get("partial_rotary_factor", 1.0))
    inv = 1.0 / (conf["rope_theta"] ** (
        jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def _attention(q, k, v):
    """Causal attention, in blocks of query rows; q (S, H, dh), k/v
    (S, Hkv, dh).  Keys after a block's last row are never read."""
    S, H, dh = q.shape
    g = H // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    bq = min(Q_BLOCK, S)
    outs = []
    for b in range(S // bq):
        end = (b + 1) * bq
        s = jnp.einsum("qhd,khd->hqk", q[b * bq:end], k[:end],
                       precision=HIGHEST) * dh ** -0.5
        mask = (jnp.arange(end)[None] <= (b * bq + jnp.arange(bq))[:, None])
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:end],
                               precision=HIGHEST))
    return jnp.concatenate(outs, 0)


def padded_len(n: int, max_len: int) -> int:
    """Length the reference runs a sequence at: the next power of two
    from 512 up, at most ``max_len`` (a few shapes, each compiled once;
    trailing padding cannot reach earlier rows of a causal model)."""
    m = 512
    while m < n:
        m *= 2
    return min(m, max_len) if n <= max_len else n


def logits_at(conf: dict, params, tokens: np.ndarray, rows: np.ndarray,
              quant: str = "none", root: Path = spec.ROOT) -> np.ndarray:
    """float32 logits (len(rows), V) of the sequence ``tokens`` at the
    positions ``rows`` (each sees the tokens up to and including it)."""
    return spec.family(conf, root).logits_at(conf, params, tokens, rows,
                                             quant)
