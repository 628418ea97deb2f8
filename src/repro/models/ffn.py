"""FFN variants: SwiGLU MLP and Mixture-of-Experts.

MoE uses sort-based capacity dispatch (no (N,E,C) one-hot tensor):
tokens are argsorted by expert id, packed into per-expert buffers of capacity
C = ceil(N*k*cf/E) via gathers, processed with batched expert einsums, and
combined with a gather of each token's expert outputs.

Routing rows: training/prefill routes per sequence (rows=B, tokens=S);
decode routes over the batch (rows=1, tokens=B) so capacity stays
proportional to live tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _glu(x, p, act):
    h = jnp.einsum("...d,df->...f", x, p["w1"].astype(x.dtype))
    g = jnp.einsum("...d,df->...f", x, p["w3"].astype(x.dtype))
    h = act(h) * g
    return jnp.einsum("...f,fd->...d", h, p["w2"].astype(x.dtype))


def swiglu(x, p):
    """x (..., D) with params w1,w3 (D,F), w2 (F,D)."""
    return _glu(x, p, jax.nn.silu)


def geglu(x, p):
    """Gated-GeLU MLP (RecurrentGemma/Gemma style)."""
    return _glu(x, p, jax.nn.gelu)


def moe_capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(math.ceil(n_tokens * top_k * cf / n_experts))
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def moe_apply(x, p, cfg):
    """Mixture-of-experts FFN.  x (R, N, D) -> (y (R, N, D), aux_metrics).

    R = routing rows (sorted independently), N = tokens per row.
    """
    e = cfg.moe
    R, N, D = x.shape
    E, K = e.num_experts, e.top_k
    C = moe_capacity(N, K, E, e.capacity_factor)

    router_logits = jnp.einsum("rnd,de->rne", x, p["router"].astype(x.dtype))
    router_logits = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, K)                       # (R, N, K)
    if K > 1:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # ---- dispatch bookkeeping (all (R, N*K) int32) ----
    e_flat = eidx.reshape(R, N * K)
    order = jnp.argsort(e_flat, axis=-1, stable=True)           # slots grouped by expert
    sorted_e = jnp.take_along_axis(e_flat, order, axis=-1)
    hist = jnp.sum(jax.nn.one_hot(e_flat, E, dtype=jnp.int32), axis=1)  # (R, E)
    starts = jnp.cumsum(hist, axis=-1) - hist                   # exclusive cumsum
    pos_in_e = jnp.arange(N * K)[None, :] - jnp.take_along_axis(
        starts, sorted_e, axis=-1)
    keep = pos_in_e < C
    tok_sorted = order // K                                      # token id per sorted slot

    # destination-major view: slot (e, c) <- sorted position starts[e] + c
    slot = starts[:, :, None] + jnp.arange(C)[None, None, :]     # (R, E, C)
    slot_valid = jnp.arange(C)[None, None, :] < jnp.minimum(hist, C)[:, :, None]
    slot_c = jnp.clip(slot, 0, N * K - 1)
    src_tok = jnp.take_along_axis(tok_sorted, slot_c.reshape(R, -1), axis=-1)
    src_tok = src_tok.reshape(R, E, C)
    gates_flat = jnp.take_along_axis(
        gates.reshape(R, N * K), order, axis=-1)
    slot_gate = jnp.take_along_axis(gates_flat, slot_c.reshape(R, -1), axis=-1)
    slot_gate = (slot_gate.reshape(R, E, C) * slot_valid).astype(x.dtype)

    # ---- gather -> expert compute -> gather-based combine ----
    # All data movement is take_along_axis over one collapsed dim (implicit
    # batch): these partition on the row dim under GSPMD, while scatter-add
    # or multi-dim advanced indexing would replicate the operands.
    x_e = jnp.take_along_axis(x, src_tok.reshape(R, E * C)[..., None],
                              axis=1).reshape(R, E, C, D)
    x_e = x_e * slot_valid[..., None].astype(x.dtype)
    h = jnp.einsum("recd,edf->recf", x_e, p["w1"].astype(x.dtype))
    g = jnp.einsum("recd,edf->recf", x_e, p["w3"].astype(x.dtype))
    y_e = jnp.einsum("recf,efd->recd", jax.nn.silu(h) * g,
                     p["w2"].astype(x.dtype))
    y_e = y_e * slot_gate[..., None]

    # invert the sort: position of every (token, choice) inside its expert
    inv = jnp.argsort(order, axis=-1)
    pos_unsorted = jnp.take_along_axis(pos_in_e, inv, axis=-1)
    slot_c2 = pos_unsorted.reshape(R, N, K)
    valid_tok = (slot_c2 < C)
    flat_idx = (eidx * C + jnp.clip(slot_c2, 0, C - 1)).reshape(R, N * K)
    picked = jnp.take_along_axis(y_e.reshape(R, E * C, D),
                                 flat_idx[..., None], axis=1)
    picked = picked.reshape(R, N, K, D)                # gated expert outputs
    y = jnp.sum(picked * valid_tok[..., None].astype(x.dtype), axis=2)

    if e.num_shared > 0:
        y = y + swiglu(x, p["shared"])

    # ---- aux losses (Switch-style load balance + router z-loss) ----
    frac = jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=(1, 2))
    mean_p = jnp.mean(probs, axis=1)                             # (R, E)
    aux = E * jnp.mean(jnp.sum(frac * mean_p, axis=-1))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(router_logits, axis=-1)))
    dropped = 1.0 - jnp.sum(slot_valid) / (R * N * K)
    metrics = {"moe_aux": aux * e.aux_coef, "moe_z": z * e.router_z_coef,
               "moe_dropped": dropped}
    return y, metrics
