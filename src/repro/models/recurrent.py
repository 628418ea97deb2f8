"""Recurrent temporal-mixing blocks: RG-LRU (RecurrentGemma/Griffin) and
xLSTM cells (mLSTM with parallel+recurrent forms, sLSTM sequential).

Parallel (training) and recurrent (decode) forms are numerically consistent —
property-tested in tests/test_models.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SQRT_EPS = 1e-8
RGLRU_C = 8.0


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

def block_diag_linear(x, w, b=None):
    """x (..., H, dh_in) @ w (H, dh_in, dh_out)."""
    y = jnp.einsum("...hi,hij->...hj", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def _rglru_coeffs(x, p, n_heads):
    """x (B,S,d_rnn) -> a (gate-modulated decay), b (gated input), fp32."""
    B, S, d = x.shape
    xh = x.reshape(B, S, n_heads, d // n_heads)
    r = jax.nn.sigmoid(block_diag_linear(xh, p["w_a"], p["b_a"])
                       .reshape(B, S, d).astype(jnp.float32))
    i = jax.nn.sigmoid(block_diag_linear(xh, p["w_x"], p["b_x"])
                       .reshape(B, S, d).astype(jnp.float32))
    log_a = -RGLRU_C * r * jax.nn.softplus(p["lam"].astype(jnp.float32))
    a = jnp.exp(log_a)
    a2 = jnp.exp(2.0 * log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a2, SQRT_EPS)) * i * x.astype(jnp.float32)
    return a, b


def rglru_scan(x, p, n_heads, h0=None):
    """Parallel RG-LRU over a sequence via associative scan.

    x (B, S, d_rnn); h0 (B, d_rnn) optional initial state.
    Returns (y (B,S,d_rnn), h_last (B,d_rnn)).
    """
    a, b = _rglru_coeffs(x, p, n_heads)
    if h0 is not None:
        # fold h0 into the first step:  h_1 = a_1 h0 + b_1
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    aa, hh = jax.lax.associative_scan(combine, (a, b), axis=1)
    return hh.astype(x.dtype), hh[:, -1]


def rglru_step(x, p, n_heads, h):
    """One decode step. x (B, d_rnn), h (B, d_rnn) -> (y, h_new)."""
    a, b = _rglru_coeffs(x[:, None], p, n_heads)
    h_new = a[:, 0] * h.astype(jnp.float32) + b[:, 0]
    return h_new.astype(x.dtype), h_new


def causal_conv1d(x, w, b, state=None):
    """Depthwise causal conv.  x (B,S,d), w (W,d).  state (B,W-1,d) for decode.

    Returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype) for i in range(W))
    y = y + b.astype(x.dtype)
    return y, xp[:, -(W - 1):]


# ---------------------------------------------------------------------------
# mLSTM (matrix memory; parallel quadratic + recurrent forms)
# ---------------------------------------------------------------------------

def mlstm_parallel(q, k, v, log_i, log_f):
    """q,k,v (B,H,S,dh); log_i/log_f (B,H,S) fp32. Returns h (B,H,S,dh)."""
    S = q.shape[2]
    dh = q.shape[3]
    lf32 = log_f.astype(jnp.float32)
    li32 = log_i.astype(jnp.float32)
    F = jnp.cumsum(lf32, axis=-1)                       # inclusive
    D = F[..., :, None] - F[..., None, :] + li32[..., None, :]
    mask = jnp.tril(jnp.ones((S, S), bool))
    D = jnp.where(mask, D, -jnp.inf)
    m = jnp.max(D, axis=-1)                             # (B,H,S)
    Ds = jnp.exp(D - m[..., None])
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k,
                        preferred_element_type=jnp.float32) * (dh ** -0.5)
    Sm = scores * Ds
    norm = jnp.maximum(jnp.abs(jnp.sum(Sm, axis=-1)), jnp.exp(-m))
    h = jnp.einsum("bhst,bhtd->bhsd", Sm.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (h / norm[..., None]).astype(q.dtype)


def mlstm_step(q, k, v, log_i, log_f, state):
    """Recurrent mLSTM step (stabilized).

    q,k,v (B,H,dh); log_i/log_f (B,H); state = (C (B,H,dh,dh), n (B,H,dh),
    m (B,H)).  Returns (h (B,H,dh), new_state).
    """
    C, n, m = state
    li = log_i.astype(jnp.float32)
    lf = log_f.astype(jnp.float32)
    m_new = jnp.maximum(lf + m, li)
    i_p = jnp.exp(li - m_new)
    f_p = jnp.exp(lf + m - m_new)
    k32, v32, q32 = (t.astype(jnp.float32) for t in (k, v, q))
    C_new = f_p[..., None, None] * C + i_p[..., None, None] * (
        k32[..., :, None] * v32[..., None, :])
    n_new = f_p[..., None] * n + i_p[..., None] * k32
    dh = q.shape[-1]
    qs = q32 * (dh ** -0.5)
    num = jnp.einsum("bhd,bhde->bhe", qs, C_new)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qs, n_new)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).astype(q.dtype)
    return h, (C_new, n_new, m_new)


def _empty_mlstm_state(B, H, dh, dv):
    return (jnp.zeros((B, H, dh, dv), jnp.float32),
            jnp.zeros((B, H, dh), jnp.float32),
            jnp.full((B, H), -1e30, jnp.float32))


def _chunk_update(k, v, li, lf, F, state):
    """Chunk-end state update. k,v (B,H,W,dh); li/lf/F (B,H,W)."""
    C, n, m = state
    F_tot = F[..., -1]                                   # (B,H)
    decay_s = F_tot[..., None] - F + li                  # (B,H,W)
    m_new = jnp.maximum(m + F_tot, jnp.max(decay_s, axis=-1))
    carry_c = jnp.exp(m + F_tot - m_new)
    w_s = jnp.exp(decay_s - m_new[..., None])
    k32 = k.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    C_new = carry_c[..., None, None] * C + jnp.einsum(
        "bhw,bhwd,bhwe->bhde", w_s, k32, v32)
    n_new = carry_c[..., None] * n + jnp.einsum("bhw,bhwd->bhd", w_s, k32)
    return C_new, n_new, m_new


def mlstm_final_state(q, k, v, log_i, log_f, state=None):
    """State after consuming the whole sequence (for prefill caches)."""
    B, H, S, dh = k.shape
    if state is None:
        state = _empty_mlstm_state(B, H, dh, v.shape[-1])
    F = jnp.cumsum(log_f.astype(jnp.float32), axis=-1)
    return _chunk_update(k, v, log_i.astype(jnp.float32), log_f, F, state)


def mlstm_chunkwise(q, k, v, log_i, log_f, *, chunk: int, state=None):
    """Chunkwise-parallel mLSTM: O(S*chunk) intra + O(S/chunk) recurrence.

    q,k,v (B,H,S,dh); log_i/log_f (B,H,S).  Returns (h, final_state).
    Numerically consistent with mlstm_parallel / mlstm_step (stabilized).
    """
    B, H, S, dh = q.shape
    dv = v.shape[-1]
    assert S % chunk == 0
    Nc = S // chunk
    if state is None:
        state = _empty_mlstm_state(B, H, dh, dv)

    rs = lambda t: t.reshape(B, H, Nc, chunk, -1).transpose(2, 0, 1, 3, 4)
    rg = lambda t: t.astype(jnp.float32).reshape(B, H, Nc, chunk) \
        .transpose(2, 0, 1, 3)
    qs, ks, vs = rs(q), rs(k), rs(v)
    lis, lfs = rg(log_i), rg(log_f)
    scale = dh ** -0.5
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(carry, xs):
        C, n, m = carry
        qc, kc, vc, li, lf = xs
        F = jnp.cumsum(lf, axis=-1)
        D = F[..., :, None] - F[..., None, :] + li[..., None, :]
        D = jnp.where(tri, D, -jnp.inf)
        g = F + m[..., None]                             # inter exponent
        m_t = jnp.maximum(jnp.max(D, axis=-1), g)        # (B,H,W)
        Ds = jnp.exp(D - m_t[..., None])
        inter_w = jnp.exp(g - m_t)                       # (B,H,W)
        scores = jnp.einsum("bhsd,bhtd->bhst", qc, kc,
                            preferred_element_type=jnp.float32) * scale
        Sm = scores * Ds
        q32 = qc.astype(jnp.float32) * scale
        num = jnp.einsum("bhst,bhtd->bhsd", Sm.astype(vc.dtype), vc,
                         preferred_element_type=jnp.float32) \
            + inter_w[..., None] * jnp.einsum("bhsd,bhde->bhse", q32, C)
        den = jnp.abs(jnp.sum(Sm, axis=-1)
                      + inter_w * jnp.einsum("bhsd,bhd->bhs", q32, n))
        den = jnp.maximum(den, jnp.exp(-m_t))
        h = (num / den[..., None]).astype(qc.dtype)
        new_state = _chunk_update(kc, vc, li, lf, F, (C, n, m))
        return new_state, h

    final_state, hs = jax.lax.scan(jax.checkpoint(step), state,
                                   (qs, ks, vs, lis, lfs))
    h = hs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, dv)
    return h, final_state


def groupnorm_heads(x, scale, n_heads, eps: float = 1e-5):
    """Per-head LayerNorm (GroupNorm with groups = heads). x (..., inner)."""
    shp = x.shape
    dh = shp[-1] // n_heads
    xh = x.reshape(shp[:-1] + (n_heads, dh)).astype(jnp.float32)
    mu = jnp.mean(xh, axis=-1, keepdims=True)
    var = jnp.var(xh, axis=-1, keepdims=True)
    y = (xh - mu) * jax.lax.rsqrt(var + eps)
    return (y.reshape(shp) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# sLSTM (scalar memory, recurrent h->gates connections; sequential)
# ---------------------------------------------------------------------------

def slstm_seq(x, p, n_heads, state=None):
    """x (B,S,D). Block-diagonal recurrent weights per head.

    state: (c, n, h, m) each (B, D).  Returns (y (B,S,D), new_state).
    """
    B, S, D = x.shape
    dh = D // n_heads

    wx = p["w_in"].astype(jnp.float32)        # (D, 4D) -> z,i,f,o pre-acts
    r = p["r"].astype(jnp.float32)            # (H, dh, 4*dh) recurrent
    b = p["b"].astype(jnp.float32)            # (4D,)

    if state is None:
        zeros = jnp.zeros((B, D), jnp.float32)
        state = (zeros, zeros, zeros, zeros - 10.0)

    pre_x = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), wx) + b

    def step(carry, pre_t):
        c, n, h, m = carry
        hh = h.reshape(B, n_heads, dh)
        pre_h = jnp.einsum("bhi,hij->bhj", hh, r).reshape(B, 4 * D)
        z_p, i_p, f_p, o_p = jnp.split(pre_t + pre_h, 4, axis=-1)
        z = jnp.tanh(z_p)
        o = jax.nn.sigmoid(o_p)
        m_new = jnp.maximum(f_p + m, i_p)
        i_g = jnp.exp(i_p - m_new)
        f_g = jnp.exp(f_p + m - m_new)
        c_new = f_g * c + i_g * z
        n_new = f_g * n + i_g
        h_new = o * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, h_new, m_new), h_new

    new_state, ys = jax.lax.scan(step, state, jnp.moveaxis(pre_x, 1, 0))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), new_state
