"""Attention variants: GQA (flash-style blocked), local/windowed, decode,
and MLA (DeepSeek-V2 multi-head latent attention, with weight absorption on
the decode path so the cache stays compressed).

All functions are pure jnp/lax — the Pallas kernels in ``repro.kernels`` are
drop-in replacements for the hot spots (see ops.py); these serve as oracles
and as the portable path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, rope_cos_sin

NEG_INF = -1e30


def _split_heads(x, n_heads, dh):
    return x.reshape(x.shape[:-1] + (n_heads, dh))


# ---------------------------------------------------------------------------
# Blocked causal attention (flash-style online softmax, pure lax.scan)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    block_q: int = 512, block_kv: int = 512, softcap=None):
    """q (B,Sq,Hq,Dh), k/v (B,Skv,Hkv,Dh) -> (B,Sq,Hq,Dh).

    Blocked online-softmax; GQA via head grouping.  KV blocks are scanned with
    masking (exact numerics; causal skipping is done in the Pallas kernel).
    ``q_offset`` is the absolute position of q[0] (for chunked prefill).
    """
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    scale = Dh ** -0.5

    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    nq, nkv = Sq // bq, Skv // bkv
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)

    qb = q.reshape(B, nq, bq, Hq, Dh)
    kb = k.reshape(B, nkv, bkv, Hkv, Dh)
    vb = v.reshape(B, nkv, bkv, Hkv, Dv)

    def q_block(qi, i, kb, vb):
        # qi: (B, bq, Hq, Dh).  KV heads are repeated to Hq per block (tiny)
        # so GQA needs no (Hkv, G) reshape and head sharding stays clean.
        q_pos = q_offset + i * bq + jnp.arange(bq)

        def kv_step(carry, j_kj_vj):
            m, l, acc = carry
            j, kj, vj = j_kj_vj
            if G > 1:
                kj = jnp.repeat(kj, G, axis=2)
                vj = jnp.repeat(vj, G, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            if causal:
                k_pos = j * bkv + jnp.arange(bkv)
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # zero out masked entries: when an entire block is masked the
            # running max still sits at NEG_INF and exp(s - m) would be 1
            p = jnp.exp(s - m_new[..., None]) * (s > NEG_INF * 0.5)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hq, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hq, bq), jnp.float32)
        a0 = jnp.zeros((B, Hq, bq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nkv), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, Hq, bq, Dv)

    # checkpoint each q block: its backward recomputes the KV scan instead of
    # saving O(S^2) score blocks (this is what keeps train-time attention
    # memory O(S * block) like a fused flash kernel)
    q_block_ckpt = jax.checkpoint(q_block)

    def outer(_, xs):
        i, qi = xs
        return None, q_block_ckpt(qi, i, kb, vb)

    _, outs = jax.lax.scan(outer, None,
                           (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    # (nq, B, Hq, bq, Dv) -> (B, nq, bq, Hq, Dv) -> (B, Sq, Hq, Dv)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 3, 2, 4)
    return out.reshape(B, Sq, Hq, Dv).astype(q.dtype)


def local_attention(q, k, v, *, window: int, q_offset=0, block_q: int = 512):
    """Banded causal attention: each query attends the previous ``window``
    keys (inclusive of self).  Exact-FLOP banded gather — O(S * window).

    q (B,Sq,Hq,Dh), k/v (B,Skv,Hkv,Dh); requires Skv == q_offset + Sq
    (the usual prefill layout).
    """
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = Dh ** -0.5
    bq = min(block_q, Sq)
    nq = Sq // bq
    assert Sq % bq == 0
    span = window + bq  # kv span needed per q block
    # pad keys on the left so every block can take a fixed-size slice
    pad = window
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

    qb = q.reshape(B, nq, bq, Hq, Dh)

    def q_block(qi, i, kp, vp):
        start = q_offset + i * bq  # absolute position of first query in block
        ks = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        if G > 1:
            ks = jnp.repeat(ks, G, axis=2)
            vs = jnp.repeat(vs, G, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, ks,
                       preferred_element_type=jnp.float32) * scale
        # absolute positions: query t = start + qi_idx; key t' = start - window + k_idx
        qpos = jnp.arange(bq)[:, None]
        kpos = jnp.arange(span)[None, :] - window
        valid = (kpos <= qpos) & (kpos > qpos - window) \
            & (kpos + start >= 0)
        s = jnp.where(valid[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vs.dtype), vs,
                       preferred_element_type=jnp.float32)
        return o

    q_block_ckpt = jax.checkpoint(q_block)

    def outer(_, xs):
        i, qi = xs
        return None, q_block_ckpt(qi, i, kp, vp)

    _, outs = jax.lax.scan(outer, None,
                           (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    # (nq, B, Hq, bq, Dh) -> (B, Sq, Hq, Dh)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 3, 2, 4)
    return out.reshape(B, Sq, Hq, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, pos):
    """q (B,Hq,Dh); k/v_cache (B,S,Hkv,Dh); pos () current position.

    Memory-bound; positions > pos are masked.
    """
    B, Hq, Dh = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * (Dh ** -0.5)
    mask = jnp.arange(S)[None, None, None, :] <= pos
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, Dh).astype(q.dtype)


def cache_update(cache, new, pos):
    """Write ``new`` (B, Hkv, Dh) into cache (B, S, Hkv, Dh) at ``pos``,
    as a one-hot select over the whole cache (the window ring's write)."""
    S = cache.shape[1]
    hit = (jnp.arange(S) == pos)[None, :, None, None]
    return jnp.where(hit, new[:, None].astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# GQA block-level ops
# ---------------------------------------------------------------------------

def gqa_project_qkv(x, p, cfg, positions):
    """x (B,S,D) -> q (B,S,Hq,Dh), k,v (B,S,Hkv,Dh), RoPE applied."""
    dh = cfg.dh
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = _split_heads(q, cfg.n_heads, dh)
    k = _split_heads(k, cfg.n_kv_heads, dh)
    v = _split_heads(v, cfg.n_kv_heads, dh)
    cos, sin = rope_cos_sin(positions, dh, cfg.rope_theta)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_prefill_qkv(x, p, cfg, positions):
    """Returns q (B,S,H,dn+dr), decompressed k (B,S,H,dn+dr), v (B,S,H,dv),
    plus the compressed cache entries (c_kv, k_rope)."""
    m = cfg.mla
    H = cfg.n_heads
    ckv = jnp.einsum("bsd,dl->bsl", x, p["w_dkv"].astype(x.dtype))
    c, kr = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    from repro.models.common import rmsnorm
    c = rmsnorm(c, p["c_norm"])
    cos, sin = rope_cos_sin(positions, m.qk_rope_dim, cfg.rope_theta)
    kr = apply_rope(kr[:, :, None, :], cos[:, :, None, :], sin[:, :, None, :])[:, :, 0]
    q = jnp.einsum("bsd,dh->bsh", x, p["w_q"].astype(x.dtype))
    q = _split_heads(q, H, m.qk_nope_dim + m.qk_rope_dim)
    qn, qr = jnp.split(q, [m.qk_nope_dim], axis=-1)
    qr = apply_rope(qr, cos[:, :, None, :], sin[:, :, None, :])
    k_nope = jnp.einsum("bsl,lhn->bshn", c,
                        p["w_uk"].astype(x.dtype).reshape(
                            m.kv_lora_rank, H, m.qk_nope_dim))
    v = jnp.einsum("bsl,lhv->bshv", c,
                   p["w_uv"].astype(x.dtype).reshape(
                       m.kv_lora_rank, H, m.v_head_dim))
    q_full = jnp.concatenate([qn, qr], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :], qn.shape[:-1] + (m.qk_rope_dim,))],
        axis=-1)
    return q_full, k_full, v, c, kr


def mla_decode(x, p, cfg, c_cache, kr_cache, pos):
    """Weight-absorbed MLA decode: cache stays compressed.

    x (B,D); c_cache (B,S,lora); kr_cache (B,S,dr) -> out (B,D), new caches.
    """
    m = cfg.mla
    H = cfg.n_heads
    B = x.shape[0]
    ckv = jnp.einsum("bd,dl->bl", x, p["w_dkv"].astype(x.dtype))
    c, kr = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    from repro.models.common import rmsnorm
    c = rmsnorm(c, p["c_norm"])
    posv = jnp.full((B, 1), pos)
    cos, sin = rope_cos_sin(posv, m.qk_rope_dim, cfg.rope_theta)
    kr = apply_rope(kr[:, None, None, :], cos[:, :, None, :],
                    sin[:, :, None, :])[:, 0, 0]
    q = jnp.einsum("bd,dh->bh", x, p["w_q"].astype(x.dtype))
    q = q.reshape(B, H, m.qk_nope_dim + m.qk_rope_dim)
    qn, qr = jnp.split(q, [m.qk_nope_dim], axis=-1)
    qr = apply_rope(qr[:, None], cos[:, :, None, :], sin[:, :, None, :])[:, 0]
    # absorb W_uk into q:  scores_nope = (q_n W_uk^T) . c
    w_uk = p["w_uk"].astype(x.dtype).reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_abs = jnp.einsum("bhn,lhn->bhl", qn, w_uk)
    S_c = c_cache.shape[1]
    hit = (jnp.arange(S_c) == pos)[None, :, None]
    c_cache = jnp.where(hit, c[:, None].astype(c_cache.dtype), c_cache)
    kr_cache = jnp.where(hit, kr[:, None].astype(kr_cache.dtype), kr_cache)
    S = c_cache.shape[1]
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    s = (jnp.einsum("bhl,bsl->bhs", q_abs, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", qr, kr_cache,
                      preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(S)[None, None, :] <= pos
    s = jnp.where(mask, s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bhs,bsl->bhl", pr.astype(c_cache.dtype), c_cache,
                     preferred_element_type=jnp.float32)
    w_uv = p["w_uv"].astype(x.dtype).reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhl,lhv->bhv", o_c.astype(x.dtype), w_uv)
    out = jnp.einsum("bhv,hvd->bd", o,
                     p["w_o"].astype(x.dtype).reshape(H, m.v_head_dim, -1))
    return out, c_cache, kr_cache
