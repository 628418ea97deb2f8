"""Composable decoder LM covering all assigned architecture families.

Families:
  dense / vlm / audio : uniform (attn + SwiGLU/GeGLU) blocks, scan over L
  moe (moe_every=2)   : scan over groups of (attn+dense, attn+MoE)
  mla_moe             : scan over L of (MLA attn + MoE)
  hybrid              : scan over groups (rglru, rglru, local-attn) + tail
  xlstm               : scan over groups of (mLSTM ... sLSTM)

All entry points are pure functions of (cfg, params, ...):
  init_params, step_params (the weights the serving steps read),
  forward (train/prefill), loss_fn, init_cache, prefill, decode_step.

Layer params are stacked along a leading scan dim; caches mirror that
stacking, and decode scans layers with the params as xs and the caches
as a carry.

The dense block parts run under a ``jax.named_scope`` (``embed``,
``attn``, ``mlp``, ``logits``): metadata only, so that a device trace's
operations name the part they belong to.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import recurrent as rec_lib
from repro.models.common import (RuntimeConfig, DEFAULT_RC, apply_norm,
                                 dense_init, norm_params, softmax_xent_sums)

Params = Dict[str, Any]


# ===========================================================================
# Parameter init (single layer; stacked via vmap over keys)
# ===========================================================================

def _stack_init(fn, key, n):
    return jax.vmap(fn)(jax.random.split(key, n))


def _attn_params(cfg: ArchConfig, key, dtype):
    d, dh, hq, hkv = cfg.d_model, cfg.dh, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "ln": norm_params(cfg.norm, d, dtype),
        "wq": dense_init(ks[0], (d, hq * dh), dtype),
        "wk": dense_init(ks[1], (d, hkv * dh), dtype),
        "wv": dense_init(ks[2], (d, hkv * dh), dtype),
        "wo": dense_init(ks[3], (hq * dh, d), dtype,
                         scale=0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((hq * dh,), dtype),
                 bk=jnp.zeros((hkv * dh,), dtype),
                 bv=jnp.zeros((hkv * dh,), dtype))
    return p


def _mla_params(cfg: ArchConfig, key, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "ln": norm_params(cfg.norm, d, dtype),
        "w_q": dense_init(ks[0], (d, H * (m.qk_nope_dim + m.qk_rope_dim)), dtype),
        "w_dkv": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_dim), dtype),
        "c_norm": jnp.zeros((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H * m.qk_nope_dim), dtype),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "w_o": dense_init(ks[4], (H * m.v_head_dim, d), dtype,
                          scale=0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)),
    }


def _mlp_params(cfg: ArchConfig, key, dtype, d_ff=None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "ln": norm_params(cfg.norm, d, dtype),
        "w1": dense_init(ks[0], (d, f), dtype),
        "w3": dense_init(ks[1], (d, f), dtype),
        "w2": dense_init(ks[2], (f, d), dtype,
                         scale=0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)),
    }


def _moe_params(cfg: ArchConfig, key, dtype):
    e = cfg.moe
    d, E, f = cfg.d_model, e.num_experts, e.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "ln": norm_params(cfg.norm, d, dtype),
        "router": dense_init(ks[0], (d, E), dtype, scale=0.02),
        "w1": dense_init(ks[1], (E, d, f), dtype),
        "w3": dense_init(ks[2], (E, d, f), dtype),
        "w2": dense_init(ks[3], (E, f, d), dtype,
                         scale=0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)),
    }
    if e.num_shared > 0:
        sk = jax.random.split(ks[4], 3)
        sf = e.num_shared * f
        p["shared"] = {
            "w1": dense_init(sk[0], (d, sf), dtype),
            "w3": dense_init(sk[1], (d, sf), dtype),
            "w2": dense_init(sk[2], (sf, d), dtype),
        }
    return p


def _rglru_block_params(cfg: ArchConfig, key, dtype):
    r = cfg.rglru
    d, dr, H = cfg.d_model, r.d_rnn, cfg.n_heads
    dh = dr // H
    ks = jax.random.split(key, 8)
    lam = jax.random.uniform(ks[6], (dr,), jnp.float32, 0.65 ** 2, 0.999 ** 2)
    lam = jnp.log(jnp.exp(jnp.sqrt(lam) * 8.0) - 1.0) / 8.0  # inv softplus-ish
    return {
        "ln": norm_params(cfg.norm, d, dtype),
        "w_y": dense_init(ks[0], (d, dr), dtype),          # gated (GeLU) branch
        "w_xb": dense_init(ks[1], (d, dr), dtype),         # recurrence branch
        "conv_w": dense_init(ks[2], (r.conv_width, dr), dtype, scale=0.1),
        "conv_b": jnp.zeros((dr,), dtype),
        "w_a": dense_init(ks[3], (H, dh, dh), dtype),
        "b_a": dense_init(ks[4], (H, dh), dtype),
        "w_x": dense_init(ks[5], (H, dh, dh), dtype),
        "b_x": jnp.zeros((H, dh), dtype),
        "lam": lam.astype(jnp.float32),
        "w_out": dense_init(ks[7], (dr, d), dtype,
                            scale=0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)),
    }


def _mlstm_params(cfg: ArchConfig, key, dtype):
    x = cfg.xlstm
    d, H = cfg.d_model, cfg.n_heads
    inner = int(x.mlstm_proj_factor * d)
    dh = inner // H
    ks = jax.random.split(key, 7)
    return {
        "ln": norm_params(cfg.norm, d, dtype),
        "w_up": dense_init(ks[0], (d, 2 * inner), dtype),   # u, gate z
        "conv_w": dense_init(ks[1], (4, inner), dtype, scale=0.1),
        "conv_b": jnp.zeros((inner,), dtype),
        "w_q": dense_init(ks[2], (inner, inner), dtype),
        "w_k": dense_init(ks[3], (inner, inner), dtype),
        "w_if": dense_init(ks[4], (inner, 2 * H), dtype, scale=0.01),
        "b_if": jnp.concatenate([jnp.zeros((H,), dtype),
                                 jnp.full((H,), 3.0, dtype)]),  # forget-bias
        "gn": jnp.ones((inner,), dtype),
        "w_down": dense_init(ks[6], (inner, d), dtype,
                             scale=0.02 / max(1.0, (2 * cfg.n_layers) ** 0.5)),
    }


def _slstm_params(cfg: ArchConfig, key, dtype):
    x = cfg.xlstm
    d, H = cfg.d_model, cfg.n_heads
    dh = d // H
    ks = jax.random.split(key, 5)
    f_inner = int(x.slstm_proj_factor * d)
    return {
        "ln": norm_params(cfg.norm, d, dtype),
        "ln_mlp": norm_params(cfg.norm, d, dtype),
        "w_in": dense_init(ks[0], (d, 4 * d), dtype),
        "r": dense_init(ks[1], (H, dh, 4 * dh), dtype, scale=0.01),
        "b": jnp.concatenate([jnp.zeros((2 * d,), dtype),
                              jnp.full((d,), 2.0, dtype),
                              jnp.zeros((d,), dtype)]),  # z,i,f(+bias),o
        "gn": jnp.ones((d,), dtype),
        "mlp": {"w1": dense_init(ks[2], (d, f_inner), dtype),
                "w3": dense_init(ks[3], (d, f_inner), dtype),
                "w2": dense_init(ks[4], (f_inner, d), dtype)},
    }


def _hybrid_group_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """(n_groups of (rec,rec,attn), n_tail rec layers)."""
    pat = len(cfg.rglru.block_pattern)  # 3
    return cfg.n_layers // pat, cfg.n_layers % pat


def init_params(cfg: ArchConfig, key, rc: RuntimeConfig = DEFAULT_RC) -> Params:
    dtype = rc.param_dtype
    kg = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.vocab
    params: Params = {
        "embed": dense_init(kg[0], (cfg.n_codebooks * V if cfg.family == "audio"
                                    else V, d), dtype, scale=0.02),
        "out_norm": norm_params(cfg.norm, d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            kg[1], (d, cfg.n_codebooks * V if cfg.family == "audio" else V),
            dtype)

    fam = cfg.family
    if fam in ("dense", "vlm", "audio"):
        params["blocks"] = _stack_init(
            lambda k: {"attn": _attn_params(cfg, k, dtype),
                       "mlp": _mlp_params(cfg, jax.random.fold_in(k, 1), dtype)},
            kg[2], cfg.n_layers)
    elif fam == "moe":
        every = cfg.moe.moe_every
        assert cfg.n_layers % every == 0
        params["blocks"] = _stack_init(
            lambda k: {
                "attn_a": _attn_params(cfg, k, dtype),
                "mlp": _mlp_params(cfg, jax.random.fold_in(k, 1), dtype),
                "attn_b": _attn_params(cfg, jax.random.fold_in(k, 2), dtype),
                "moe": _moe_params(cfg, jax.random.fold_in(k, 3), dtype),
            }, kg[2], cfg.n_layers // every)
    elif fam == "mla_moe":
        params["blocks"] = _stack_init(
            lambda k: {"attn": _mla_params(cfg, k, dtype),
                       "moe": _moe_params(cfg, jax.random.fold_in(k, 1), dtype)},
            kg[2], cfg.n_layers)
    elif fam == "hybrid":
        G, tail = _hybrid_group_counts(cfg)
        params["blocks"] = _stack_init(
            lambda k: {
                "rec0": _rglru_block_params(cfg, k, dtype),
                "mlp0": _mlp_params(cfg, jax.random.fold_in(k, 1), dtype),
                "rec1": _rglru_block_params(cfg, jax.random.fold_in(k, 2), dtype),
                "mlp1": _mlp_params(cfg, jax.random.fold_in(k, 3), dtype),
                "attn": _attn_params(cfg, jax.random.fold_in(k, 4), dtype),
                "mlp2": _mlp_params(cfg, jax.random.fold_in(k, 5), dtype),
            }, kg[2], G)
        params["tail"] = _stack_init(
            lambda k: {"rec": _rglru_block_params(cfg, k, dtype),
                       "mlp": _mlp_params(cfg, jax.random.fold_in(k, 1), dtype)},
            kg[3], tail) if tail else {}
    elif fam == "xlstm":
        every = cfg.xlstm.slstm_every
        assert cfg.n_layers % every == 0
        n_m = every - 1
        params["blocks"] = _stack_init(
            lambda k: {
                "m": _stack_init(lambda kk: _mlstm_params(cfg, kk, dtype),
                                 k, n_m),
                "s": _slstm_params(cfg, jax.random.fold_in(k, 1), dtype),
            }, kg[2], cfg.n_layers // every)
    else:
        raise ValueError(fam)
    return params


# The leaves of the dense, vlm and audio decoder that prefill and
# decode_step read only through a cast to rc.compute_dtype, before any
# arithmetic.  Not among them: norm scales (read in f32) and the audio
# embedding (summed over codebooks before its cast, embed_inputs).
_READ_AS_COMPUTE = frozenset(
    [("lm_head",)]
    + [("blocks", "attn", w) for w in ("wq", "wk", "wv", "wo",
                                       "bq", "bk", "bv")]
    + [("blocks", "mlp", w) for w in ("w1", "w2", "w3")])


def _read_as_compute(cfg: ArchConfig, keys: Tuple) -> bool:
    if cfg.family not in ("dense", "vlm", "audio"):
        return False
    if keys == ("embed",):
        return cfg.family != "audio"
    return keys in _READ_AS_COMPUTE


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    return [x.astype(dtype) for x in leaves]


def step_params(cfg: ArchConfig, params: Params,
                rc: RuntimeConfig = DEFAULT_RC) -> Params:
    """The weights that ``prefill`` and ``decode_step`` should be fed.

    Each leaf they read only through a cast to ``rc.compute_dtype`` is
    cast to it here, once, in one jitted call, so the step programs no
    longer convert it on every call; each einsum reads the values its
    own cast would make.  (XLA may compile the f32-fed program with
    more precision than its casts ask for: on a TPU it reads f32
    embedding bits, so the logits there can differ in the last bf16
    place; on the CPU they are bit-equal.)  Only narrowing casts are
    made: a wider copy would hold more bytes than the weights
    themselves.  Every other leaf is the input's own object, and
    ``params`` itself is returned when nothing is cast (weights already
    in the compute dtype, or f32 compute)."""
    dt = jnp.dtype(rc.compute_dtype)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    picked = [i for i, (path, x) in enumerate(flat)
              if jnp.dtype(x.dtype).itemsize > dt.itemsize
              and _read_as_compute(cfg, tuple(getattr(k, "key", None)
                                              for k in path))]
    if not picked:
        return params
    leaves = [x for _, x in flat]
    for i, y in zip(picked, _cast([leaves[i] for i in picked], dt)):
        leaves[i] = y
    return jax.tree_util.tree_unflatten(tree, leaves)


# ===========================================================================
# Embedding / heads (modality frontends are stubs per the assignment)
# ===========================================================================

@jax.named_scope("embed")
def embed_inputs(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
                 rc: RuntimeConfig):
    """Returns h (B, S, D)."""
    emb = params["embed"]
    if cfg.family == "audio":
        toks = batch["tokens"]                        # (B, S, K)
        K, V = cfg.n_codebooks, cfg.vocab
        offs = jnp.arange(K, dtype=toks.dtype) * V
        h = jnp.sum(jnp.take(emb, toks + offs, axis=0), axis=2)
    elif cfg.family == "vlm" and "vis_embeds" in batch:
        te = jnp.take(emb, batch["tokens"], axis=0)   # (B, S_text, D)
        h = jnp.concatenate([batch["vis_embeds"].astype(te.dtype), te], axis=1)
    else:
        h = jnp.take(emb, batch["tokens"], axis=0)
    h = h.astype(rc.compute_dtype)
    if cfg.family == "hybrid":                        # gemma-style scaling
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


@jax.named_scope("logits")
def lm_logits(cfg: ArchConfig, params: Params, h, rc: RuntimeConfig):
    h = apply_norm(cfg.norm, h, params["out_norm"])
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("...d,dv->...v", h, w.astype(h.dtype))
    if cfg.family == "audio":
        logits = logits.reshape(logits.shape[:-1] + (cfg.n_codebooks, cfg.vocab))
    return logits


# ===========================================================================
# Block bodies (single layer, full-sequence mode)
# ===========================================================================

@jax.named_scope("attn")
def _attn_full(cfg, h, p, positions, *, window=None, make_cache=False):
    x = apply_norm(cfg.norm, h, p["ln"])
    q, k, v = attn_lib.gqa_project_qkv(x, p, cfg, positions)
    if window is not None:
        o = attn_lib.local_attention(q, k, v, window=window)
    else:
        o = attn_lib.flash_attention(q, k, v, causal=True)
    o = o.reshape(o.shape[:2] + (-1,))
    h = h + jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(o.dtype))
    cache = None
    if make_cache:
        if window is not None:
            S = k.shape[1]
            W = window
            if S >= W:
                kc = jnp.roll(k[:, -W:], S % W, axis=1)
                vc = jnp.roll(v[:, -W:], S % W, axis=1)
            else:
                pad = ((0, 0), (0, W - S), (0, 0), (0, 0))
                kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
            cache = (kc, vc)
        else:
            cache = (k, v)
    return h, cache


def _mla_full(cfg, h, p, positions, *, make_cache=False):
    x = apply_norm(cfg.norm, h, p["ln"])
    q, k, v, c, kr = attn_lib.mla_prefill_qkv(x, p, cfg, positions)
    o = attn_lib.flash_attention(q, k, v, causal=True)
    m = cfg.mla
    o = jnp.einsum("bshv,hvd->bsd", o,
                   p["w_o"].astype(o.dtype).reshape(
                       cfg.n_heads, m.v_head_dim, -1))
    h = h + o
    return h, ((c, kr) if make_cache else None)


@jax.named_scope("mlp")
def _mlp_full(cfg, h, p, *, act="swiglu"):
    x = apply_norm(cfg.norm, h, p["ln"])
    y = ffn_lib.swiglu(x, p) if act == "swiglu" else ffn_lib.geglu(x, p)
    return h + y


MOE_METRIC_KEYS = ("moe_aux", "moe_z", "moe_dropped")


def _moe_nometrics(cfg, h, p):
    x = apply_norm(cfg.norm, h, p["ln"])
    y, _ = ffn_lib.moe_apply(x, p, cfg)
    return h + y


def _moe_full(cfg, h, p, aux):
    """Returns (h, aux) with per-layer MoE metrics accumulated into ``aux``."""
    x = apply_norm(cfg.norm, h, p["ln"])
    y, metrics = ffn_lib.moe_apply(x, p, cfg)
    aux = {k: aux[k] + metrics[k] for k in MOE_METRIC_KEYS}
    return h + y, aux


def _rglru_full(cfg, h, p, *, h0=None, conv0=None, make_cache=False):
    r = cfg.rglru
    x = apply_norm(cfg.norm, h, p["ln"])
    y = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, p["w_y"].astype(x.dtype)))
    xb = jnp.einsum("bsd,dr->bsr", x, p["w_xb"].astype(x.dtype))
    xb, conv_state = rec_lib.causal_conv1d(xb, p["conv_w"], p["conv_b"],
                                           state=conv0)
    rec, h_last = rec_lib.rglru_scan(xb, p, cfg.n_heads, h0=h0)
    out = jnp.einsum("bsr,rd->bsd", rec * y, p["w_out"].astype(x.dtype))
    cache = (h_last, conv_state) if make_cache else None
    return h + out, cache


def _mlstm_qkv(cfg, p, x):
    """x (B,S,D) -> q,k,v (B,H,S,dh), log_i/log_f (B,H,S), gate z, conv_state."""
    H = cfg.n_heads
    up = jnp.einsum("bsd,de->bse", x, p["w_up"].astype(x.dtype))
    u, z = jnp.split(up, 2, axis=-1)
    uc, conv_state = rec_lib.causal_conv1d(u, p["conv_w"], p["conv_b"])
    uc = jax.nn.silu(uc)
    inner = u.shape[-1]
    dh = inner // H
    q = jnp.einsum("bse,ef->bsf", uc, p["w_q"].astype(x.dtype))
    k = jnp.einsum("bse,ef->bsf", uc, p["w_k"].astype(x.dtype))
    gates = jnp.einsum("bse,eg->bsg", uc, p["w_if"].astype(x.dtype)) \
        + p["b_if"].astype(x.dtype)
    log_i, f_pre = jnp.split(gates.astype(jnp.float32), 2, axis=-1)
    log_f = jax.nn.log_sigmoid(f_pre)
    tr = lambda t: t.reshape(t.shape[0], t.shape[1], H, dh).transpose(0, 2, 1, 3)
    v = tr(u)
    return tr(q), tr(k), v, log_i.transpose(0, 2, 1), \
        log_f.transpose(0, 2, 1), z, conv_state


def _mlstm_full(cfg, h, p, *, state=None, make_cache=False):
    x = apply_norm(cfg.norm, h, p["ln"])
    q, k, v, log_i, log_f, z, conv_state = _mlstm_qkv(cfg, p, x)
    S = q.shape[2]
    chunk = cfg.xlstm.chunk
    if S > chunk and S % chunk == 0:
        hh, new_state = rec_lib.mlstm_chunkwise(q, k, v, log_i, log_f,
                                                chunk=chunk, state=state)
    else:
        hh = rec_lib.mlstm_parallel(q, k, v, log_i, log_f)
        new_state = rec_lib.mlstm_final_state(q, k, v, log_i, log_f, state) \
            if make_cache else None
    B, H, _, dh = hh.shape
    hh = hh.transpose(0, 2, 1, 3).reshape(B, S, H * dh)
    hh = rec_lib.groupnorm_heads(hh, p["gn"], H)
    out = jnp.einsum("bse,ed->bsd", hh * jax.nn.silu(z),
                     p["w_down"].astype(h.dtype))
    return h + out, ((new_state, conv_state) if make_cache else None)


def _slstm_full(cfg, h, p, *, state=None, make_cache=False):
    x = apply_norm(cfg.norm, h, p["ln"])
    y, new_state = rec_lib.slstm_seq(x, p, cfg.n_heads, state=state)
    y = rec_lib.groupnorm_heads(y, p["gn"], cfg.n_heads)
    h = h + y
    h = h + ffn_lib.geglu(apply_norm(cfg.norm, h, p["ln_mlp"]), p["mlp"])
    return h, (new_state if make_cache else None)


# ===========================================================================
# Full-sequence forward (train / prefill)
# ===========================================================================

def _scan_blocks(carry, params, body):
    """scan over the stacked blocks; ``body(carry, layer_params) -> carry``."""
    def layer(c, p):
        return body(c, p), None

    carry, _ = jax.lax.scan(layer, carry, params["blocks"])
    return carry


def forward(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            rc: RuntimeConfig = DEFAULT_RC, return_hidden: bool = False):
    """Full-sequence forward -> logits (or pre-norm hidden). """
    h = embed_inputs(cfg, params, batch, rc)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    metrics: Dict[str, Any] = {}
    fam = cfg.family

    if fam in ("dense", "vlm", "audio"):
        def body(h, p):
            h, _ = _attn_full(cfg, h, p["attn"], positions)
            return _mlp_full(cfg, h, p["mlp"])
        h = _scan_blocks(h, params, body)
    elif fam == "moe":
        def body(carry, p):
            h, aux = carry
            h, _ = _attn_full(cfg, h, p["attn_a"], positions)
            h = _mlp_full(cfg, h, p["mlp"])
            h, _ = _attn_full(cfg, h, p["attn_b"], positions)
            h, aux = _moe_full(cfg, h, p["moe"], aux)
            return (h, aux)
        aux0 = {k: jnp.zeros((), jnp.float32) for k in MOE_METRIC_KEYS}
        h, aux = _scan_blocks((h, aux0), params, body)
        metrics.update(aux)
    elif fam == "mla_moe":
        def body(carry, p):
            h, aux = carry
            h, _ = _mla_full(cfg, h, p["attn"], positions)
            h, aux = _moe_full(cfg, h, p["moe"], aux)
            return (h, aux)
        aux0 = {k: jnp.zeros((), jnp.float32) for k in MOE_METRIC_KEYS}
        h, aux = _scan_blocks((h, aux0), params, body)
        metrics.update(aux)
    elif fam == "hybrid":
        w = cfg.rglru.window

        def body(h, p):
            h, _ = _rglru_full(cfg, h, p["rec0"])
            h = _mlp_full(cfg, h, p["mlp0"], act="gelu")
            h, _ = _rglru_full(cfg, h, p["rec1"])
            h = _mlp_full(cfg, h, p["mlp1"], act="gelu")
            h, _ = _attn_full(cfg, h, p["attn"], positions, window=w)
            return _mlp_full(cfg, h, p["mlp2"], act="gelu")
        h = _scan_blocks(h, params, body)
        if params.get("tail"):
            tail = params["tail"]
            n_tail = jax.tree_util.tree_leaves(tail)[0].shape[0]
            for i in range(n_tail):
                tp = jax.tree_util.tree_map(lambda a: a[i], tail)
                h, _ = _rglru_full(cfg, h, tp["rec"])
                h = _mlp_full(cfg, h, tp["mlp"], act="gelu")
    elif fam == "xlstm":
        n_m = cfg.xlstm.slstm_every - 1

        def body(h, p):
            for i in range(n_m):
                mp = jax.tree_util.tree_map(lambda a: a[i], p["m"])
                h, _ = _mlstm_full(cfg, h, mp)
            h, _ = _slstm_full(cfg, h, p["s"])
            return h
        h = _scan_blocks(h, params, body)
    else:
        raise ValueError(fam)

    if return_hidden:
        return h, metrics
    return lm_logits(cfg, params, h, rc), metrics


# ===========================================================================
# Serving: cache init / prefill / decode
# ===========================================================================

def _kv_shape(cfg, B, S):
    return (B, S, cfg.n_kv_heads, cfg.dh)


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int,
               rc: RuntimeConfig = DEFAULT_RC) -> Dict[str, Any]:
    """Zero-initialised decode cache (pytree of arrays + 'pos' scalar)."""
    B, dt = batch_size, rc.compute_dtype
    fam = cfg.family
    L = cfg.n_layers
    z = jnp.zeros
    if fam in ("dense", "vlm", "audio"):
        cache = {"ck": z((L,) + _kv_shape(cfg, B, max_len), dt),
                 "cv": z((L,) + _kv_shape(cfg, B, max_len), dt)}
    elif fam == "moe":
        G = L // cfg.moe.moe_every
        kv = (G,) + _kv_shape(cfg, B, max_len)
        cache = {"cka": z(kv, dt), "cva": z(kv, dt),
                 "ckb": z(kv, dt), "cvb": z(kv, dt)}
    elif fam == "mla_moe":
        m = cfg.mla
        cache = {"cc": z((L, B, max_len, m.kv_lora_rank), dt),
                 "ckr": z((L, B, max_len, m.qk_rope_dim), dt)}
    elif fam == "hybrid":
        G, tail = _hybrid_group_counts(cfg)
        r = cfg.rglru
        W = min(r.window, max_len)
        cache = {
            "rh0": z((G, B, r.d_rnn), jnp.float32),
            "rconv0": z((G, B, r.conv_width - 1, r.d_rnn), dt),
            "rh1": z((G, B, r.d_rnn), jnp.float32),
            "rconv1": z((G, B, r.conv_width - 1, r.d_rnn), dt),
            "wk": z((G, B, W, cfg.n_kv_heads, cfg.dh), dt),
            "wv": z((G, B, W, cfg.n_kv_heads, cfg.dh), dt),
        }
        if tail:
            cache["tail"] = {
                "rh": z((tail, B, r.d_rnn), jnp.float32),
                "rconv": z((tail, B, r.conv_width - 1, r.d_rnn), dt),
            }
    elif fam == "xlstm":
        x = cfg.xlstm
        G = L // x.slstm_every
        n_m = x.slstm_every - 1
        inner = int(x.mlstm_proj_factor * cfg.d_model)
        dh = inner // cfg.n_heads
        H, D = cfg.n_heads, cfg.d_model
        cache = {
            "mC": z((G, n_m, B, H, dh, dh), jnp.float32),
            "mn": z((G, n_m, B, H, dh), jnp.float32),
            "mm": jnp.full((G, n_m, B, H), -1e30, jnp.float32),
            "mconv": z((G, n_m, B, 3, inner), dt),
            "sc": z((G, B, D), jnp.float32),
            "sn": z((G, B, D), jnp.float32),
            "sh": z((G, B, D), jnp.float32),
            "sm": jnp.full((G, B, D), -10.0, jnp.float32),
        }
    else:
        raise ValueError(fam)
    cache["pos"] = jnp.zeros((), jnp.int32)
    return cache


def prefill(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            rc: RuntimeConfig = DEFAULT_RC, max_len: Optional[int] = None):
    """Full-sequence pass that also builds the decode cache.

    Returns (last_logits, cache).  Caches are padded to ``max_len`` if given.
    """
    h = embed_inputs(cfg, params, batch, rc)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    fam = cfg.family
    blocks = params["blocks"]
    metrics: Dict[str, Any] = {}

    if fam in ("dense", "vlm", "audio"):
        def body(h, p):
            h, (k, v) = _attn_full(cfg, h, p["attn"], positions,
                                   make_cache=True)
            h = _mlp_full(cfg, h, p["mlp"])
            return h, {"ck": k, "cv": v}
        h, cache = jax.lax.scan(body, h, blocks)
    elif fam == "moe":
        def body(h, p):
            h, (ka, va) = _attn_full(cfg, h, p["attn_a"], positions,
                                     make_cache=True)
            h = _mlp_full(cfg, h, p["mlp"])
            h, (kb, vb) = _attn_full(cfg, h, p["attn_b"], positions,
                                     make_cache=True)
            h = _moe_nometrics(cfg, h, p["moe"])
            return h, {"cka": ka, "cva": va, "ckb": kb, "cvb": vb}
        h, cache = jax.lax.scan(body, h, blocks)
    elif fam == "mla_moe":
        def body(h, p):
            h, (c, kr) = _mla_full(cfg, h, p["attn"], positions,
                                   make_cache=True)
            h = _moe_nometrics(cfg, h, p["moe"])
            return h, {"cc": c, "ckr": kr}
        h, cache = jax.lax.scan(body, h, blocks)
    elif fam == "hybrid":
        w = cfg.rglru.window

        def body(h, p):
            h, (h0, cv0) = _rglru_full(cfg, h, p["rec0"], make_cache=True)
            h = _mlp_full(cfg, h, p["mlp0"], act="gelu")
            h, (h1, cv1) = _rglru_full(cfg, h, p["rec1"], make_cache=True)
            h = _mlp_full(cfg, h, p["mlp1"], act="gelu")
            h, (kc, vc) = _attn_full(cfg, h, p["attn"], positions,
                                     window=w, make_cache=True)
            h = _mlp_full(cfg, h, p["mlp2"], act="gelu")
            return h, {"rh0": h0, "rconv0": cv0, "rh1": h1, "rconv1": cv1,
                       "wk": kc, "wv": vc}
        h, cache = jax.lax.scan(body, h, blocks)
        if params.get("tail"):
            def tbody(h, p):
                h, (hs, cv) = _rglru_full(cfg, h, p["rec"], make_cache=True)
                h = _mlp_full(cfg, h, p["mlp"], act="gelu")
                return h, {"rh": hs, "rconv": cv}
            h, tcache = jax.lax.scan(tbody, h, params["tail"])
            cache["tail"] = tcache
    elif fam == "xlstm":
        n_m = cfg.xlstm.slstm_every - 1

        def body(h, p):
            mC, mn, mm, mcv = [], [], [], []
            for i in range(n_m):
                mp = jax.tree_util.tree_map(lambda a: a[i], p["m"])
                h, st = _mlstm_full(cfg, h, mp, make_cache=True)
                (C, n, m), conv = st
                mC.append(C); mn.append(n); mm.append(m); mcv.append(conv)
            h, s_st = _slstm_full(cfg, h, p["s"], make_cache=True)
            sc, sn, sh, sm = s_st
            return h, {"mC": jnp.stack(mC), "mn": jnp.stack(mn),
                       "mm": jnp.stack(mm), "mconv": jnp.stack(mcv),
                       "sc": sc, "sn": sn, "sh": sh, "sm": sm}
        h, cache = jax.lax.scan(body, h, blocks)
    else:
        raise ValueError(fam)

    if max_len is not None and max_len > S and fam in (
            "dense", "vlm", "audio", "moe", "mla_moe"):
        pad = max_len - S
        cache = jax.tree_util.tree_map(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad))
                              + ((0, 0),) * (a.ndim - 3)), cache)
    cache["pos"] = jnp.asarray(S, jnp.int32)
    logits = lm_logits(cfg, params, h[:, -1:], rc)[:, 0]
    return logits, cache


# --- per-family decode bodies ----------------------------------------------

def _kv_write(c, row, i, pos):
    """Write ``row`` (B, Hkv, Dh) into the stacked cache ``c`` (L, B, S,
    Hkv, Dh) at layer ``i``, position ``pos``: one row-sized update,
    made in place when the caller donates the cache."""
    return jax.lax.dynamic_update_slice(
        c, row[None, :, None].astype(c.dtype), (i, 0, pos, 0, 0))


@jax.named_scope("attn")
def _attn_decode(cfg, h, p, ck, cv, i, pos, positions):
    """Layer ``i``'s attention for one new token.  ``ck``/``cv`` are the
    whole stacked (L, B, S, Hkv, Dh) caches the layer scan carries: the
    new K/V row is written into them at (i, :, pos) and attention reads
    their layer ``i``; no layer's slice is copied out and written back."""
    x = apply_norm(cfg.norm, h, p["ln"])
    q, k, v = attn_lib.gqa_project_qkv(x, p, cfg, positions)
    ck = _kv_write(ck, k[:, 0], i, pos)
    cv = _kv_write(cv, v[:, 0], i, pos)
    o = attn_lib.decode_attention(
        q[:, 0], jax.lax.dynamic_index_in_dim(ck, i, keepdims=False),
        jax.lax.dynamic_index_in_dim(cv, i, keepdims=False), pos)
    o = o.reshape(o.shape[0], 1, -1)
    h = h + jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(o.dtype))
    return h, ck, cv


@jax.named_scope("attn")
def _window_attn_decode(cfg, h, p, ck, cv, pos, positions):
    """Local attention for one new token over one layer's ring cache
    ``ck``/``cv`` (B, W, Hkv, Dh): the row goes to slot ``pos % W``."""
    x = apply_norm(cfg.norm, h, p["ln"])
    q, k, v = attn_lib.gqa_project_qkv(x, p, cfg, positions)
    W = ck.shape[1]
    slot = pos % W
    ck = attn_lib.cache_update(ck, k[:, 0], slot)
    cv = attn_lib.cache_update(cv, v[:, 0], slot)
    o = attn_lib.decode_attention(q[:, 0], ck, cv, jnp.minimum(pos, W - 1))
    o = o.reshape(o.shape[0], 1, -1)
    h = h + jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(o.dtype))
    return h, ck, cv


def _rglru_decode(cfg, h, p, rh, rconv):
    x = apply_norm(cfg.norm, h, p["ln"])
    y = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, p["w_y"].astype(x.dtype)))
    xb = jnp.einsum("bsd,dr->bsr", x, p["w_xb"].astype(x.dtype))
    xb, rconv = rec_lib.causal_conv1d(xb, p["conv_w"], p["conv_b"], state=rconv)
    rec, rh = rec_lib.rglru_step(xb[:, 0], p, cfg.n_heads, rh)
    out = jnp.einsum("br,rd->bd", rec * y[:, 0], p["w_out"].astype(x.dtype))
    return h + out[:, None], rh, rconv


def _mlstm_decode(cfg, h, p, state, conv):
    x = apply_norm(cfg.norm, h, p["ln"])
    H = cfg.n_heads
    up = jnp.einsum("bsd,de->bse", x, p["w_up"].astype(x.dtype))
    u, z = jnp.split(up, 2, axis=-1)
    uc, conv = rec_lib.causal_conv1d(u, p["conv_w"], p["conv_b"], state=conv)
    uc = jax.nn.silu(uc)
    inner = u.shape[-1]
    dh = inner // H
    q = jnp.einsum("bse,ef->bsf", uc, p["w_q"].astype(x.dtype))[:, 0]
    k = jnp.einsum("bse,ef->bsf", uc, p["w_k"].astype(x.dtype))[:, 0]
    gates = (jnp.einsum("bse,eg->bsg", uc, p["w_if"].astype(x.dtype))
             + p["b_if"].astype(x.dtype))[:, 0].astype(jnp.float32)
    log_i, f_pre = jnp.split(gates, 2, axis=-1)
    log_f = jax.nn.log_sigmoid(f_pre)
    rs = lambda t: t.reshape(-1, H, dh)
    hh, state = rec_lib.mlstm_step(rs(q), rs(k), rs(u[:, 0]), log_i, log_f,
                                   state)
    hh = rec_lib.groupnorm_heads(hh.reshape(-1, inner), p["gn"], H)
    out = jnp.einsum("be,ed->bd", hh * jax.nn.silu(z[:, 0]),
                     p["w_down"].astype(h.dtype))
    return h + out[:, None], state, conv


def _scan_layers(body, h, blocks, cache, keys):
    """Layer scan with the decode caches ``keys`` as a *carry* (not
    xs/ys), whole, with the layer's index.

    xs/ys buffers cannot alias in XLA while-loops, which would double the
    multi-GB KV cache; carries alias in place.
    body(h, p, caches, i) -> (h, caches).
    """
    def step(carry, p):
        h, caches, i = carry
        h, caches = body(h, p, caches, i)
        return (h, caches, i + 1), None

    (h, caches, _), _ = jax.lax.scan(
        step, (h, {k: cache[k] for k in keys}, jnp.zeros((), jnp.int32)),
        blocks)
    return h, caches


def _scan_layers_carry(body_kv, h, blocks, cache, keys):
    """``_scan_layers`` for bodies that take one layer's slice of each
    cache and return it whole.
    body_kv(h, p, layer_cache) -> (h, new_layer_cache).
    """
    def body(h, p, caches, i):
        layer = {k: jax.lax.dynamic_index_in_dim(v, i, axis=0, keepdims=False)
                 for k, v in caches.items()}
        h, newl = body_kv(h, p, layer)
        return h, {k: jax.lax.dynamic_update_index_in_dim(
            caches[k], newl[k].astype(caches[k].dtype), i, axis=0)
            for k in caches}

    return _scan_layers(body, h, blocks, cache, keys)


def decode_step(cfg: ArchConfig, params: Params, tokens, cache,
                rc: RuntimeConfig = DEFAULT_RC):
    """One decode step.  tokens (B,) int32 (audio: (B, K)).

    Returns (logits (B, V) or (B, K, V), new_cache)."""
    pos = cache["pos"]
    B = tokens.shape[0]
    batch = {"tokens": tokens[:, None] if tokens.ndim == 1 else tokens[:, None]}
    h = embed_inputs(cfg, params, batch, rc)          # (B, 1, D)
    positions = jnp.full((B, 1), pos)
    fam = cfg.family
    blocks = params["blocks"]
    new_cache = {}

    if fam in ("dense", "vlm", "audio"):
        def body(h, p, c, i):
            h, ck, cv = _attn_decode(cfg, h, p["attn"], c["ck"], c["cv"],
                                     i, pos, positions)
            h = _mlp_full(cfg, h, p["mlp"])
            return h, {"ck": ck, "cv": cv}
        h, kv = _scan_layers(body, h, blocks, cache, ("ck", "cv"))
        new_cache.update(kv)
    elif fam == "moe":
        def body(h, p, c, i):
            h, cka, cva = _attn_decode(cfg, h, p["attn_a"], c["cka"],
                                       c["cva"], i, pos, positions)
            h = _mlp_full(cfg, h, p["mlp"])
            h, ckb, cvb = _attn_decode(cfg, h, p["attn_b"], c["ckb"],
                                       c["cvb"], i, pos, positions)
            x = apply_norm(cfg.norm, h, p["moe"]["ln"])
            y, _ = ffn_lib.moe_apply(x.reshape(1, B, -1), p["moe"], cfg)
            h = h + y.reshape(B, 1, -1)
            return h, {"cka": cka, "cva": cva, "ckb": ckb, "cvb": cvb}
        h, kv = _scan_layers(body, h, blocks, cache,
                             ("cka", "cva", "ckb", "cvb"))
        new_cache.update(kv)
    elif fam == "mla_moe":
        def body(h, p, c):
            x = apply_norm(cfg.norm, h, p["attn"]["ln"])
            out, cc, ckr = attn_lib.mla_decode(x[:, 0], p["attn"], cfg,
                                               c["cc"], c["ckr"], pos)
            h = h + out[:, None]
            x = apply_norm(cfg.norm, h, p["moe"]["ln"])
            y, _ = ffn_lib.moe_apply(x.reshape(1, B, -1), p["moe"], cfg)
            h = h + y.reshape(B, 1, -1)
            return h, {"cc": cc, "ckr": ckr}
        h, kv = _scan_layers_carry(body, h, blocks, cache, ("cc", "ckr"))
        new_cache.update(kv)
    elif fam == "hybrid":
        def body(h, p, c):
            h, rh0, rcv0 = _rglru_decode(cfg, h, p["rec0"], c["rh0"],
                                         c["rconv0"])
            h = _mlp_full(cfg, h, p["mlp0"], act="gelu")
            h, rh1, rcv1 = _rglru_decode(cfg, h, p["rec1"], c["rh1"],
                                         c["rconv1"])
            h = _mlp_full(cfg, h, p["mlp1"], act="gelu")
            h, wk, wv = _window_attn_decode(cfg, h, p["attn"], c["wk"],
                                            c["wv"], pos, positions)
            h = _mlp_full(cfg, h, p["mlp2"], act="gelu")
            return h, {"rh0": rh0, "rconv0": rcv0, "rh1": rh1, "rconv1": rcv1,
                       "wk": wk, "wv": wv}
        h, kv = _scan_layers_carry(body, h, blocks, cache,
                                   ("rh0", "rconv0", "rh1", "rconv1",
                                    "wk", "wv"))
        new_cache.update(kv)
        if params.get("tail"):
            def tbody(h, p, c):
                h, rh, rcv = _rglru_decode(cfg, h, p["rec"], c["rh"],
                                           c["rconv"])
                h = _mlp_full(cfg, h, p["mlp"], act="gelu")
                return h, {"rh": rh, "rconv": rcv}
            h, tkv = _scan_layers_carry(tbody, h, params["tail"],
                                        cache["tail"], ("rh", "rconv"))
            new_cache["tail"] = tkv
    elif fam == "xlstm":
        n_m = cfg.xlstm.slstm_every - 1

        def body(h, p, c):
            mC, mn, mm, mcv = [], [], [], []
            for i in range(n_m):
                mp = jax.tree_util.tree_map(lambda a: a[i], p["m"])
                st = (c["mC"][i], c["mn"][i], c["mm"][i])
                h, st, cv = _mlstm_decode(cfg, h, mp, st, c["mconv"][i])
                mC.append(st[0]); mn.append(st[1]); mm.append(st[2])
                mcv.append(cv)
            x = apply_norm(cfg.norm, h, p["s"]["ln"])
            y, s_st = rec_lib.slstm_seq(x, p["s"], cfg.n_heads,
                                        state=(c["sc"], c["sn"], c["sh"],
                                               c["sm"]))
            y = rec_lib.groupnorm_heads(y, p["s"]["gn"], cfg.n_heads)
            h = h + y
            h = h + ffn_lib.geglu(
                apply_norm(cfg.norm, h, p["s"]["ln_mlp"]), p["s"]["mlp"])
            return h, {"mC": jnp.stack(mC), "mn": jnp.stack(mn),
                       "mm": jnp.stack(mm), "mconv": jnp.stack(mcv),
                       "sc": s_st[0], "sn": s_st[1], "sh": s_st[2],
                       "sm": s_st[3]}
        h, kv = _scan_layers_carry(body, h, blocks, cache,
                                   ("mC", "mn", "mm", "mconv",
                                    "sc", "sn", "sh", "sm"))
        new_cache.update(kv)
    else:
        raise ValueError(fam)

    new_cache["pos"] = pos + 1
    logits = lm_logits(cfg, params, h, rc)[:, 0]
    return logits, new_cache


LOSS_CHUNK = 512
Z_LOSS_COEF = 1e-4


def chunked_xent(cfg: ArchConfig, params: Params, h, labels):
    """Cross-entropy without materializing full-sequence fp32 logits.

    Scans S in chunks; each chunk projects h -> logits and reduces to sums;
    jax.checkpoint makes the backward recompute chunk logits instead of
    saving them (decisive at 150k-200k vocab: full fp32 logits are GBs).
    """
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    B, S = h.shape[0], h.shape[1]
    chunk = LOSS_CHUNK if (S % LOSS_CHUNK == 0) else S
    nc = S // chunk

    def body(carry, xs):
        hc, lc = xs
        logits = jnp.einsum("bsd,dv->bsv", hc, w.astype(hc.dtype))
        if cfg.family == "audio":
            logits = logits.reshape(logits.shape[:-1]
                                    + (cfg.n_codebooks, cfg.vocab))
        t, n_, nv = softmax_xent_sums(logits, lc, z_loss_coef=Z_LOSS_COEF)
        return (carry[0] + t, carry[1] + n_, carry[2] + nv), None

    hcs = jnp.moveaxis(h.reshape(B, nc, chunk, -1), 1, 0)
    lcs = jnp.moveaxis(labels.reshape((B, nc, chunk) + labels.shape[2:]), 1, 0)
    z = jnp.zeros((), jnp.float32)
    (tot, nll, n), _ = jax.lax.scan(jax.checkpoint(body), (z, z, z),
                                    (hcs, lcs))
    n = jnp.maximum(n, 1.0)
    return tot / n, {"nll": nll / n, "ntokens": n}


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            rc: RuntimeConfig = DEFAULT_RC):
    h, metrics = forward(cfg, params, batch, rc, return_hidden=True)
    labels = batch["labels"]
    if cfg.family == "vlm" and "vis_embeds" in batch:
        # patch positions carry no labels
        nf = batch["vis_embeds"].shape[1]
        pad = jnp.full(labels.shape[:1] + (nf,), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    h = apply_norm(cfg.norm, h, params["out_norm"])
    loss, lm_metrics = chunked_xent(cfg, params, h, labels)
    metrics.update(lm_metrics)
    for k in ("moe_aux", "moe_z"):
        if k in metrics:
            loss = loss + metrics[k]
    metrics["loss"] = loss
    return loss, metrics
