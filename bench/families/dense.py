"""The dense decoder family: OLMo-1B, Phi-4-mini.

Token embedding; per layer a pre-norm, multi-head (grouped-query)
attention with rotary positions, a residual add, a pre-norm SwiGLU MLP
and a residual add; a final norm and the output head (the embedding,
where tied).  The norm is RMSNorm, or OLMo's non-parametric LayerNorm
where the file says ``"layer_norm_type": "nonparametric"``.

Weights (:func:`make`) are laid out as the program's dense decoder
reads its parameters (``repro.models.lm``): a tree of stacked
per-layer arrays.  Scales follow the usual decoder initialisation:
truncated normal (+-2 sigma) of standard deviation 0.02, and
0.02 / sqrt(2 L) on the two projections that write into the residual
stream.  An RMSNorm weight is stored as its offset from 1 (zeros), as
the program keeps it.

The reference (:func:`logits_at`) runs one layer at a time from the
stacked weights (each layer is upcast inside its own call), attends in
blocks of query rows, and computes the head only at the rows asked
for, so that it fits beside the served weights of a 3.8B model on one
chip.  Rotary is rotate-half, over ``partial_rotary_factor`` of each
head.

Counts (:func:`flops`, :func:`hbm_bytes`) of one served instruction,
the prefill of a whole prompt or one decode step:

* Matrix products: 2 operations per multiply-add of the projections
  (``q``, ``k``, ``v``, ``o``, the three MLP matrices) for every token
  in the instruction, and of the output head at the last position only.
* Attention: causal.  A prefill of ``S`` tokens scores and mixes
  ``S (S + 1) / 2`` query-key pairs per head; a decode step attends to
  the ``kv_len`` keys up to and including its own position, not to the
  cache's full ``max_len``.
* Bytes: every weight matrix read once per instruction, at the compute
  dtype (bfloat16), whatever dtype it is stored in; the embedding rows
  of the instruction's tokens (where the head is not the embedding);
  the keys and values written for its tokens; for a decode step, the
  keys and values read up to ``kv_len``.

Norms, rotary and softmax arithmetic are left out (a few operations
per element, far below the products).  So a share of a peak computed
from these counts can only be an underestimate of what the chip did.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import COMPUTE_BYTES
from bench.reference import _attention, _mm, _norm, _rope, padded_len
from bench.weights import _normal


def dims(conf: dict) -> dict:
    """The shape numbers of a configuration file, by short name."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    return {"L": conf["num_hidden_layers"], "d": d, "H": h,
            "Hkv": conf["num_key_value_heads"],
            "dh": conf.get("head_dim") or d // h,
            "f": conf["intermediate_size"], "V": conf["vocab_size"],
            "tied": bool(conf["tie_word_embeddings"]),
            "rms": conf.get("layer_norm_type") != "nonparametric"}


# --- the program's configuration ----------------------------------------

def program_config(conf: dict):
    """The program's architecture, with the head tied to the embedding
    or not as the file states it (the program's option)."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(conf["arch"]),
                               tie_embeddings=dims(conf)["tied"])


def check_arch(conf: dict, cfg) -> None:
    """Raise unless the program's configuration is the file's shape."""
    n = dims(conf)
    want = {"family": "dense", "n_layers": n["L"], "d_model": n["d"],
            "n_heads": n["H"], "n_kv_heads": n["Hkv"], "dh": n["dh"],
            "d_ff": n["f"], "vocab": n["V"], "tie_embeddings": n["tied"],
            "rope_theta": conf["rope_theta"], "qkv_bias": False,
            "norm": "rmsnorm" if n["rms"] else "layernorm_nonparam"}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['name']}: the program's {cfg.name} is "
                         f"{got}, the configuration file says {want}")


# --- weights --------------------------------------------------------------

def make(conf: dict, dtype, key):
    n = dims(conf)
    L, d, H, Hkv, dh, f, V = (n[k] for k in
                              ("L", "d", "H", "Hkv", "dh", "f", "V"))
    out_scale = 0.02 / max(1.0, (2 * L) ** 0.5)
    ks = iter(jax.random.split(key, 16))

    def norm(*lead):
        return {"scale": jnp.zeros(lead + (d,), dtype)} if n["rms"] else {}

    params = {
        "embed": _normal(next(ks), (V, d), dtype, 0.02),
        "out_norm": norm(),
        "blocks": {
            "attn": {"ln": norm(L),
                     "wq": _normal(next(ks), (L, d, H * dh), dtype, 0.02),
                     "wk": _normal(next(ks), (L, d, Hkv * dh), dtype, 0.02),
                     "wv": _normal(next(ks), (L, d, Hkv * dh), dtype, 0.02),
                     "wo": _normal(next(ks), (L, H * dh, d), dtype,
                                   out_scale)},
            "mlp": {"ln": norm(L),
                    "w1": _normal(next(ks), (L, d, f), dtype, 0.02),
                    "w3": _normal(next(ks), (L, d, f), dtype, 0.02),
                    "w2": _normal(next(ks), (L, f, d), dtype, out_scale)},
        },
    }
    if not n["tied"]:
        params["lm_head"] = _normal(next(ks), (d, V), dtype, 0.02)
    return params


# --- the plain float32 reference ----------------------------------------

@functools.partial(jax.jit, static_argnames=("conf_items", "quant"))
def _layer(h, blocks, i, *, conf_items, quant):
    conf = dict(conf_items)
    n = dims(conf)
    p = jax.tree.map(lambda a: a[i], blocks)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = _norm(conf, h, p["attn"]["ln"])
    q = _mm(x, p["attn"]["wq"], quant).reshape(S, n["H"], n["dh"])
    k = _mm(x, p["attn"]["wk"], quant).reshape(S, n["Hkv"], n["dh"])
    v = _mm(x, p["attn"]["wv"], quant).reshape(S, n["Hkv"], n["dh"])
    o = _attention(_rope(conf, q, pos), _rope(conf, k, pos), v)
    h = h + _mm(o.reshape(S, -1), p["attn"]["wo"], quant)
    x = _norm(conf, h, p["mlp"]["ln"])
    y = jax.nn.silu(_mm(x, p["mlp"]["w1"], quant)) \
        * _mm(x, p["mlp"]["w3"], quant)
    return h + _mm(y, p["mlp"]["w2"], quant)


@functools.partial(jax.jit, static_argnames=("conf_items", "quant"))
def _head(h_rows, params, *, conf_items, quant):
    conf = dict(conf_items)
    x = _norm(conf, h_rows, params["out_norm"])
    w = params["embed"].T if conf["tie_word_embeddings"] \
        else params["lm_head"]
    return _mm(x, w, quant)


def _items(conf: dict):
    """The hashable part of a configuration the reference reads."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size",
            "num_hidden_layers", "tie_word_embeddings", "layer_norm_type",
            "layer_norm_eps", "rms_norm_eps", "rope_theta",
            "partial_rotary_factor")
    return tuple((k, conf[k]) for k in keys if k in conf)


def logits_at(conf: dict, params, tokens: np.ndarray, rows: np.ndarray,
              quant: str = "none") -> np.ndarray:
    items = _items(conf)
    T = padded_len(len(tokens), conf["serving"]["max_len"])
    toks = np.zeros(T, np.int32)
    toks[:len(tokens)] = tokens
    h = jnp.take(params["embed"], jnp.asarray(toks), axis=0) \
        .astype(jnp.float32)
    for i in range(conf["num_hidden_layers"]):
        h = _layer(h, params["blocks"], jnp.int32(i), conf_items=items,
                   quant=quant)
    out = _head(h[jnp.asarray(rows)], params, conf_items=items, quant=quant)
    return np.asarray(out, np.float32)


# --- counts ---------------------------------------------------------------

def _matrix_params(conf: dict) -> tuple:
    n = dims(conf)
    d, H, Hkv, dh, f = n["d"], n["H"], n["Hkv"], n["dh"], n["f"]
    layer = d * H * dh + 2 * d * Hkv * dh + H * dh * d + 3 * d * f
    return n["L"] * layer, d * n["V"]


def _kv_bytes_per_token(conf: dict) -> int:
    n = dims(conf)
    return 2 * n["L"] * n["Hkv"] * n["dh"] * COMPUTE_BYTES


def flops(conf: dict, kind: str, tokens: int, kv_len: int) -> float:
    n = dims(conf)
    body, head = _matrix_params(conf)
    per_pair = 4 * n["H"] * n["dh"] * n["L"]      # q.k and p.v
    if kind == "prefill":
        pairs = tokens * (tokens + 1) / 2
        return 2.0 * body * tokens + per_pair * pairs + 2.0 * head
    return 2.0 * body + per_pair * kv_len + 2.0 * head


def hbm_bytes(conf: dict, kind: str, tokens: int, kv_len: int) -> float:
    n = dims(conf)
    body, head = _matrix_params(conf)
    weights = (body + head) * COMPUTE_BYTES
    # a tied head reads the whole embedding already
    embed_rows = 0 if n["tied"] else tokens * n["d"] * COMPUTE_BYTES
    kv = _kv_bytes_per_token(conf)
    if kind == "prefill":
        return weights + embed_rows + kv * tokens
    return weights + embed_rows + kv * kv_len
