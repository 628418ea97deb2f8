"""Random weights for a configuration, made on the device from the seed.

The benchmark makes the weights itself, in one jitted call and in the
dtype they are served in, so that the plain reference
(``bench/reference.py``) can read them without taking anything the
program made.  They are laid out as the program's dense decoder reads
its parameters (``repro.models.lm``): a tree of stacked per-layer
arrays.  :func:`check_layout` compares that layout with the program's
own ``init_params`` by shape alone, so a program whose layout moved
fails at set-up with the leaf named, not with wrong numbers.

Scales follow the usual decoder initialisation: truncated normal
(+-2 sigma) of standard deviation 0.02, and 0.02 / sqrt(2 L) on the
two projections that write into the residual stream.  An RMSNorm
weight is stored as its offset from 1 (zeros), as the program keeps it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.loadgen import key_words

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


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


def _normal(key, shape, dtype, scale):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def _make(conf: dict, dtype, key):
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


def make_params(conf: dict, seed: int):
    """The configuration's weights for ``seed``, on the default device."""
    dtype = DTYPES[conf["serving"]["param_dtype"]]
    key = jnp.asarray(key_words(seed), jnp.uint32)
    return jax.jit(lambda k: _make(conf, dtype, k))(key)


def param_shapes(conf: dict):
    dtype = DTYPES[conf["serving"]["param_dtype"]]
    return jax.eval_shape(lambda k: _make(conf, dtype, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def check_layout(conf: dict, program_shapes) -> None:
    """Raise unless the program's parameter tree has this layout."""
    mine = param_shapes(conf)
    a = jax.tree_util.tree_flatten_with_path(mine)[0]
    b = jax.tree_util.tree_flatten_with_path(program_shapes)[0]
    sa = {jax.tree_util.keystr(p): (x.shape, np.dtype(x.dtype))
          for p, x in a}
    sb = {jax.tree_util.keystr(p): (x.shape, np.dtype(x.dtype))
          for p, x in b}
    if sa != sb:
        diff = sorted(set(sa.items()) ^ set(sb.items()))
        raise ValueError(f"{conf['name']}: the program's parameter layout "
                         f"differs from the benchmark's: {diff[:6]}")
