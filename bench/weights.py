"""Random weights for a configuration, made on the device from the seed.

The benchmark makes the weights itself, in one jitted call and in the
dtype they are served in, so that the plain reference
(``bench/reference.py``) can read them without taking anything the
program made.  What the tree holds, and how each leaf is drawn, is the
configuration's family's (``bench/families/``); it is laid out as the
program reads its parameters.  :func:`check_layout` compares that
layout with the program's own ``init_params`` by shape alone, so a
program whose layout moved fails at set-up with the leaf named, not
with wrong numbers.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.loadgen import key_words

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def dims(conf: dict, root: Path = spec.ROOT) -> dict:
    """The shape numbers of a configuration file, by short name."""
    return spec.family(conf, root).dims(conf)


def _normal(key, shape, dtype, scale):
    return (scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def make_params(conf: dict, seed: int, root: Path = spec.ROOT):
    """The configuration's weights for ``seed``, on the default device."""
    make = spec.family(conf, root).make
    dtype = DTYPES[conf["serving"]["param_dtype"]]
    key = jnp.asarray(key_words(seed), jnp.uint32)
    return jax.jit(lambda k: make(conf, dtype, k))(key)


def param_shapes(conf: dict, root: Path = spec.ROOT):
    make = spec.family(conf, root).make
    dtype = DTYPES[conf["serving"]["param_dtype"]]
    return jax.eval_shape(lambda k: make(conf, dtype, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def check_layout(conf: dict, program_shapes, root: Path = spec.ROOT) -> None:
    """Raise unless the program's parameter tree has this layout."""
    mine = param_shapes(conf, root)
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
