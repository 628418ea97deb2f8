"""lm.step_params: the weights the serving step programs read, cast to
the compute dtype once.  The step programs must compute the very same
bits from the cast tree as from the caller's tree, and the function
must copy nothing when no leaf needs a cast."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.models.common import CPU_RC, DEFAULT_RC, RuntimeConfig

BF16_RC = DEFAULT_RC                       # f32 weights, bf16 compute
BF16_PARAMS_RC = RuntimeConfig(param_dtype=jnp.bfloat16)
S, MAX_LEN = 8, 16

# dense (RMSNorm; LayerNorm without parameters; q/k/v biases), vlm with
# image embeddings, audio (its embedding is not cast)
CAST_ARCHS = ["tinyllama-1.1b", "olmo-1b", "qwen1.5-110b",
              "llava-next-34b", "musicgen-large"]
OTHER_ARCHS = ["llama4-maverick-400b-a17b", "deepseek-v2-lite-16b",
               "recurrentgemma-2b", "xlstm-125m"]


def _cfg(arch, tie=True):
    return dataclasses.replace(get_config(arch + "-smoke"),
                               tie_embeddings=tie)


def _batch(cfg, key):
    if cfg.family == "audio":
        toks = jax.random.randint(key, (1, S, cfg.n_codebooks), 0, cfg.vocab)
        return {"tokens": toks}, toks[:, -1]
    toks = jax.random.randint(key, (1, S), 0, cfg.vocab)
    if cfg.family == "vlm":
        nf = cfg.n_frontend_tokens
        vis = jax.random.normal(key, (1, nf, cfg.d_model), jnp.float32)
        return {"tokens": toks[:, :S - nf], "vis_embeds": vis}, toks[:, -1]
    return {"tokens": toks}, toks[:, -1]


def _f32(x):
    return np.asarray(x, np.float32)


def _paths(tree):
    return {tuple(k.key for k in path): x for path, x in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("arch", CAST_ARCHS)
def test_steps_on_the_cast_tree_are_bit_equal(arch, tie):
    """Prefill logits and cache, and one decode step from it, are the
    same bits whether the programs read the f32 tree or the cast one."""
    cfg = _cfg(arch, tie)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), BF16_RC)
    cast = lm.step_params(cfg, params, BF16_RC)
    assert cast is not params
    prefill = jax.jit(lambda p, b: lm.prefill(cfg, p, b, BF16_RC,
                                              max_len=MAX_LEN))
    decode = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c, BF16_RC))
    batch, tok = _batch(cfg, jax.random.PRNGKey(1))
    want_logits, want_cache = prefill(params, batch)
    got_logits, got_cache = prefill(cast, batch)
    np.testing.assert_array_equal(_f32(got_logits), _f32(want_logits))
    for k in want_cache:
        np.testing.assert_array_equal(_f32(got_cache[k]),
                                      _f32(want_cache[k]))
    want_logits, want_cache = decode(params, tok, want_cache)
    got_logits, got_cache = decode(cast, tok, got_cache)
    np.testing.assert_array_equal(_f32(got_logits), _f32(want_logits))
    for k in want_cache:
        np.testing.assert_array_equal(_f32(got_cache[k]),
                                      _f32(want_cache[k]))


@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
def test_only_weights_read_through_a_cast_are_cast(tie):
    """Projection, MLP, embedding and head weights go to bf16; norm
    scales keep their dtype and are the caller's own objects."""
    cfg = _cfg("tinyllama-1.1b", tie)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), BF16_RC)
    before, after = _paths(params), _paths(lm.step_params(cfg, params,
                                                          BF16_RC))
    assert before.keys() == after.keys()
    want = {("embed",)} | {("blocks", "attn", w) for w in
                           ("wq", "wk", "wv", "wo")} \
        | {("blocks", "mlp", w) for w in ("w1", "w2", "w3")}
    if not tie:
        want.add(("lm_head",))
    cast = {k for k in after if after[k] is not before[k]}
    assert cast == want
    for k in cast:
        assert after[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            _f32(after[k]), _f32(before[k].astype(jnp.bfloat16)))
    norms = [k for k in before if "ln" in k or k[0] == "out_norm"]
    assert norms
    assert all(after[k].dtype == jnp.float32 for k in norms)


def test_audio_embedding_is_left_alone():
    """The audio embedding is summed over codebooks before its cast, so
    it stays f32; the untied head is cast."""
    cfg = _cfg("musicgen-large", tie=False)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), BF16_RC)
    cast = lm.step_params(cfg, params, BF16_RC)
    assert cast["embed"] is params["embed"]
    assert cast["lm_head"].dtype == jnp.bfloat16


@pytest.mark.parametrize("arch", CAST_ARCHS + OTHER_ARCHS)
def test_nothing_to_cast_returns_the_input(arch):
    """bf16 weights under bf16 compute, and f32 weights under f32
    compute: every leaf is the input's own object (no copy).  Families
    whose reads do arithmetic before the cast are not touched."""
    cfg = _cfg(arch)
    key = jax.random.PRNGKey(0)
    cases = [(lm.init_params(cfg, key, BF16_PARAMS_RC), BF16_RC),
             (lm.init_params(cfg, key, CPU_RC), CPU_RC)]
    if arch in OTHER_ARCHS:
        cases.append((lm.init_params(cfg, key, BF16_RC), BF16_RC))
    for params, rc in cases:
        out = lm.step_params(cfg, params, rc)
        assert out is params
        assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(params),
                                           jax.tree_util.tree_leaves(out)))
