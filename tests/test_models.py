"""Model-level consistency tests: prefill+decode == full forward for every
family; recurrent parallel/chunkwise/step forms agree; attention variants
against naive oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn_lib
from repro.models import lm
from repro.models import recurrent as rec_lib
from repro.models.common import CPU_RC

ARCHS = ["tinyllama-1.1b", "llama4-maverick-400b-a17b", "deepseek-v2-lite-16b",
         "olmo-1b", "phi4-mini-3.8b", "qwen1.5-110b", "recurrentgemma-2b",
         "llava-next-34b", "xlstm-125m", "musicgen-large"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch + "-smoke")
    key = jax.random.PRNGKey(1)
    B, S, S1 = 2, 12, 8
    params = lm.init_params(cfg, key, CPU_RC)
    if cfg.family == "audio":
        toks = jax.random.randint(key, (B, S, cfg.n_codebooks), 0, cfg.vocab)
    else:
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch, pre = {"tokens": toks}, {"tokens": toks[:, :S1]}
    if cfg.family == "vlm":
        nf = cfg.n_frontend_tokens
        vis = jax.random.normal(key, (B, nf, cfg.d_model), jnp.float32)
        batch = {"tokens": toks[:, :S - nf], "vis_embeds": vis}
        pre = {"tokens": toks[:, :S1 - nf], "vis_embeds": vis}
    full, _ = lm.forward(cfg, params, batch, CPU_RC)
    last, cache = lm.prefill(cfg, params, pre, CPU_RC, max_len=S)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, S1 - 1]),
                               atol=2e-3, rtol=1e-3)
    for t in range(S1, S):
        tok = (batch["tokens"][:, t - (cfg.n_frontend_tokens
                                       if cfg.family == "vlm" else 0)]
               if cfg.family == "vlm" else toks[:, t])
        logits, cache = lm.decode_step(cfg, params, tok, cache, CPU_RC)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, t]),
                                   atol=2e-3, rtol=1e-3)


def test_flash_vs_naive_attention():
    key = jax.random.PRNGKey(0)
    B, Sq, Hq, Hkv, dh = 2, 64, 8, 2, 32
    q = jax.random.normal(key, (B, Sq, Hq, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, Hkv, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sq, Hkv, dh))
    out = attn_lib.flash_attention(q, k, v, causal=True, block_q=16,
                                   block_kv=16)
    # naive
    G = Hq // Hkv
    kr = jnp.repeat(k, G, axis=2)
    vr = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * (dh ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((Sq, Sq), bool))[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_local_attention_window_semantics():
    key = jax.random.PRNGKey(0)
    B, S, H, dh, W = 1, 64, 2, 16, 8
    q = jax.random.normal(key, (B, S, H, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, dh))
    out = attn_lib.local_attention(q, k, v, window=W, block_q=16)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dh ** -0.5)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - W)
    s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_mlstm_forms_agree():
    """parallel == chunkwise == recurrent stepping (stabilized)."""
    key = jax.random.PRNGKey(3)
    B, H, S, dh = 2, 2, 32, 16
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, S, dh))
    k = jax.random.normal(ks[1], (B, H, S, dh))
    v = jax.random.normal(ks[2], (B, H, S, dh))
    log_i = jax.random.normal(ks[3], (B, H, S)) * 2.0
    log_f = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)

    h_par = rec_lib.mlstm_parallel(q, k, v, log_i, log_f)
    h_chk, state_chk = rec_lib.mlstm_chunkwise(q, k, v, log_i, log_f, chunk=8)
    np.testing.assert_allclose(np.asarray(h_par), np.asarray(h_chk),
                               atol=1e-4, rtol=1e-4)
    # recurrent stepping
    st = rec_lib._empty_mlstm_state(B, H, dh, dh)
    outs = []
    for t in range(S):
        o, st = rec_lib.mlstm_step(q[:, :, t], k[:, :, t], v[:, :, t],
                                   log_i[:, :, t], log_f[:, :, t], st)
        outs.append(o)
    h_seq = jnp.stack(outs, axis=2)
    np.testing.assert_allclose(np.asarray(h_par), np.asarray(h_seq),
                               atol=1e-4, rtol=1e-4)
    # chunkwise final state == sequential final state
    for a, b in zip(state_chk, st):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


def test_rglru_scan_vs_step():
    cfg = get_config("recurrentgemma-2b-smoke")
    p = lm._rglru_block_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.rglru.d_rnn))
    y_par, h_last = rec_lib.rglru_scan(x, p, cfg.n_heads)
    h = jnp.zeros((B, cfg.rglru.d_rnn))
    ys = []
    for t in range(S):
        y, h = rec_lib.rglru_step(x[:, t], p, cfg.n_heads, h)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(y_par),
                               np.asarray(jnp.stack(ys, 1)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h), atol=1e-5)


def test_chunked_xent_matches_full():
    cfg = get_config("tinyllama-1.1b-smoke")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), CPU_RC)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    from repro.models.common import apply_norm, softmax_xent
    h, _ = lm.forward(cfg, params, {"tokens": toks}, CPU_RC,
                      return_hidden=True)
    hn = apply_norm(cfg.norm, h, params["out_norm"])
    l1, _ = lm.chunked_xent(cfg, params, hn, toks)
    logits, _ = lm.forward(cfg, params, {"tokens": toks}, CPU_RC)
    l2, _ = softmax_xent(logits, toks, z_loss_coef=lm.Z_LOSS_COEF)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
