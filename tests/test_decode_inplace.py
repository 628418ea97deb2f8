"""The decode step writes each new K/V row into the stacked cache in
place, and the serving decode program consumes (donates) the cache it
is given.  Against the layer-slice decode it replaced (kept here as a
test-local copy), every logit and every cache row is the same bits; a
served run that evicts and restores a request serves the same tokens;
a cache passed to the decode is not usable again.  The CPU compiler
keeps every cache in its plain (L, B, S, Hkv, Dh) order; the order the
TPU compiler picks for Phi-4-mini, head-major, is forced here through
``serving._order`` so that its transposes run on the CPU too."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.scheduler import Policy
from repro.core import serving
from repro.core.serving import MESCServer, Request, _programs
from repro.core.task import Crit
from repro.models import attention as attn_lib
from repro.models import lm
from repro.models.common import CPU_RC, DEFAULT_RC, apply_norm

S, MAX_LEN, STEPS = 8, 16, 6
HEAD_MAJOR = (0, 1, 3, 2, 4)


@pytest.fixture(params=["compiler", "head-major"])
def order(request, monkeypatch):
    """The stored order: the compiler's, or head-major for every K/V."""
    if request.param == "head-major":
        monkeypatch.setattr(serving, "_order", lambda fn, p, cache: {
            k: HEAD_MAJOR if k != "pos" else () for k in cache})
    return request.param


def _cfg(arch, tie=True, mha=False):
    cfg = get_config(arch + "-smoke")
    return dataclasses.replace(
        cfg, tie_embeddings=tie,
        n_kv_heads=cfg.n_heads if mha else cfg.n_kv_heads)


def _layer_slice_decode(cfg, params, tokens, cache, rc):
    """The dense decode step as written before the rows went in place:
    each layer's K/V slice is taken out of the stacked cache, the new
    row goes in as a select over the slice, and the slice is written
    back."""
    pos = cache["pos"]
    h = lm.embed_inputs(cfg, params, {"tokens": tokens[:, None]}, rc)
    positions = jnp.full((tokens.shape[0], 1), pos)

    def body(carry, p):
        h, ck, cv, i = carry
        lk = jax.lax.dynamic_index_in_dim(ck, i, keepdims=False)
        lv = jax.lax.dynamic_index_in_dim(cv, i, keepdims=False)
        a = p["attn"]
        x = apply_norm(cfg.norm, h, a["ln"])
        q, k, v = attn_lib.gqa_project_qkv(x, a, cfg, positions)
        lk = attn_lib.cache_update(lk, k[:, 0], pos)
        lv = attn_lib.cache_update(lv, v[:, 0], pos)
        o = attn_lib.decode_attention(q[:, 0], lk, lv, pos)
        o = o.reshape(o.shape[0], 1, -1)
        h = h + jnp.einsum("bsh,hd->bsd", o, a["wo"].astype(o.dtype))
        h = lm._mlp_full(cfg, h, p["mlp"])
        ck = jax.lax.dynamic_update_index_in_dim(ck, lk, i, axis=0)
        cv = jax.lax.dynamic_update_index_in_dim(cv, lv, i, axis=0)
        return (h, ck, cv, i + 1), None

    (h, ck, cv, _), _ = jax.lax.scan(
        body, (h, cache["ck"], cache["cv"], jnp.zeros((), jnp.int32)),
        params["blocks"])
    logits = lm.lm_logits(cfg, params, h, rc)[:, 0]
    return logits, {"ck": ck, "cv": cv, "pos": pos + 1}


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("rc", [DEFAULT_RC, CPU_RC], ids=["bf16", "f32"])
@pytest.mark.parametrize("mha", [False, True], ids=["gqa", "mha"])
@pytest.mark.parametrize("tie", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("arch", ["olmo-1b", "phi4-mini-3.8b"])
def test_in_place_rows_are_bit_equal_to_layer_slices(arch, tie, mha, rc,
                                                     order):
    """Prefill, then STEPS decode steps fed each step's greedy token:
    the logits and every cache row of the serving decode program are
    the bits of the layer-slice decode, in either stored order."""
    cfg = _cfg(arch, tie, mha)
    params = lm.step_params(
        cfg, lm.init_params(cfg, jax.random.PRNGKey(0), rc), rc)
    decode, prefill = _programs(cfg, rc, MAX_LEN)
    ref = jax.jit(lambda p, t, c: _layer_slice_decode(cfg, p, t, c, rc))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, cfg.vocab)
    logits, cache = prefill(params, {"tokens": toks})
    if order == "head-major":
        assert decode.order["ck"] == decode.order["cv"] == HEAD_MAJOR
    want_cache = jax.tree_util.tree_map(jnp.copy, decode.logical(cache))
    for _ in range(STEPS):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        want, want_cache = ref(params, tok, want_cache)
        logits, cache = decode(params, tok, cache)
        np.testing.assert_array_equal(_f32(logits), _f32(want))
        got = decode.logical(cache)
        for k in ("ck", "cv"):
            np.testing.assert_array_equal(_f32(got[k]), _f32(want_cache[k]))
        assert int(cache["pos"]) == int(want_cache["pos"])
    assert int(cache["pos"]) == S + STEPS


def _reqs(cfg):
    rng = np.random.default_rng(0)

    def req(rid, crit, prio, n):
        return Request(rid=rid, priority=prio, max_new_tokens=n, crit=crit,
                       prompt=rng.integers(0, cfg.vocab, S, dtype=np.int32))
    return [req(0, Crit.LO, 10, 8), req(1, Crit.HI, 0, 4)]


def _serve(cfg, params, slots):
    """LO runs two steps, then HI arrives; with one slot the LO cache is
    saved to the host and restored after HI finishes."""
    srv = MESCServer(cfg, params, policy=Policy.mesc(), rc=DEFAULT_RC,
                     max_len=MAX_LEN, resident_slots=slots)
    lo, hi = _reqs(cfg)
    srv.submit(lo)
    srv.step()
    srv.step()
    srv.submit(hi)
    srv.run()
    return lo, hi


def _greedy(cfg, params, r):
    """The request's tokens from the layer-slice decode, prefill first."""
    rc = DEFAULT_RC
    p = lm.step_params(cfg, params, rc)
    logits, cache = jax.jit(lambda p, b: lm.prefill(
        cfg, p, b, rc, max_len=MAX_LEN))(p, {"tokens": r.prompt[None]})
    step = jax.jit(lambda p, t, c: _layer_slice_decode(cfg, p, t, c, rc))
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < r.max_new_tokens:
        logits, cache = step(p, jnp.asarray([out[-1]], jnp.int32), cache)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_evicted_and_restored_request_serves_the_same_tokens(order):
    cfg = _cfg("olmo-1b")
    params = lm.init_params(cfg, jax.random.PRNGKey(3), DEFAULT_RC)
    lo, hi = _serve(cfg, params, slots=1)
    assert lo.saves == 1 and lo.restore_bytes > 0
    kept_lo, kept_hi = _serve(cfg, params, slots=2)
    assert kept_lo.saves == 0
    assert lo.generated == kept_lo.generated == _greedy(cfg, params, lo)
    assert hi.generated == kept_hi.generated == _greedy(cfg, params, hi)


def test_no_cache_is_passed_to_the_decode_twice(order):
    """A cache handed to the decode is never used again: the server
    keeps the returned one, each cache goes in once, and every one, a
    restored one too, goes in in the stored order and its plain layout.
    Where the backend donates (an accelerator; the v5e compile test
    checks that the cache is aliased) the one handed in is deleted; the
    CPU backend keeps it."""
    cfg = _cfg("tinyllama-1.1b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), CPU_RC)
    decode, prefill = _programs(cfg, CPU_RC, MAX_LEN)
    _, cache = prefill(params, {"tokens": jnp.zeros((1, S), jnp.int32)})
    _, out = decode(params, jnp.zeros((1,), jnp.int32), cache)
    donates = jax.default_backend() != "cpu"
    assert all(a.is_deleted() == donates
               for a in jax.tree_util.tree_leaves(cache))
    plain = lm.init_cache(cfg, 1, MAX_LEN, CPU_RC)["ck"].shape
    shape = tuple(plain[d] for d in decode.order["ck"])
    assert out["ck"].shape == shape
    assert (shape != plain) == (order == "head-major")

    def default_layout(c):
        return all(a.format.layout.major_to_minor == tuple(range(a.ndim))
                   for a in jax.tree_util.tree_leaves(c))

    srv = MESCServer(cfg, params, rc=CPU_RC, max_len=MAX_LEN,
                     resident_slots=1)
    given = []

    def watched(p, t, c):
        given.append(c)
        return decode(p, t, c)
    srv._decode = watched
    lo, hi = _reqs(cfg)
    srv.submit(lo)
    srv.step()
    srv.step()
    srv.submit(hi)
    srv.run()
    assert lo.saves == 1 and lo.done and hi.done
    assert len({id(c) for c in given}) == len(given) > 8
    assert all(c["ck"].shape == shape and default_layout(c) for c in given)
