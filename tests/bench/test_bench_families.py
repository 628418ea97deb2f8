"""The harness reads the same through the dense family module as it
read when the dense code was its own: weights, reference logits and
counts pinned bit for bit to what it gave before (CPU)."""
import hashlib
import json

import jax
import numpy as np
import pytest

import benchkit
from bench import counts, reference, spec, weights

PHI = json.loads((spec.ROOT / "bench/configs/phi4-mini-3.8b.json")
                 .read_text())


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed, want", [
    (7, "22fc297dcbdf35117254ba6b1ec7992ff9e02fd87a8ecfb05cc1a157d2eb7c6a"),
    (2**31 + 12345,
     "9daa662b84c9966fb8e251abda7866ad2960433cad1176aa5e5f3986c7b90d83"),
], ids=["seed7", "seed2p31"])
def test_weights_are_the_bits_they_were(seed, want):
    assert _digest(weights.make_params(benchkit.TINY_CONFIG, seed)) == want


@pytest.mark.parametrize("quant, want", [
    ("none",
     "89b4a5ad078e3b7034ba93b65d40e09f901a25a3e0d4894903c8afed61ffa101"),
    ("bf16",
     "621a84ea426bd44408d98ac78876a2879cd311b0421e376fadb0e15ddc631f05"),
], ids=["none", "bf16"])
def test_reference_logits_are_the_bits_they_were(quant, want):
    conf = benchkit.TINY_CONFIG
    params = weights.make_params(conf, 7)
    toks = (np.arange(100) * 37 % 4096).astype(np.int32)
    got = reference.logits_at(conf, params, toks, np.arange(0, 100, 3),
                              quant=quant)
    assert got.shape == (34, 4096) and got.dtype == np.float32
    assert hashlib.sha256(got.tobytes()).hexdigest() == want


@pytest.mark.parametrize("kind, tokens, kv_len, ops, moved", [
    ("prefill", 4096, 4096, 29688848449536.0, 8208515072),
    ("decode", 1, 6000, 10030940160.0, 8458076160),
], ids=["prefill", "decode"])
def test_phi_counts_are_what_they_were(kind, tokens, kv_len, ops, moved):
    assert counts.flops(PHI, kind, tokens, kv_len) == ops
    assert counts.hbm_bytes(PHI, kind, tokens, kv_len) == moved
