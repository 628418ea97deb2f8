"""``bench/counts.py`` against OLMo-1B's numbers worked by hand."""
import pytest

from bench import counts, spec

OLMO = spec.cell("olmo-1b.hi_over_lo_chat").config

# per layer: q, k, v, o are 2048 x 2048; the MLP three 2048 x 8192
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 8192          # 67,108,864
BODY = 16 * LAYER                                   # 1,073,741,824
HEAD = 2048 * 50304                                 # 103,022,592 (tied)
KV_PER_TOKEN = 2 * 16 * 16 * 128 * 2                # k and v, bf16


def test_olmo_decode():
    kv = 1000
    attn = 4 * 16 * 128 * 16 * kv                   # q.k and p.v
    assert counts.flops(OLMO, "decode", 1, kv) == \
        pytest.approx(2 * BODY + 2 * HEAD + attn)
    assert counts.flops(OLMO, "decode", 1, kv) == pytest.approx(
        2_353_528_832 + 131_072_000)
    assert counts.hbm_bytes(OLMO, "decode", 1, kv) == \
        pytest.approx(2 * (BODY + HEAD) + KV_PER_TOKEN * kv)
    assert counts.hbm_bytes(OLMO, "decode", 1, kv) == pytest.approx(
        2_353_528_832 + 131_072_000)


def test_olmo_prefill():
    S = 512
    pairs = S * (S + 1) // 2                        # causal
    want = 2 * BODY * S + 4 * 16 * 128 * 16 * pairs + 2 * HEAD
    assert counts.flops(OLMO, "prefill", S, S) == pytest.approx(want)
    assert counts.flops(OLMO, "prefill", S, S) == pytest.approx(
        1_099_511_627_776 + 17_213_423_616 + 206_045_184)
    assert counts.hbm_bytes(OLMO, "prefill", S, S) == \
        pytest.approx(2 * (BODY + HEAD) + KV_PER_TOKEN * S)


def test_a_batched_step_cannot_pass_the_peak():
    """Weights are counted once per instruction, not per token, so even a
    prefill of the longest prompt needs more than the weight bytes only
    once."""
    one = counts.hbm_bytes(OLMO, "decode", 1, 1)
    long = counts.hbm_bytes(OLMO, "prefill", 1536, 1536)
    assert long < 2 * one
