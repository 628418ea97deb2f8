"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute via ``interpret=True`` — bit-exact kernel-body
semantics in Python.  On TPU backends they compile natively
(``interpret=False``); ``tests/test_tpu_compile.py`` compiles each one for a
v5e.  The models use their jnp paths: no model calls these wrappers yet.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_tpu
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.rglru_scan import rglru_scan_tpu
from repro.kernels.systolic_gemm import gemm_partial, systolic_gemm


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def gemm(a, b, *, bm=256, bn=256, bk=256):
    return systolic_gemm(a, b, bm=bm, bn=bn, bk=bk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k_begin", "k_end", "bk"))
def gemm_resume(a, b, acc, k_begin, k_end, *, bk=256):
    """Preemptible GEMM step: process K blocks [k_begin, k_end)."""
    return gemm_partial(a, b, acc, k_begin, k_end, bk=bk,
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal=True, block_q=512, block_kv=512):
    return flash_attention_tpu(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q, k_cache, v_cache, pos, *, block_s=1024):
    return decode_attention_tpu(q, k_cache, v_cache, pos, block_s=block_s,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_s", "block_d"))
def rglru(a, b, h0, *, block_s=256, block_d=256):
    return rglru_scan_tpu(a, b, h0, block_s=block_s, block_d=block_d,
                          interpret=_interpret())
