"""Systolic-array GEMM as a Pallas TPU kernel — the Gemmini^RT analogue.

The paper's accelerator streams (mvin, preload, compute, mvout) tiles
through a 16x16 systolic array with an explicitly managed scratchpad.  On
TPU the same insight maps to: MXU-aligned (128-multiple) VMEM tiles via
BlockSpec, a fp32 accumulator living in VMEM scratch across the K grid
dimension, and — the MESC-specific part — a **checkpointable** variant
whose accumulator can be written out mid-K ("step_wise_mvout of the
accumulator") and resumed later, giving instruction-level preemption
granularity *inside* a single GEMM:

    acc   = gemm_partial(A, B, acc, k0, k1)   # preempt here, acc -> DRAM
    out   = gemm_partial(A, B, acc, k1, nK)   # resume

Grid (M/bm, N/bn, K/bk), K innermost (sequential on TPU) so the scratch
accumulator carries across K steps.  The M/N grid dimensions are
declared ``parallel`` and K ``arbitrary`` (``dimension_semantics``), so
the Mosaic pipeliner can overlap the K-loop's HBM->VMEM tile fetches
with the MXU work of the previous step instead of serialising the whole
grid; a ``CostEstimate`` (exact GEMM flops/bytes) feeds the scheduler's
overlap heuristics.  Both knobs are compile-time only — interpret-mode
CI and the equivalence tests vs ``kernels/ref.py`` are unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256

# M and N tiles are independent outputs; only K (the accumulation dim)
# must run in order on the TPU's sequential grid.
_DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _gemm_cost(M: int, K: int, N: int, a_dtype, b_dtype,
               out_dtype) -> pl.CostEstimate:
    """Exact cost of C[M,N] = A[M,K] @ B[K,N] for the pipeliner."""
    return pl.CostEstimate(
        flops=2 * M * N * K,
        transcendentals=0,
        bytes_accessed=(M * K * a_dtype.itemsize
                        + K * N * b_dtype.itemsize
                        + M * N * out_dtype.itemsize),
    )


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def systolic_gemm(a, b, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  bk: int = DEFAULT_BK, out_dtype=None,
                  interpret: bool = False):
    """C = A @ B with VMEM-tiled accumulation.  A (M,K), B (K,N)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    out_dtype = out_dtype or a.dtype
    out_sds = jax.ShapeDtypeStruct((M, N), out_dtype)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
                  pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni))],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=out_sds,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_DIM_SEMANTICS),
        cost_estimate=_gemm_cost(M, K, N, a.dtype, b.dtype, out_sds.dtype),
        interpret=interpret,
    )(a, b)


def _gemm_partial_kernel(a_ref, b_ref, acc_in_ref, acc_out_ref, acc_ref,
                         *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = acc_in_ref[...]        # restore saved accumulator

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _out():
        acc_out_ref[...] = acc_ref[...]       # step_wise_mvout


def gemm_partial(a, b, acc, k_begin: int, k_end: int, *,
                 bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 bk: int = DEFAULT_BK, interpret: bool = False):
    """Process K-chunks [k_begin, k_end) of C += A@B, resuming from ``acc``.

    ``acc`` is the fp32 accumulator (M, N) saved at the previous preemption
    point; returns the updated accumulator.  ``k_begin``/``k_end`` are in
    units of bk blocks (static).  The full product is recovered by chaining
    calls until k_end == K // bk and casting.
    """
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert K % bk == 0
    nk_total = K // bk
    assert 0 <= k_begin < k_end <= nk_total
    nk = k_end - k_begin
    a_sl = jax.lax.slice_in_dim(a, k_begin * bk, k_end * bk, axis=1)
    b_sl = jax.lax.slice_in_dim(b, k_begin * bk, k_end * bk, axis=0)
    out_sds = jax.ShapeDtypeStruct((M, N), jnp.float32)
    cost = _gemm_cost(M, nk * bk, N, a.dtype, b.dtype, out_sds.dtype)
    cost = pl.CostEstimate(
        flops=cost.flops, transcendentals=0,
        # the saved accumulator is both read and written
        bytes_accessed=cost.bytes_accessed + M * N * 4)
    return pl.pallas_call(
        functools.partial(_gemm_partial_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
                  pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
                  pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni))],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=out_sds,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_DIM_SEMANTICS),
        cost_estimate=cost,
        interpret=interpret,
    )(a_sl, b_sl, acc)
