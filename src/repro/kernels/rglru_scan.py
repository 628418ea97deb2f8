"""RG-LRU linear recurrence as a Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t over the sequence, with the channel dim blocked
into VMEM lanes and the hidden state carried in VMEM scratch across
(sequential) S blocks — one HBM read of (a, b) and one write of h, instead
of the log-depth associative-scan's repeated passes.

Inputs a, b fp32 (B, S, D) (precomputed gates; see models.recurrent);
h0 (B, D) initial state.  Returns h (B, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, state_ref, *, bs: int):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        state_ref[...] = h0_ref[0]

    h = state_ref[...]                 # (1, bd)

    def step(t, h):
        # row t read from the refs: Mosaic lowers a dynamic ref slice,
        # not a dynamic index into an already-loaded block
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        y_ref[0, row, :] = h
        return h

    h = jax.lax.fori_loop(0, bs, step, h)
    state_ref[...] = h


def rglru_scan_tpu(a, b, h0, *, block_s: int = 256, block_d: int = 256,
                   interpret: bool = False):
    """a,b (B,S,D) fp32; h0 (B,D) -> h (B,S,D)."""
    B, S, D = a.shape
    bs, bd = min(block_s, S), min(block_d, D)
    assert S % bs == 0 and D % bd == 0
    return pl.pallas_call(
        functools.partial(_rglru_kernel, bs=bs),
        grid=(B * (D // bd), S // bs),
        in_specs=[
            pl.BlockSpec((1, bs, bd),
                         lambda bd_i, si: (bd_i // (D // bd), si,
                                           bd_i % (D // bd))),
            pl.BlockSpec((1, bs, bd),
                         lambda bd_i, si: (bd_i // (D // bd), si,
                                           bd_i % (D // bd))),
            # h0 as (B, 1, D): a (1, bd) block of (B, D) breaks the
            # TPU's (8, 128) tiling rule whenever B > 1
            pl.BlockSpec((1, 1, bd),
                         lambda bd_i, si: (bd_i // (D // bd), 0,
                                           bd_i % (D // bd))),
        ],
        out_specs=pl.BlockSpec((1, bs, bd),
                               lambda bd_i, si: (bd_i // (D // bd), si,
                                                 bd_i % (D // bd))),
        out_shape=jax.ShapeDtypeStruct((B, S, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
