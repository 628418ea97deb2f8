"""Fully-compiled lockstep simulation backend (``select_backend="jit"``).

This module compiles the *entire* lockstep step of the vectorized
engine — candidate min/argmin, every masked event handler (release,
scheduler tick, pending finish/overrun interrupt), the scheduler pass
(mode progression, pick_next, blocking bookkeeping) and the full
context-switch cost model — into one pure ``(carry) -> (carry)``
function under ``jax.jit`` + ``jax.lax.while_loop``.  The host submits
one XLA computation per batch and only observes the final state: the
"streaming accelerator executes the schedule, host only observes"
structure MESC itself argues for.  This removes the NumPy engine's
fixed per-step host-call budget (~300 NumPy calls per lockstep
iteration) that capped campaign throughput regardless of batch width.

RNG-equivalence contract
------------------------
The event/NumPy engines draw demands from sequential per-point
``np.random.Generator`` streams whose call count is data-dependent —
host RNG inside the loop, the exact structure a compiled loop cannot
replicate.  The jit backend replaces those with *counter-based* draws:
a splitmix64 hash of ``(seed, task, release_index)`` yields the two
uniforms of each accepted release (``jax.random.fold_in``'s threefry
would be semantically equivalent but costs ~50 extra kernels per
lockstep step on CPU).  Consequences:

  * **statistical equivalence** under demand jitter: same release
    phases (still drawn host-side from the point's ``default_rng(seed)``
    in the NumPy engine's order), identical demand *distributions*, but
    different demand *realizations* — per-point trajectories diverge
    while every corpus-level statistic (success rates, blocking, mode
    residency) agrees within sampling error.  Pinned by
    ``tests/test_simulator_jit.py`` and gated in CI;
  * **exact equivalence** on the degenerate zero-jitter profile
    (``demand_profile="nominal"``: demand == C_LO, no in-loop draws
    exist): metrics match the NumPy vec engine bit-for-bit, pinned per
    run and gated in CI.

``JIT_SIM_SEMANTICS_VERSION`` salts campaign cache keys for jit points
(``repro.experiments.spec``), so jit results never collide with event-
or vec-engine cache entries.

Grouped carry layout
--------------------
XLA:CPU pays a ~flat dispatch cost per emitted kernel inside a
``while_loop`` body, so the loop carry is grouped into a handful of
dtype-homogeneous tensors, each written by ONE fused pass per step,
instead of the ~38 individually-updated per-field arrays of the first
jit engine (measured kernel counts: ``lockstep_kernel_count``, logged
into ``BENCH_sim.json`` by ``benchmarks/perf_sim.py``):

  * ``flags`` — one ``(P, T)`` int32 *bitfield* holding all eight small
    per-task state fields (status, pc, cause, budget_overrun,
    data_in_accel, released_in_hi, ctx_valid, ctx_kept) plus the
    per-task release counter: one gather yields every field of a task,
    and one 5-write read-modify-write chain replaces eight separate
    masked-write kernels at *fewer* total element passes;
  * six ``(P, T)`` float64 event/time arrays (exec_cy, demand,
    job_deadline, blocked_since, next_release, tick_release) — kept
    separate on purpose: stacking them into one ``(P, 6, T)`` block
    measures *slower* on XLA:CPU (the concatenate defeats loop fusion);
  * four ``(P, T)`` int32 byte-count arrays (res_bytes, acc_bytes,
    ctx_acc, ctx_spad; residency cap 256 KiB and accumulator cap
    64 KiB do not fit one int32 together);
  * the pending-interrupt table as ``(P, K)`` float64 ``ev_time`` plus
    one ``(P, K)`` int32 ``ev_pay`` payload (``tid * 4 + kind``),
    merging the old tid/kind pair;
  * ``pi`` — one packed ``(P, 24)`` int32 per-point block: mode,
    running task, locked banks, resident-LO count, active/HI counts,
    alive + overflow bits, then the 16 int metric counters; written by
    ONE fused column-onehot where-chain + add-chain (assembling the
    same block via stack/concatenate measures ~2.7x slower per step —
    XLA:CPU materializes concat operands as separate thunks);
  * ``pf`` — one packed ``(P, 14)`` float64 per-point block: clock,
    accelerator-free time, run-started stamp, mode stamp, CS-tick
    time, then the 9 float metric accumulators; same single fused
    write.

Stale-interrupt pruning (the step's compaction pass)
----------------------------------------------------
A pending finish/overrun entry ``(tid, t_e)`` is *provably dead* — it
can never pass the firing guard ``running == tid and status[tid] ==
RUNNING`` in any future — when, at the end of a step, task ``tid`` has
no live job (status PENDING) and ``t_e < next_release[tid]``: a
PENDING task can only become RUNNING at/after its next release, so at
time ``t_e`` it is still PENDING and the guard fails.  In the event
engine a guard-failing pop is a pure heap pop — no advance, no metric,
no state change — so removing the entry early is unobservable
(bit-exactness vs the unpruned NumPy engine pinned by the nominal CI
gate and a hypothesis property test).  The common producer of such
entries is a HI job whose sampled demand stays below C_LO (probability
``1 - overrun_prob`` per HI job): its overrun timer at
``dispatch + (C_LO - exec)`` outlives the finish at
``dispatch + (demand - exec)``, and once the job completes the task is
PENDING with a next release typically far beyond the timer.  Every
*other* stale-entry class fires strictly before its superseding event
(within one job, ``at + rem`` and ``at + C_LO - exec`` are
nondecreasing across re-dispatches, because execution time gained
never exceeds wall time elapsed) while its task may be running again —
those entries MUST be replayed, because their guarded pop calls the
advance and checkpoints the integer-floored residency growth of
``note_execution``; the pruning pass keeps them, exactly as the NumPy
engines replay them.  Pruning both shrinks the fixed-width table's
common-case occupancy (making the double-on-overflow retry ladder
rarer) and removes the dead entries' no-op pops from the lockstep
(fewer ``while_loop`` iterations).

Implementation notes
--------------------
  * Static per-batch tables (priorities, periods, program boundary
    tables) are traced arguments, so one compilation serves every batch
    of the same shape/policy class.
  * The pending finish/overrun interrupt table is fixed-width (XLA
    needs static shapes).  A push into a full table sets a per-point
    overflow flag; the affected points are re-run in small padded
    sub-batches at doubled widths (``_run_chunk``) — counter-based RNG
    makes every retry bit-deterministic and results independent of
    batch composition.  A point still overflowing at the maximum width
    raises a point-identified error instead of returning metrics from
    a saturated table.  ``REPRO_JIT_TABLE_WIDTH`` /
    ``REPRO_JIT_TABLE_MAX`` override the ladder bounds (CI shrinks
    them to exercise the ladder and the error path every run).
  * Scheduler aggregates (active/HI counts, locked banks, resident-LO
    counts) ride in the packed ``pi`` block and are updated
    incrementally at the NumPy engine's sites; pick_next keys are
    rank-compressed int32.
  * Batches are dispatched as *device superchunks*: ``shard_map`` over
    a 1-D mesh of logical host devices (``REPRO_DEVICES``,
    ``runtime.device_config``) splits the point axis of one
    ``devices x 64`` superchunk so every logical device runs its own
    copy of the while_loop on its point-shard — simulation points are
    independent, so the mapped body has no collectives and each
    device's loop halts on its own shard's quiescence.  Per-point
    keyed RNG draws make the sharded output bit-identical to the
    single-device engine at any device count (gated in CI at
    ``REPRO_DEVICES`` 2 and 4).  The carry is donated to the runner,
    so the dominant buffers are reused in place.
  * Everything runs in float64/int64 under ``jax.enable_x64(True)``
    (scoped, not process-global): event times must not round-trip
    through float32.

JAX is an optional dependency of this module: importing it (and
``core.simulator_vec``) works without JAX installed; selecting the
backend then raises a ``RuntimeError`` naming the fix.
"""
from __future__ import annotations

import functools
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

try:  # optional dependency — guarded so module import never fails
    import jax
    import jax.numpy as jnp
except ImportError:  # pragma: no cover - exercised via monkeypatch test
    jax = None
    jnp = None

from repro.core.isa import (ACCUM_BYTES, DMA_BYTES_PER_CYCLE,
                            DMA_SETUP_CYCLES, FLUSH_CYCLES)
from repro.core.program import Program
from repro.core.scheduler import Policy
from repro.core.simulator import AggSamples, RunMetrics
from repro.core.simulator_vec import (_BB, _C_CI, _C_CIQ, _C_NONE, _C_PI,
                                      _CAP, _CFG_CY, _FF, _HI, _INT,
                                      _LO, _MODE_KEYS, _NBANKS, _PEND,
                                      _PID_KEY, _READY, _REMAP_CY,
                                      _RESTORE_FIXED, _RUN, _TRANS,
                                      _VecBatch)
# the jit cache salt lives in (jax-free) simulator_vec so the
# experiments/spec layer can hash points without importing JAX;
# re-exported here as the canonical name
from repro.core.simulator_vec import JIT_SIM_SEMANTICS_VERSION  # noqa: F401
from repro.core.task import TaskParams
from repro.scenarios import (burst_multiplier, burst_window_index,
                             demand_multiplier, get_scenario)
# env validation + logical-device plumbing live with the other runtime
# environment code; both are importable without JAX
from repro.runtime.device_config import (_env_int, configure_host_devices,
                                         default_device_count,
                                         jax_initialized,
                                         resolve_device_count)

# XLA reads --xla_force_host_platform_device_count exactly once, at
# first backend init — which in a campaign process is triggered by this
# engine's first computation.  Forcing the REPRO_DEVICES pool at import
# (env mutation only, no jax touched) guarantees the flag is in place
# even when the caller runs a single-device batch before a sharded one.
if default_device_count() > 1 and not jax_initialized():
    configure_host_devices()

# pending-interrupt table: primary width, the give-up bound for the
# host-side double-on-overflow retry ladder, and the padded sub-batch
# size retries are grouped into (bounds compilation variants).  The
# NumPy engine's on-demand table settles at 32-64 on the reference
# corpora; with the stale-interrupt pruning pass the common-case
# occupancy is lower still, so starting at 64 makes the retry the
# rare path.  REPRO_JIT_TABLE_WIDTH / REPRO_JIT_TABLE_MAX override
# both bounds (validated in _env_int).
_K0 = 64
_K_MAX = 1024
_RETRY_BUCKET = 64

# compile-time switch for the stale-interrupt pruning pass; part of
# the compilation cache key.  Only tests flip it (to prove pruning is
# semantics-free by diffing against the unpruned graph).
_PRUNE_STALE = True

# lockstep width per device: small enough to stay cache-resident,
# large enough to amortize per-step fixed cost (measured optimum on
# the 512-point BENCH corpus); a superchunk is devices * this
_STREAM_CHUNK = 64

# "no eligible task" sentinel for the rank-compressed int32 pick_next
# keys (every real key is rank * (T+1) + column << 2**30)
_EMPTY32 = 2 ** 30

# ---- flags: the (P, T) int32 per-task bitfield -----------------------
# [1:0] status (PEND/READY/RUN/INT)   [2] pc>0     [4:3] blocking cause
# [5] budget_overrun   [6] data_in_accel   [7] released_in_hi
# [8] ctx_valid        [9] ctx_kept        [30:10] release counter
_FL_ST_M = 3
_FL_PC_SH = 2
_FL_CZ_SH = 3
_FL_BO_SH = 5
_FL_DIA_SH = 6
_FL_RH_SH = 7
_FL_CV_SH = 8
_FL_CK_SH = 9
_FL_RC_SH = 10          # 21 bits: < 2**21 accepted releases per task
_FL_CZ_M = 3 << _FL_CZ_SH

# ---- pi: the packed (P, 24) int32 per-point block --------------------
# [0] mode  [1] running tid  [2] locked banks  [3] resident-LO count
# [4] active count  [5] active-HI count  [6] alive  [7] table overflow
# [8:24] int metric counters (_MI_* offsets are relative to _I_MI):
#   [jobs_lo, jobs_hi, done_lo, done_hi, miss_lo, miss_hi, mbm_lo,
#    mbm_tr, mbm_hi, lo_rel_hi, lo_done_hi, cs_count, pi_n, ci_n,
#    save_n, restore_n]
(_I_MODE, _I_RUN, _I_LOCKED, _I_RESLO, _I_ACT, _I_HI,
 _I_ALIVE, _I_OVF) = range(8)
_I_MI = 8
_MI_JOBS, _MI_DONE, _MI_MISS, _MI_MBM = 0, 2, 4, 6
_MI_LO_REL, _MI_LO_DONE, _MI_CS = 9, 10, 11
_MI_PI_N, _MI_CI_N, _MI_SAVE_N, _MI_RESTORE_N = 12, 13, 14, 15
_MI_W = 16
_PI_W = _I_MI + _MI_W

# ---- pf: the packed (P, 14) float64 per-point block ------------------
# [0] now  [1] accel_free_at  [2] run_started  [3] last_mode_stamp
# [4] tick_cs  [5:14] float metric accumulators (_MF_* offsets are
# relative to _F_MF): [exec_sum, overhead, pi_sum, ci_sum, save_sum,
# restore_sum, mode_cycles_lo/tr/hi]
_F_NOW, _F_FREE, _F_RSTART, _F_LMS, _F_TICKCS = range(5)
_F_MF = 5
_MF_EXEC, _MF_OVERHEAD, _MF_PI, _MF_CI = 0, 1, 2, 3
_MF_SAVE, _MF_RESTORE, _MF_MC = 4, 5, 6
_MF_W = 9
_PF_W = _F_MF + _MF_W


def require_jax(backend: str = "jit") -> None:
    """Fail fast with an actionable message when JAX is unavailable."""
    if jax is None:
        raise RuntimeError(
            f"select_backend={backend!r} needs JAX, which is not "
            "importable in this environment; install jax (CPU wheels: "
            "`pip install jax`) or use select_backend='numpy'")


def _table_width() -> int:
    return _env_int("REPRO_JIT_TABLE_WIDTH", _K0)


def _table_max(k0: int) -> int:
    return max(_env_int("REPRO_JIT_TABLE_MAX", _K_MAX), k0)


# ----------------------------------------------------------------------
# Compiled step (built once per static policy/profile class)
# ----------------------------------------------------------------------

def _build_run(use_banks: bool, drop_lo: bool, preempt: str,
               nominal: bool, prune: bool, scenario=None):
    """Compile the whole-simulation while_loop for one static config.

    Everything dynamic (per-batch tables, scalars, carry) is a traced
    argument; jax re-specializes per array shape, so batches sharing
    (n_points, n_tasks, K, table sizes) share one compilation.

    XLA:CPU pays a ~flat dispatch cost per emitted kernel inside a
    while_loop, so the body is shaped to minimize *kernel count*, not
    flops (see the module docstring's carry-layout notes):

      * per-point single-column reads are gathers (cheap), and the
        ``flags`` bitfield makes one gather serve every small per-task
        field of a column; every carried array receives exactly ONE
        fused write pass per step (XLA CPU scatters are pathologically
        slow, and one chain beats separate masked writes);
      * deferring all writes to the end of the step is sound because
        the four event classes are disjoint per point and handlers
        only touch their own point's row — the few same-row
        read-after-write hazards (advance -> dispatch, finish ->
        scheduler, overrun -> dispatch on the same column) are
        resolved by deriving the post-write values as (P,)-scalars
        instead of re-reading the array;
      * metric counters live in the packed ``pi``/``pf`` tails and are
        updated by one fused add-chain each;
      * the demand draw is a branch-free splitmix64 hash (a handful of
        fused u64 ops; ``jax.random``'s threefry costs ~50 kernels per
        step on CPU).
    """

    GOLD = np.uint64(0x9E3779B97F4A7C15)

    def _mix64(x):
        """splitmix64 finalizer — the counter-based RNG's mixer."""
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))

    def _u01(bits):
        """Top 53 bits -> uniform double in [0, 1)."""
        return (bits >> np.uint64(11)).astype(jnp.float64) \
            * (1.0 / (1 << 53))

    def _oh(col, width):
        return col[:, None] == jnp.arange(width)[None, :]

    def _get(arr, col):
        """arr[p, col[p]] (clamped columns; callers mask the result)."""
        return jnp.take_along_axis(arr, col[:, None], axis=1)[:, 0]

    def _chain(arr, *writes):
        """One fused masked-write pass: ``writes`` are (oh, mask, val)
        triples applied lowest-precedence-first (later entries win on
        overlap, matching the sequential write order they replace)."""
        out = arr
        for oh, mask, val in writes:
            val = jnp.asarray(val, arr.dtype)
            if val.ndim:
                val = val[:, None]
            out = jnp.where(oh & mask[:, None], val, out)
        return out

    def _apply_inc(M, incs):
        """One fused add-chain over a packed metric block; ``incs`` are
        (column, mask, value) with scalar or per-point columns."""
        cols = jnp.arange(M.shape[1])
        out = M
        for idx, mask, val in incs:
            idx = jnp.asarray(idx)
            if idx.ndim:
                ohm = (idx[:, None] == cols[None, :]) & mask[:, None]
            else:
                ohm = (cols == idx)[None, :] & mask[:, None]
            val = jnp.asarray(val, M.dtype)
            if val.ndim:
                val = val[:, None]
            out = out + jnp.where(ohm, val, jnp.zeros((), M.dtype))
        return out

    def _dma(nbytes):
        cy = DMA_SETUP_CYCLES + (nbytes + DMA_BYTES_PER_CYCLE - 1) \
            // DMA_BYTES_PER_CYCLE
        return jnp.where(nbytes <= 0, 0, cy)

    def _banks(nbytes):
        return (nbytes + _BB - 1) // _BB

    def _bit(fl, sh):
        return ((fl >> sh) & 1) != 0

    def _boundaries(tb, pids, off):
        """Vectorized Program.next_{instruction,operator}_boundary via
        one searchsorted over the globally keyed tables (identical
        float/int op order to the NumPy engine's ``_boundaries``)."""
        total = tb["prog_total"][pids]
        wrap = off >= total
        base = jnp.where(wrap,
                         jnp.floor_divide(off, total) * total, 0.0)
        off = off - base
        pk = pids.astype(jnp.float64) * float(_PID_KEY)
        # searchsorted as a broadcast compare+count: the tables are a
        # few hundred entries, and one dense pass beats the unrolled
        # binary search's serial gather chain on CPU
        if preempt == "instruction":
            off = jnp.minimum(jnp.maximum(off, 0.0), total - 1e-9)
            q = pk + off
            i = (tb["seg_key"][None, :] <= q[:, None]).sum(axis=1)
            seg_start = (tb["seg_key"][i] - pk) - tb["seg_cycles"][i]
            within = off - seg_start
            pat = tb["seg_pat"][i]
            rep = jnp.floor_divide(within, pat)
            rem = within - rep * pat
            cum = tb["pat_cumsum"][i]
            k = (cum <= rem[:, None]).sum(axis=1)
            acc = _get(cum, k)
            return jnp.trunc(base + seg_start + rep * pat + acc)
        q = pk + off
        i = (tb["op_key"][None, :] <= q[:, None]).sum(axis=1)
        i = jnp.minimum(i, tb["op_hi"][pids])
        return jnp.trunc(base + tb["op_end"][i])

    def _sample_demand(tb, sc, rcol, n, hi_r, c_lo_r):
        """Counter-based per-release demand draw: splitmix64 of
        (seed, task, release index) — identical distributions to the
        sequential-stream engines, but order-free so the compiled loop
        needs no host RNG state (see the module docstring)."""
        ctr = (rcol.astype(jnp.uint64) << np.uint64(33)) \
            + (n.astype(jnp.uint64) << np.uint64(1))
        s = tb["seed64"] + ctr * GOLD
        u0 = _u01(_mix64(s))
        u1 = _u01(_mix64(s + GOLD))
        over = hi_r & (u0 < sc["overrun_prob"])
        mag = jnp.where(over, 1.0 + (sc["cf"] - 1.0) * u1,
                        0.7 + 0.3 * u1)
        return c_lo_r * mag

    # ------------------------------------------------------------------
    def _step(tb, sc, c):
        """One lockstep iteration: pop each live point's next event and
        apply the handlers as masked updates — the jit counterpart of
        ``_VecBatch.run``'s loop body, one event class per point.  The
        scheduler aggregates (locked banks, resident-LO / active / HI
        counts) ride in the packed ``pi`` block and are updated
        incrementally at the NumPy engine's sites; every carried array
        is written once, at the end (see ``_build_run``)."""
        T = tb["valid"].shape[1]
        K = c["ev_time"].shape[1]
        next_tick = lambda t: (jnp.floor_divide(t, sc["t_sr"]) + 1) \
            * sc["t_sr"]
        mi_inc, mf_inc = [], []

        # ---- unpack the grouped carry (slices fuse into consumers) ---
        flags = c["flags"]
        status_a = flags & _FL_ST_M
        pi, pf = c["pi"], c["pf"]
        mode0 = pi[:, _I_MODE]
        run0 = pi[:, _I_RUN]
        locked0 = pi[:, _I_LOCKED]
        res_lo0 = pi[:, _I_RESLO]
        act0 = pi[:, _I_ACT]
        hic0 = pi[:, _I_HI]
        alive0 = pi[:, _I_ALIVE] != 0
        ovf0 = pi[:, _I_OVF] != 0
        now0 = pf[:, _F_NOW]
        free0 = pf[:, _F_FREE]
        rs0 = pf[:, _F_RSTART]
        lms0 = pf[:, _F_LMS]
        tcs0 = pf[:, _F_TICKCS]

        # ---- candidate argmin over the four event sources ------------
        # hierarchical: per-source row mins feed a tiny (P, 4) argmin
        # (a single concatenated (P, 2T+K+1) pop has fewer kernels but
        # measures slower on XLA:CPU — the concat defeats loop fusion)
        rel_min = c["next_release"].min(axis=1)
        tickR_min = c["tick_release"].min(axis=1)
        ev_min = c["ev_time"].min(axis=1)
        cand = jnp.stack([rel_min, tickR_min, ev_min, tcs0], axis=1)
        j = jnp.argmin(cand, axis=1)
        tmin = cand.min(axis=1)
        fire = alive0 & (tmin <= sc["duration"])
        now = jnp.where(fire, tmin, now0)
        is_rel = fire & (j == 0)
        is_tickR = fire & (j == 1)
        is_cs = fire & (j == 3)
        is_int = fire & (j == 2)

        # ---- release events (no scheduler pass of their own) ---------
        rcol = jnp.argmin(c["next_release"], axis=1)
        ohR = _oh(rcol, T)
        fl_r = _get(flags, rcol)
        st_r = fl_r & _FL_ST_M
        hi_r = _get(tb["is_hi"], rcol)
        crit_r = hi_r.astype(jnp.int32)
        # previous job still live: count one miss, skip this release
        fresh_miss = is_rel & (st_r != _PEND) \
            & (_get(c["job_deadline"], rcol) != jnp.inf)
        mi_inc.append((_MI_MISS + crit_r, fresh_miss, 1))
        mi_inc.append((_MI_MBM + mode0, fresh_miss, 1))
        accept = is_rel & (st_r == _PEND)
        if drop_lo:                   # AMC: LO not released off-LO
            accept = accept & (hi_r | (mode0 == _LO))
        act1 = act0 + accept
        hic1 = hic0 + (accept & hi_r)
        c_lo_r = _get(tb["c_lo"], rcol)
        n_r = fl_r >> _FL_RC_SH
        if nominal:                   # zero-jitter profile: no draws
            dem = c_lo_r
        else:
            dem = _sample_demand(tb, sc, rcol, n_r, hi_r, c_lo_r)
        if scenario is not None:
            # scenario CRN draws: keyed on the absolute release-event
            # counter ``sn`` (bumped for every release, accepted or
            # not — policy-independent), never on the accepted-release
            # counter n_r.  Same splitmix64 arithmetic as the host
            # engines (scenarios.crn), so nominal-profile runs stay
            # bit-exact vs the vec engine per scenario.  Carry writes
            # happen inline (sn/sw/sm are read only here, so deferring
            # them past the barrier buys nothing).
            sn_r = _get(c["sn"], rcol)
            if scenario.affects_demand:
                if scenario.has_burst:
                    wi = burst_window_index(scenario, jnp, now)
                    fresh_bm = burst_multiplier(scenario, jnp,
                                                tb["seed64"], wi)
                    # per-window draw cached in the carry: pure in
                    # (seed, window), so reuse is exact
                    bm = jnp.where(wi == c["sw"], c["sm"], fresh_bm)
                    c["sw"] = jnp.where(is_rel, wi, c["sw"])
                    c["sm"] = jnp.where(is_rel, bm, c["sm"])
                else:
                    bm = None
                # abs pins the (non-negative) product as a plain IEEE
                # multiply — LLVM would otherwise contract it with
                # downstream subtracts into an FMA and drift a ulp off
                # the host engines' demand values (see scenarios
                # ._nofuse)
                dem = jnp.abs(dem * demand_multiplier(
                    scenario, jnp, tb["seed64"],
                    rcol.astype(jnp.uint64),
                    sn_r.astype(jnp.uint64), now, burst_m=bm))
            c["sn"] = _chain(c["sn"], (ohR, is_rel, sn_r + 1))
        mi_inc.append((_MI_JOBS + crit_r, accept, 1))
        rel_hi = accept & ~hi_r & (mode0 != _LO)
        mi_inc.append((_MI_LO_REL, rel_hi, 1))

        # ---- scheduler-tick pops (defer while a CS is in flight) -----
        ohT = _oh(jnp.argmin(c["tick_release"], axis=1), T)
        tcs1 = jnp.where(is_cs, jnp.inf, tcs0)
        tick_mask = is_tickR | is_cs
        busy_t = tick_mask & (now < free0)
        tcs2 = jnp.where(busy_t,
                         jnp.minimum(tcs1, next_tick(free0)), tcs1)
        tick_sched = tick_mask & ~busy_t

        # ---- pending finish/overrun interrupts: pop + guard ----------
        icol = jnp.argmin(c["ev_time"], axis=1)
        ohI = _oh(icol, K)
        pay_i = _get(c["ev_pay"], icol)
        itid = pay_i >> 2
        ikind = pay_i & 3
        tidc = jnp.maximum(itid, 0)
        ohTid = _oh(tidc, T)
        fl_tid = _get(flags, tidc)
        guard = is_int & (run0 == itid) \
            & ((fl_tid & _FL_ST_M) == _RUN)

        # ---- one advance for every point that needs it this step -----
        # (the running column is shared by the advance, the interrupt
        # target and the dispatch drain, so the post-advance values are
        # carried forward as scalars instead of array re-reads)
        runc = jnp.maximum(run0, 0)
        ohRun = _oh(runc, T)
        elapsed = now - rs0
        do_adv = (guard | tick_sched) & (run0 >= 0) & (elapsed > 0)
        exec_r0 = _get(c["exec_cy"], runc)
        exec_r1 = jnp.where(do_adv, exec_r0 + elapsed, exec_r0)
        mf_inc.append((_MF_EXEC, do_adv, elapsed))
        rs1 = jnp.where(do_adv, now, rs0)
        # GemminiRT.note_execution (exact integer growth model)
        etab_r = _get(tb["etab"], runc).astype(jnp.int64) * _BB
        grow = jnp.floor(elapsed * DMA_BYTES_PER_CYCLE).astype(jnp.int64)
        if use_banks:
            have = _get(c["res_bytes"], runc).astype(jnp.int64)
            free = (_NBANKS - locked0).astype(jnp.int64)
            growing = do_adv & (have < etab_r) & (free > 0)
            want = jnp.minimum(jnp.minimum(etab_r, have + free * _BB),
                               have + grow)
            rb_grown = jnp.maximum(have, want)
            rb_1 = jnp.where(growing, rb_grown, have)
            locked1 = locked0 + jnp.where(
                growing, _banks(rb_grown) - _banks(have), 0).astype(
                    jnp.int32)
            went = growing & (have == 0) & (rb_grown > 0) \
                & ~_get(tb["is_hi"], runc)
            res_lo1 = res_lo0 + went
        else:
            have = _get(c["res_bytes"], runc).astype(jnp.int64)
            growing = do_adv & (have < etab_r)
            others = c["res_bytes"].sum(axis=1) - have
            want = jnp.minimum(
                jnp.minimum(etab_r, jnp.maximum(_CAP - others, 0)),
                have + grow)
            rb_1 = jnp.where(growing, jnp.maximum(have, want), have)
            locked1, res_lo1 = locked0, res_lo0
        acc_r0 = _get(c["acc_bytes"], runc).astype(jnp.int64)
        filling = do_adv & (acc_r0 < ACCUM_BYTES)
        grow_acc = jnp.floor_divide(
            elapsed * DMA_BYTES_PER_CYCLE, 4).astype(jnp.int64)
        acc_1 = jnp.where(filling,
                          jnp.minimum(ACCUM_BYTES, acc_r0 + grow_acc),
                          acc_r0)

        # ---- fire guard-passing finish/overrun events ----------------
        # (the interrupt target IS the running column for guard-passing
        # points, so exec_r1 / rb_1 are its post-advance values)
        done_m = guard & (ikind == 1) \
            & (exec_r1 >= _get(c["demand"], tidc) - 1e-6)
        hi_i = _get(tb["is_hi"], tidc)
        crit_i = hi_i.astype(jnp.int32)
        ddl_i = _get(c["job_deadline"], tidc)
        mi_inc.append((_MI_DONE + crit_i, done_m, 1))
        late = done_m & (now > ddl_i)
        mi_inc.append((_MI_MISS + crit_i, late, 1))
        mi_inc.append((_MI_MBM + mode0, late, 1))
        surv = done_m & _bit(fl_tid, _FL_RH_SH) & (now <= ddl_i)
        mi_inc.append((_MI_LO_DONE, surv, 1))
        act2 = act1 - done_m
        hic2 = hic1 - (done_m & hi_i)
        # GemminiRT.evict
        mf_inc.append((_MF_OVERHEAD, done_m, float(FLUSH_CYCLES)))
        if use_banks:
            locked2 = locked1 - jnp.where(
                done_m, _banks(rb_1), 0).astype(jnp.int32)
            res_lo2 = res_lo1 - (done_m & (rb_1 > 0) & ~hi_i)
        else:
            locked2, res_lo2 = locked1, res_lo1
        run1 = jnp.where(done_m, -1, run0)
        # overrun: flag the budget excess, degrade LO -> transition
        fire_o = guard & (ikind == 2) \
            & (exec_r1 >= _get(tb["c_lo"], tidc) - 1e-6) \
            & ~_bit(fl_tid, _FL_BO_SH)
        was_lo = fire_o & (mode0 == _LO)
        mf_inc.append((_MF_MC + mode0, was_lo, now - lms0))
        lms1 = jnp.where(was_lo, now, lms0)
        mode1 = jnp.where(was_lo, _TRANS, mode0)

        # ---- scheduler pass ------------------------------------------
        sched = tick_sched | done_m | fire_o
        # a stale event can land mid-switch: defer like a tick re-push
        busy_s = sched & (now < free0)
        tcs3 = jnp.where(busy_s,
                         jnp.minimum(tcs2, next_tick(free0)), tcs2)
        sched = sched & ~busy_s
        # mode progression (SS IV) off the carried aggregates
        mt = sched & (mode1 != _LO)
        to_hi = mt & (mode1 == _TRANS) & (res_lo2 <= 1)
        to_lo = mt & ~to_hi & (act2 == 0)
        mode2 = jnp.where(to_hi, _HI, jnp.where(to_lo, _LO, mode1))
        chg = mode2 != mode1
        mf_inc.append((_MF_MC + mode1, chg, now - lms1))
        lms2 = jnp.where(chg, now, lms1)
        # pick_next via masked min over the rank-compressed
        # (priority, column) keys; the finishing task left the active
        # set this step, which the deferred status write hasn't
        # recorded yet — mask its column out here
        active = (status_a != _PEND) & tb["valid"] \
            & ~(ohTid & done_m[:, None])
        act_key = jnp.where(active, tb["key32"], _EMPTY32).min(axis=1)
        hi_key = jnp.where(active & tb["is_hi"], tb["key32"],
                           _EMPTY32).min(axis=1)
        hi_active = hic2 > 0
        off_lo = mode2 != _LO
        if drop_lo:                   # AMC: LO never runs off-LO
            key = jnp.where(off_lo, hi_key, act_key)
        else:
            key = jnp.where(off_lo & hi_active, hi_key, act_key)
            # transition mode: a LO task may run only while its data
            # is still resident (rare — branch around the extra pass,
            # correcting for this step's deferred writes)
            need_tr = sched & off_lo & ~hi_active & (mode2 == _TRANS)

            def _tr_keys(_):
                resid = _bit(flags, _FL_DIA_SH)
                if use_banks:
                    resid = resid | (c["res_bytes"] > 0)
                resid = resid & ~(ohTid & done_m[:, None])
                if use_banks:
                    resid = resid | (ohRun
                                     & (growing & (rb_grown > 0))[:, None])
                ok = active & (tb["is_hi"] | resid)
                return jnp.where(ok, tb["key32"], _EMPTY32).min(axis=1)

            key_tr = jax.lax.cond(
                need_tr.any(), _tr_keys,
                lambda _: jnp.full_like(key, _EMPTY32), None)
            key = jnp.where(need_tr, key_tr, key)
        nxt = (key % (T + 1)).astype(jnp.int32)
        nxt = jnp.where(key >= _EMPTY32, -1, nxt)
        # clear a stale running slot (event engine's defensive check)
        curc = jnp.maximum(run1, 0)
        ohC = _oh(curc, T)
        fl_c = _get(flags, curc)
        stale = sched & (run1 >= 0) & ((fl_c & _FL_ST_M) != _RUN)
        run2 = jnp.where(stale, -1, run1)
        # ohC / curc stay valid: stale points get cur < 0, for which
        # every consumer below is masked out — and whenever a dispatch
        # drains a current task, curc equals runc (the point advanced
        # the same column this step), so rb_1 / acc_1 / exec_r1 are its
        # post-advance values
        cur = run2
        act_m = sched & (nxt >= 0) & (cur != nxt)
        # a displaced current task blocks the newcomer until the switch
        nxtc = jnp.maximum(nxt, 0)
        ohN = _oh(nxtc, T)
        fl_n = _get(flags, nxtc)
        hi_n = _get(tb["is_hi"], nxtc)
        hi_c = _get(tb["is_hi"], curc)
        blocked = act_m & (cur >= 0)
        bsince_0 = _get(c["blocked_since"], nxtc)
        fresh_b = blocked & jnp.isnan(bsince_0)
        bsince_1 = jnp.where(fresh_b, now, bsince_0)
        run_lo = (cur >= 0) & ~hi_c
        ci_shape = hi_n & run_lo
        cause_v = jnp.where(
            ci_shape, jnp.where(mode2 != _LO, _C_CI, _C_CIQ), _C_PI)
        cz_1 = jnp.where(fresh_b, cause_v, (fl_n >> _FL_CZ_SH) & 3)
        if preempt == "none":         # cannot displace the running task
            act_m = act_m & (cur < 0)

        # ---- dispatch (context switch, Alg. 1) -----------------------
        has_cur = act_m & (cur >= 0)
        # drain to the preemption boundary
        boundary = _boundaries(tb, _get(tb["prog_id"], curc), exec_r1)
        drain = jnp.maximum(
            0.0, jnp.minimum(boundary, _get(c["demand"], curc))
            - exec_r1)
        exec_r2 = jnp.where(has_cur, exec_r1 + drain, exec_r1)
        drain_i = jnp.trunc(drain).astype(jnp.int64)
        # context_save cost model (GemminiRT)
        acc_cy = _dma(acc_1)
        if use_banks:
            need = _get(tb["eta"], nxtc) + locked2 > _NBANKS
            spadsave = need & (rb_1 > 0)
            remap_cy = _REMAP_CY
            resident = rb_1
        else:
            resident = _get(c["res_bytes"], curc).astype(jnp.int64)
            resident = jnp.where(curc == runc, rb_1, resident)
            spadsave = resident > 0
            remap_cy = 0
        spad_cy = jnp.where(spadsave, _dma(resident), 0)
        br_save = drain_i + (_FF + _CFG_CY + remap_cy) + acc_cy + spad_cy
        kept = ~spadsave
        sv = has_cur & spadsave
        # HI-mode LO->LO preemption: full eviction of the old LO data
        lolo = has_cur & (mode2 == _HI) & ~hi_c & ~hi_n
        if use_banks:
            locked3 = locked2 - jnp.where(
                sv, _banks(resident), 0).astype(jnp.int32)
            res_lo3 = res_lo2 - (sv & ~hi_c)
            # the lolo eviction sees the residency left after the save
            rb_2 = jnp.where(sv, 0, rb_1)
            locked4 = locked3 - jnp.where(
                lolo, _banks(rb_2), 0).astype(jnp.int32)
            res_lo4 = res_lo3 - (lolo & (rb_2 > 0))
        else:
            locked4, res_lo4 = locked2, res_lo2
        mi_inc.append((_MI_CS, has_cur, 1))
        mf_inc.append((_MF_SAVE, has_cur, br_save.astype(jnp.float64)))
        mi_inc.append((_MI_SAVE_N, has_cur, 1))
        # context_restore for resumed tasks
        resume = act_m & (_bit(fl_n, _FL_PC_SH)
                          | ((fl_n & _FL_ST_M) == _INT))
        has_ctx = _bit(fl_n, _FL_CV_SH)
        ctx_acc_n = _get(c["ctx_acc"], nxtc).astype(jnp.int64)
        ctx_spad_n = _get(c["ctx_spad"], nxtc).astype(jnp.int64)
        acc_cy_r = jnp.where(has_ctx, _dma(ctx_acc_n), 0)
        reload = resume & has_ctx & ~_bit(fl_n, _FL_CK_SH) \
            & (ctx_spad_n > 0)
        spad_cy_r = jnp.where(reload, _dma(ctx_spad_n), 0)
        br_rest = jnp.where(has_ctx,
                            acc_cy_r + spad_cy_r + _RESTORE_FIXED, 0)
        if use_banks:
            br_rest = br_rest + jnp.where(reload, _REMAP_CY, 0)
            free_b = (_NBANKS - locked4).astype(jnp.int64)
            new_res = jnp.minimum(ctx_spad_n, free_b * _BB)
            locked5 = locked4 + jnp.where(
                reload, _banks(new_res), 0).astype(jnp.int32)
            res_lo5 = res_lo4 + (reload & (new_res > 0) & ~hi_n)
        else:
            new_res = ctx_spad_n
            locked5, res_lo5 = locked4, res_lo4
        mf_inc.append((_MF_RESTORE, resume, br_rest.astype(jnp.float64)))
        mi_inc.append((_MI_RESTORE_N, resume, 1))
        # commit the switch
        switch = jnp.where(has_cur, br_save, 0).astype(jnp.float64) \
            + jnp.where(resume, br_rest, 0).astype(jnp.float64)
        mf_inc.append((_MF_OVERHEAD, act_m, switch))
        run3 = jnp.where(act_m, nxt, run2)
        # _record_unblock(nxt, at=now + switch)
        at = now + switch
        was_b = act_m & ~jnp.isnan(bsince_1)
        dt = at - bsince_1
        cz = jnp.where((cz_1 == _C_CIQ) & (mode2 != _LO), _C_CI, cz_1)
        posd = was_b & (dt > 0)
        ci_m = posd & (cz == _C_CI)
        pi_m = posd & (cz != _C_CI)
        mf_inc.append((_MF_CI, ci_m, dt))
        mi_inc.append((_MI_CI_N, ci_m, 1))
        mf_inc.append((_MF_PI, pi_m, dt))
        mi_inc.append((_MI_PI_N, pi_m, 1))
        rs2 = jnp.where(act_m, at, rs1)
        free1 = jnp.where(act_m, at, free0)
        # future events for the new running task
        exec_n = _get(c["exec_cy"], nxtc)
        rem = _get(c["demand"], nxtc) - exec_n
        c_lo_n = _get(tb["c_lo"], nxtc)
        arm = act_m & hi_n & ~_bit(fl_n, _FL_BO_SH) & (exec_n < c_lo_n)
        t_fin = at + rem
        t_ovr = at + (c_lo_n - exec_n)
        ddl_new = now + _get(tb["deadline_rel"], rcol)
        nrel_new = now + _get(tb["period"], rcol)
        tr_new = next_tick(now)

        # ---- flag-write values (one RMW per write site; see the
        # conflict analysis in _build_run's docstring) ------------------
        # release: fresh job — set READY, clear pc/budget_overrun, set
        # released_in_hi, bump the release counter; keep cause/ctx bits
        keep_r = _FL_CZ_M | (1 << _FL_DIA_SH) | (1 << _FL_CV_SH) \
            | (1 << _FL_CK_SH)
        fl_release = (fl_r & keep_r) | _READY \
            | (rel_hi.astype(jnp.int32) << _FL_RH_SH) \
            | ((n_r + 1) << _FL_RC_SH)
        # finish: back to PENDING, data gone, context invalid
        fl_done = fl_tid & ~jnp.int32(_FL_ST_M | (1 << _FL_DIA_SH)
                                      | (1 << _FL_CV_SH))
        # overrun: set budget_overrun (kept for non-dispatching points;
        # folded into fl_cur below when the same column is displaced)
        fl_fireo = fl_tid | (1 << _FL_BO_SH)
        # displaced current task: INTERRUPTED + ctx snapshot bits.  An
        # overrun fired on this very column this step (fire_o implies
        # tidc == curc) — fold its budget_overrun bit in so the RMW
        # does not resurrect the pre-step value
        fl_c2 = fl_c | (fire_o.astype(jnp.int32) << _FL_BO_SH)
        fl_cur = (fl_c2 & ~jnp.int32(_FL_ST_M | (1 << _FL_DIA_SH)
                                     | (1 << _FL_CV_SH)
                                     | (1 << _FL_CK_SH))) \
            | _INT \
            | ((kept & ~lolo).astype(jnp.int32) << _FL_DIA_SH) \
            | (1 << _FL_CV_SH) \
            | (kept.astype(jnp.int32) << _FL_CK_SH)
        # dispatched task: RUNNING + pc, blocking cause resolved, data
        # present again when a context reload happened
        st_n = jnp.where(act_m, _RUN, fl_n & _FL_ST_M)
        pc_n = jnp.where(act_m, 1, (fl_n >> _FL_PC_SH) & 1)
        cz_n = jnp.where(was_b, _C_NONE,
                         jnp.where(fresh_b, cause_v,
                                   (fl_n >> _FL_CZ_SH) & 3))
        dia_n = jnp.where(resume & has_ctx, 1, (fl_n >> _FL_DIA_SH) & 1)
        keep_n = ~jnp.int32(_FL_ST_M | (1 << _FL_PC_SH) | _FL_CZ_M
                            | (1 << _FL_DIA_SH))
        fl_nxt = (fl_n & keep_n) | st_n | (pc_n << _FL_PC_SH) \
            | (cz_n << _FL_CZ_SH) | (dia_n << _FL_DIA_SH)

        # ---- barrier, then deferred writes: one fused pass per array -
        # XLA:CPU loop fusion re-evaluates a shared producer once per
        # fused consumer; the barrier materializes every (P,) scalar
        # and one-hot mask exactly once, so the write chains below are
        # each a cheap read-modify-select pass
        (ohR, ohT, ohI, ohTid, ohRun, ohC, ohN,
         is_rel, is_tickR, is_int, accept, fresh_miss, done_m, fire_o,
         act_m, has_cur, resume, has_ctx, reload, sv, lolo, was_b,
         fresh_b, do_adv, growing, filling, arm, dem, exec_r2,
         rb_1, acc_1, new_res, ctx_acc_n, resident, spadsave,
         t_fin, t_ovr, nxtc, now, ddl_new, nrel_new, tr_new,
         fl_release, fl_done, fl_fireo, fl_cur, fl_nxt,
         mode2, run3, locked5, res_lo5, act2, hic2, fire,
         free1, rs2, lms2, tcs3, mi_inc, mf_inc) = \
            jax.lax.optimization_barrier(
                (ohR, ohT, ohI, ohTid, ohRun, ohC, ohN,
                 is_rel, is_tickR, is_int, accept, fresh_miss, done_m,
                 fire_o, act_m, has_cur, resume, has_ctx, reload, sv,
                 lolo, was_b, fresh_b, do_adv, growing, filling, arm,
                 dem, exec_r2, rb_1, acc_1, new_res, ctx_acc_n,
                 resident, spadsave, t_fin, t_ovr, nxtc, now, ddl_new,
                 nrel_new, tr_new, fl_release, fl_done, fl_fireo,
                 fl_cur, fl_nxt, mode2, run3, locked5, res_lo5, act2,
                 hic2, fire, free1, rs2, lms2, tcs3, mi_inc, mf_inc))

        # per-task state (precedence follows the sequential order the
        # chains replace; distinct-column conflicts were ruled out in
        # the dispatch analysis above, and the one same-column overlap
        # — overrun + displacement — is folded into fl_cur)
        flags_new = _chain(flags, (ohR, accept, fl_release),
                           (ohTid, done_m, fl_done),
                           (ohTid, fire_o, fl_fireo),
                           (ohC, has_cur, fl_cur),
                           (ohN, act_m | fresh_b, fl_nxt))
        c["flags"] = flags_new
        c["exec_cy"] = _chain(c["exec_cy"], (ohR, accept, 0.0),
                              (ohRun, do_adv | has_cur, exec_r2))
        c["demand"] = _chain(c["demand"], (ohTid, done_m, jnp.inf),
                             (ohR, accept, dem))
        c["job_deadline"] = _chain(
            c["job_deadline"], (ohR, fresh_miss, jnp.inf),
            (ohR, accept, ddl_new))
        nrel_a = _chain(c["next_release"], (ohR, is_rel, nrel_new))
        c["next_release"] = nrel_a
        c["tick_release"] = _chain(c["tick_release"],
                                   (ohT, is_tickR, jnp.inf),
                                   (ohR, accept, tr_new))
        c["blocked_since"] = _chain(c["blocked_since"],
                                    (ohN, fresh_b, now),
                                    (ohN, was_b, jnp.nan))
        if use_banks:
            c["res_bytes"] = _chain(
                c["res_bytes"],
                (ohRun, growing | done_m | sv | lolo,
                 jnp.where(done_m | sv | lolo, 0, rb_1)),
                (ohN, reload, new_res))
        else:
            c["res_bytes"] = _chain(
                c["res_bytes"],
                (ohRun, growing | done_m | sv,
                 jnp.where(done_m | sv, 0, rb_1)),
                (ohN, reload, new_res))
        c["acc_bytes"] = _chain(
            c["acc_bytes"],
            (ohRun, filling | done_m | has_cur,
             jnp.where(done_m | has_cur, 0, acc_1)),
            (ohN, resume & has_ctx, ctx_acc_n))
        c["ctx_acc"] = _chain(c["ctx_acc"], (ohC, has_cur, acc_1))
        c["ctx_spad"] = _chain(
            c["ctx_spad"],
            (ohC, has_cur, jnp.where(spadsave, resident, 0)))

        # ---- pending-interrupt table: pop + prune + push -------------
        # stale-interrupt pruning (proof in the module docstring): an
        # entry whose task ends this step with no live job and whose
        # fire time precedes that task's next release can never pass
        # the firing guard again — drop it and free the slot now
        popped = ohI & is_int[:, None]
        if prune:
            tid_k = jnp.maximum(c["ev_pay"] >> 2, 0)
            st_k = jnp.take_along_axis(flags_new & _FL_ST_M, tid_k,
                                       axis=1)
            nrel_k = jnp.take_along_axis(nrel_a, tid_k, axis=1)
            dead = jnp.isfinite(c["ev_time"]) & (st_k == _PEND) \
                & (c["ev_time"] < nrel_k)
            clear = popped | dead
        else:
            clear = popped
        # this step's freed slots (pop + pruned) are immediately
        # reusable by the pushes, like the event engine's heap
        isfree = jnp.isinf(c["ev_time"]) | clear
        n_free = isfree.sum(axis=1)
        oh1 = _oh(jnp.argmax(isfree, axis=1), K)
        oh2 = _oh(jnp.argmax(isfree & ~oh1, axis=1), K)
        do1 = act_m & (n_free >= 1)
        do2 = arm & (n_free >= 2)
        ovf1 = ovf0 | (act_m & (n_free < 1)) | (arm & (n_free < 2))
        ev_t = jnp.where(clear, jnp.inf, c["ev_time"])
        c["ev_time"] = _chain(ev_t, (oh1, do1, t_fin),
                              (oh2, do2, t_ovr))
        c["ev_pay"] = _chain(c["ev_pay"], (oh1, do1, nxtc * 4 + 1),
                             (oh2, do2, nxtc * 4 + 2))

        # ---- packed per-point blocks: one fused write each -----------
        # column-onehot where-chain + add-chain over the whole block:
        # everything fuses into ONE kernel per block (a stack +
        # concatenate assembly of the same values measures ~2.7x
        # slower per step — XLA:CPU materializes concat operands as
        # separate thunks inside the loop)
        cols_i = jnp.arange(_PI_W)
        new_pi = pi
        for col, val in ((_I_MODE, mode2), (_I_RUN, run3),
                         (_I_LOCKED, locked5), (_I_RESLO, res_lo5),
                         (_I_ACT, act2), (_I_HI, hic2),
                         (_I_ALIVE, fire), (_I_OVF, ovf1)):
            new_pi = jnp.where((cols_i == col)[None, :],
                               jnp.asarray(val, jnp.int32)[:, None],
                               new_pi)
        c["pi"] = _apply_inc(new_pi,
                             [(_I_MI + i, m, v) for i, m, v in mi_inc])
        cols_f = jnp.arange(_PF_W)
        new_pf = pf
        for col, val in ((_F_NOW, now), (_F_FREE, free1),
                         (_F_RSTART, rs2), (_F_LMS, lms2),
                         (_F_TICKCS, tcs3)):
            new_pf = jnp.where((cols_f == col)[None, :],
                               val[:, None], new_pf)
        c["pf"] = _apply_inc(new_pf,
                             [(_F_MF + i, m, v) for i, m, v in mf_inc])
        c["steps"] = c["steps"] + 1
        return c

    def _run(tb, sc, carry):
        def cond(c):
            # overflowing points keep stepping (their results are
            # discarded and selectively re-run at a wider table); the
            # healthy majority of the batch must run to completion
            return (c["pi"][:, _I_ALIVE] != 0).any() \
                & (c["steps"] < sc["max_steps"])

        return jax.lax.while_loop(cond, functools.partial(_step, tb, sc),
                                  carry)

    return _run


# tb/sc/carry dict layouts, fixed by _tables/_run_once/_carry0: the
# shard_map partition specs below are derived from these key lists, so
# they live next to the functions that define the dicts
_TB_PER_POINT = frozenset({
    "seed64", "valid", "key32", "period", "deadline_rel", "c_lo",
    "is_hi", "eta", "etab", "prog_id"})
_TB_KEYS = tuple(sorted(_TB_PER_POINT) + [
    "prog_total", "seg_key", "seg_cycles", "seg_pat", "pat_cumsum",
    "op_key", "op_end", "op_hi"])
_SC_KEYS = ("t_sr", "overrun_prob", "cf", "duration", "max_steps")
_CARRY_KEYS = (
    "flags", "exec_cy", "demand", "job_deadline", "blocked_since",
    "next_release", "tick_release", "res_bytes", "acc_bytes",
    "ctx_acc", "ctx_spad", "ev_time", "ev_pay", "sn", "sw", "sm",
    "pi", "pf", "steps")


@functools.lru_cache(maxsize=None)
def _compiled_run(use_banks: bool, drop_lo: bool, preempt: str,
                  nominal: bool, prune: bool, scenario=None,
                  devices: int = 1):
    """One jitted runner per static (policy/profile, device count)
    class — the memo is what makes 'one compilation per shape/config'
    true: jax.jit caches specializations per *function object*, so
    handing back a fresh closure per call would retrace and recompile
    every chunk.

    ``devices > 1`` wraps the runner in ``shard_map`` over a 1-D
    logical-device mesh: per-point tables and the whole carry shard
    along the point axis, the global program tables and scalars
    replicate, and — because simulation points are independent — the
    mapped body needs no collectives (``check_vma=False``: there is no
    replicated output for shard_map to prove anything about).  Each
    device's while_loop halts when its own point-shard quiesces, so a
    fast shard does not wait for a slow one's extra steps.  The carry
    (the dominant allocation) is donated in both variants.
    """
    run = _build_run(use_banks, drop_lo, preempt, nominal, prune,
                     scenario)
    if devices == 1:
        return jax.jit(run, donate_argnums=(2,))
    from jax.sharding import PartitionSpec as P

    from repro.runtime.sharding import logical_device_mesh

    mesh = logical_device_mesh(devices)
    tb_specs = {k: P("dev") if k in _TB_PER_POINT else P()
                for k in _TB_KEYS}
    sc_specs = {k: P() for k in _SC_KEYS}
    carry_specs = {k: P("dev") for k in _CARRY_KEYS}

    def _dev_body(tb, sc, c):
        # each device runs the scalar-step runner on its point-shard;
        # the (devices,) step counter contributes one lane per device
        c = dict(c)
        c["steps"] = c["steps"][0]
        out = run(tb, sc, c)
        out["steps"] = out["steps"][None]
        return out

    return jax.jit(
        jax.shard_map(_dev_body, mesh=mesh,
                      in_specs=(tb_specs, sc_specs, carry_specs),
                      out_specs=carry_specs, check_vma=False),
        donate_argnums=(2,))


# ----------------------------------------------------------------------
# Host driver: state build, overflow retry, tail accounting, assembly
# ----------------------------------------------------------------------

def _rank_keys(b: _VecBatch) -> np.ndarray:
    """Rank-compress the NumPy engine's (priority, column) int64 keys
    into int32: pick_next only compares keys *within* a point, so a
    per-point dense rank of the priorities preserves the selection
    (ties still break on the lowest column) at a quarter of the
    memory traffic."""
    pr = np.minimum(b.prio, 2 ** 40)
    key = np.empty((b.P, b.T), np.int32)
    cols = np.arange(b.T, dtype=np.int32)
    for p in range(b.P):
        distinct = np.unique(pr[p])
        key[p] = np.searchsorted(distinct, pr[p]).astype(np.int32) \
            * (b.T + 1) + cols
    return key


def _tables(b: _VecBatch, seeds: Sequence[int]) -> Dict[str, "jnp.ndarray"]:
    return {
        "seed64": jnp.asarray(
            np.asarray(seeds, dtype=np.int64).astype(np.uint64)),
        "valid": jnp.asarray(b.valid),
        "key32": jnp.asarray(_rank_keys(b)),
        "period": jnp.asarray(b.period),
        "deadline_rel": jnp.asarray(b.deadline_rel),
        "c_lo": jnp.asarray(b.c_lo),
        "is_hi": jnp.asarray(b.is_hi),
        "eta": jnp.asarray(b.eta.astype(np.int32)),
        "etab": jnp.asarray(b.etab.astype(np.int32)),
        "prog_id": jnp.asarray(b.prog_id.astype(np.int32)),
        "prog_total": jnp.asarray(b._prog_total.astype(np.float64)),
        "seg_key": jnp.asarray(b._g_seg_key),
        "seg_cycles": jnp.asarray(b._g_seg_cycles),
        "seg_pat": jnp.asarray(b._g_seg_pat),
        "pat_cumsum": jnp.asarray(b._g_pat_cumsum),
        "op_key": jnp.asarray(b._g_op_key),
        "op_end": jnp.asarray(b._g_op_end),
        "op_hi": jnp.asarray(b._g_op_hi),
    }


def _carry0(b: _VecBatch, seeds: Sequence[int], K: int,
            devices: int = 1) -> Dict[str, "jnp.ndarray"]:
    """Initial carry: the freshly-initialized NumPy batch state (which
    already drew the release phases from each point's host RNG) as the
    grouped tensors of the module docstring, plus empty packed metric
    blocks and an interrupt table of width ``K``.  The step counter is
    scalar on one device and one lane per device when sharded."""
    P, T = b.P, b.T
    pi0 = np.zeros((P, _PI_W), np.int32)
    pi0[:, _I_RUN] = -1
    pi0[:, _I_ALIVE] = 1
    pf0 = np.zeros((P, _PF_W))
    pf0[:, _F_TICKCS] = np.inf
    return {
        "flags": jnp.zeros((P, T), jnp.int32),
        "exec_cy": jnp.zeros((P, T)),
        "demand": jnp.full((P, T), jnp.inf),
        "job_deadline": jnp.zeros((P, T)),
        "blocked_since": jnp.full((P, T), jnp.nan),
        "next_release": jnp.asarray(b.next_release),
        "tick_release": jnp.full((P, T), jnp.inf),
        "res_bytes": jnp.zeros((P, T), jnp.int32),
        "acc_bytes": jnp.zeros((P, T), jnp.int32),
        "ctx_acc": jnp.zeros((P, T), jnp.int32),
        "ctx_spad": jnp.zeros((P, T), jnp.int32),
        "ev_time": jnp.full((P, K), jnp.inf),
        "ev_pay": jnp.full((P, K), -1, jnp.int32),
        # scenario state: absolute release-event counter + the cached
        # per-window burst draw (window index, multiplier).  Carried
        # unconditionally so the carry pytree is scenario-independent;
        # with scenario=None they are loop-invariant pass-throughs.
        "sn": jnp.zeros((P, T), jnp.int32),
        "sw": jnp.full((P,), -1, jnp.int32),
        "sm": jnp.ones((P,)),
        "pi": jnp.asarray(pi0),
        "pf": jnp.asarray(pf0),
        "steps": jnp.zeros((), jnp.int64) if devices == 1
        else jnp.zeros((devices,), jnp.int64),
    }


def _max_steps(b: _VecBatch, duration: float) -> int:
    """Loose per-point event-count bound — a diverging while_loop is an
    engine bug and must surface as an error, not a hang."""
    with np.errstate(divide="ignore"):
        rel = np.where(b.valid, duration / b.period + 2, 0.0).sum(axis=1)
    return int(64 * (rel.max() + 16) + 65536)


def _run_once(b: _VecBatch, policy: Policy, seeds: Sequence[int],
              duration: float, overrun_prob: float, cf: float,
              nominal: bool, K: int,
              devices: int = 1, scenario=None) -> Dict[str, np.ndarray]:
    """One compiled run of a prepared batch at interrupt-table width
    ``K``, sharded over ``devices`` logical devices; returns the final
    carry as NumPy arrays."""
    if b.P % max(devices, 1):
        raise ValueError(
            f"sharded run needs the point count ({b.P}) divisible by "
            f"the device count ({devices}); the span planner pads to "
            "a devices x chunk rectangle")
    run = _compiled_run(policy.use_banks, policy.drop_lo_in_hi,
                        policy.preemption, nominal, _PRUNE_STALE,
                        scenario, devices)
    max_steps = _max_steps(b, duration)
    # event times are float64; everything (array upload included) must
    # happen under x64 or XLA would round-trip them through float32
    with jax.enable_x64(True):
        tb = _tables(b, seeds)
        sc = {"t_sr": jnp.float64(policy.t_sr),
              "overrun_prob": jnp.float64(overrun_prob),
              "cf": jnp.float64(cf),
              "duration": jnp.float64(duration),
              "max_steps": jnp.int64(max_steps)}
        final = run(tb, sc, _carry0(b, seeds, K, devices=devices))
        final = {k: np.asarray(v) for k, v in final.items()}
    # unpack the layout-dependent bits here so _run_chunk (and its
    # tests) stay independent of the packed-block column order
    final["overflow"] = final["pi"][:, _I_OVF] != 0
    if int(np.max(final["steps"])) >= max_steps \
            and final["pi"][:, _I_ALIVE].any():
        raise RuntimeError(
            f"jit engine: lockstep loop hit the {max_steps}-step "
            "safety bound with live points remaining")
    return final


def _run_chunk(tasksets, programs, policy, seeds, duration, overrun_prob,
               cf, demand_profile: str,
               point_ids: Optional[Sequence[int]] = None,
               devices: int = 1, scenario=None) -> List[RunMetrics]:
    """Simulate one (super)chunk with the per-point overflow-retry
    ladder.

    The chunk first runs at the narrow primary interrupt table (ample
    for typical points, rarer still with stale-interrupt pruning),
    sharded over ``devices`` logical devices when asked.  Points whose
    table overflowed — a per-point, batch-composition-independent
    event — are re-run in small padded single-device sub-batches at
    doubled widths until they fit; the counter-based RNG makes every
    retry bit-deterministic, so a point's result never depends on
    which batch, table width, or device count executed it.  A point
    that still overflows at the maximum width raises a loud,
    point-identified error: metrics computed from a saturated table
    would silently drop interrupts.
    """
    nominal = demand_profile == "nominal"
    scenario = get_scenario(scenario)
    # only demand-affecting components reach the compiled loop (phase
    # shift is applied host-side at batch init, instance loss is
    # serving-only): a scenario with every in-loop component off shares
    # the scenario-free graph — disabled scenarios stay compiled-out
    loop_scen = scenario if scenario is not None \
        and scenario.affects_demand else None
    out: List[Optional[RunMetrics]] = [None] * len(tasksets)
    idx = list(range(len(tasksets)))
    K = _table_width()
    k_max = _table_max(K)
    first = True
    while idx:
        ts = [tasksets[i] for i in idx]
        sd = [int(seeds[i]) for i in idx]
        # pad retry sub-batches up to the bucket size so the ladder
        # reuses one compilation per (bucket, K) instead of one per
        # subset shape (padded copies are simulated and discarded)
        if not first and len(ts) < _RETRY_BUCKET:
            pad = _RETRY_BUCKET - len(ts)
            ts = ts + [ts[-1]] * pad
            sd = sd + [sd[-1]] * pad
        b = _VecBatch(ts, programs, policy, seeds=sd, duration=duration,
                      overrun_prob=overrun_prob, cf=cf,
                      scenario=scenario)
        final = _run_once(b, policy, sd, duration, overrun_prob, cf,
                          nominal, K, devices=devices if first else 1,
                          scenario=loop_scen)
        metrics = _assemble(b, final, duration)
        overflow = final["overflow"]
        redo = []
        for pos, i in enumerate(idx):
            if overflow[pos]:
                redo.append(i)
            else:
                out[i] = metrics[pos]
        idx = redo
        K *= 2
        first = False
        if idx and K > k_max:
            pts = ", ".join(
                f"(taskset {point_ids[i] if point_ids is not None else i}"
                f", seed {int(seeds[i])})" for i in idx)
            raise RuntimeError(
                f"jit engine: pending-interrupt table for {len(idx)} "
                f"point(s) still overflowed at the maximum width "
                f"{k_max} — refusing to return metrics from a "
                f"saturated table.  Affected (taskset index, seed): "
                f"[{pts}].  Raise REPRO_JIT_TABLE_MAX (or unset "
                f"REPRO_JIT_TABLE_WIDTH) to widen the retry ladder.")
    return out  # type: ignore[return-value]


def _assemble(b: _VecBatch, s: Dict[str, np.ndarray],
              duration: float) -> List[RunMetrics]:
    """Tail accounting (the event engine's post-loop pass) + RunMetrics
    assembly from the final grouped carry."""
    P = b.P
    out: List[RunMetrics] = []
    status = s["flags"] & _FL_ST_M
    live = (status != _PEND) & b.valid \
        & (duration > s["job_deadline"])
    mi = s["pi"][:, _I_MI:]
    mf = s["pf"][:, _F_MF:]
    mode = s["pi"][:, _I_MODE]
    lms = s["pf"][:, _F_LMS]
    for p in range(P):
        mode_cycles = mf[p, _MF_MC:_MF_MC + 3].copy()
        mode_cycles[mode[p]] += duration - lms[p]
        misses = mi[p, _MI_MISS:_MI_MISS + 2].astype(np.int64).copy()
        for t in live[p].nonzero()[0]:
            misses[int(b.is_hi[p, t])] += 1
        out.append(RunMetrics(
            pi_blocking=AggSamples(mf[p, _MF_PI], mi[p, _MI_PI_N]),
            ci_blocking=AggSamples(mf[p, _MF_CI], mi[p, _MI_CI_N]),
            save_cycles=AggSamples(mf[p, _MF_SAVE], mi[p, _MI_SAVE_N]),
            restore_cycles=AggSamples(mf[p, _MF_RESTORE],
                                      mi[p, _MI_RESTORE_N]),
            jobs={"LO": int(mi[p, _MI_JOBS]),
                  "HI": int(mi[p, _MI_JOBS + 1])},
            done={"LO": int(mi[p, _MI_DONE]),
                  "HI": int(mi[p, _MI_DONE + 1])},
            misses={"LO": int(misses[0]), "HI": int(misses[1])},
            misses_by_mode={k: int(mi[p, _MI_MBM + i])
                            for i, k in enumerate(_MODE_KEYS)},
            lo_released_in_hi=int(mi[p, _MI_LO_REL]),
            lo_done_in_hi=int(mi[p, _MI_LO_DONE]),
            mode_cycles={k: float(mode_cycles[i])
                         for i, k in enumerate(_MODE_KEYS)},
            cs_count=int(mi[p, _MI_CS]),
            exec_cycles=float(mf[p, _MF_EXEC]),
            overhead_cycles=float(mf[p, _MF_OVERHEAD])))
    return out


# ----------------------------------------------------------------------
# Public entry point (called by simulator_vec.simulate_vbatch)
# ----------------------------------------------------------------------

def while_body_kernels(compiled_text: str) -> int:
    """Number of XLA kernels (fusion instructions) in the while-loop
    *body* of one optimized HLO module, excluding free instructions
    (tuple plumbing, constants) — i.e. the number of thunks XLA:CPU
    dispatches per lockstep step.

    The body is identified as the largest non-fused computation in the
    module (the step dominates cond/entry by far).  This walker is the
    single implementation behind :func:`lockstep_kernel_count` and the
    ``tools/graphlint`` budget manifests; keep them on one code path so
    the committed kernel budgets and the perf log never disagree about
    what "a kernel" is."""
    best: List[str] = []
    for m in re.finditer(r"(?m)^(\S[^{\n]*) \{$(.*?)^\}",
                         compiled_text, re.S):
        name, body = m.group(1).strip(), m.group(2)
        if "fused_computation" in name:
            continue
        ops = re.findall(r"(?m)=\s+\S+\s+([\w-]+)\(", body)
        if len(ops) > len(best):
            best = ops
    free = ("get-tuple-element", "constant", "tuple", "parameter",
            "bitcast")
    return sum(1 for op in best if op not in free)


def lockstep_kernel_count(tasksets: Sequence[List[TaskParams]],
                          programs: Dict[str, Program], policy: Policy,
                          *, seeds: Sequence[int], duration: float = 2e7,
                          overrun_prob: float = 0.3, cf: float = 2.0,
                          demand_profile: str = "sampled",
                          table_width: Optional[int] = None,
                          scenario=None) -> int:
    """:func:`while_body_kernels` of the compiled lockstep computation
    for this batch shape/config.

    The grouped-carry refactor's whole point is cutting this number —
    XLA:CPU pays a per-kernel dispatch cost inside ``while_loop``
    bodies.  The *pinned* per-engine budgets live in
    ``tools/graphlint/budgets.json`` (rule ``ir-budget-drift``), which
    is also where ``benchmarks/perf_sim.py`` sources the
    ``xla_kernels`` numbers it logs into ``BENCH_sim.json``; this
    function remains the thin measurement primitive behind both."""
    require_jax()
    nominal = demand_profile == "nominal"
    scenario = get_scenario(scenario)
    loop_scen = scenario if scenario is not None \
        and scenario.affects_demand else None   # as simulate_jbatch
    K = _table_width() if table_width is None else table_width
    b = _VecBatch(tasksets, programs, policy,
                  seeds=[int(s) for s in seeds], duration=duration,
                  overrun_prob=overrun_prob, cf=cf, scenario=scenario)
    run = _compiled_run(policy.use_banks, policy.drop_lo_in_hi,
                        policy.preemption, nominal, _PRUNE_STALE,
                        loop_scen)
    max_steps = _max_steps(b, duration)
    with jax.enable_x64(True):
        tb = _tables(b, seeds)
        sc = {"t_sr": jnp.float64(policy.t_sr),
              "overrun_prob": jnp.float64(overrun_prob),
              "cf": jnp.float64(cf),
              "duration": jnp.float64(duration),
              "max_steps": jnp.int64(max_steps)}
        txt = run.lower(tb, sc, _carry0(b, seeds, K)).compile().as_text()
    return while_body_kernels(txt)


def _plan_spans(n: int, chunk: int,
                devices: int) -> List[Tuple[List[int], int, int]]:
    """Split ``n`` points into ``(indices, real, devices)`` spans.

    A span is one dispatch: a ``d * c`` rectangle (``c`` points per
    logical device) padded — by duplicating its last point — so
    shard_map sees equal shards; padded copies are simulated and
    discarded by the caller.  The first (possibly only) span of a
    small batch shrinks ``d`` and ``c`` to the batch instead of
    simulating a mostly-padding superchunk; later ragged tails pad up
    to the full common shape so they reuse the superchunk's
    compilation — the same rule the single-device engine applied to
    its ragged tail (and ``devices=1`` reproduces the old plan
    exactly).
    """
    spans: List[Tuple[List[int], int, int]] = []
    lo = 0
    while lo < n:
        real = min(chunk * devices, n - lo)
        if lo == 0:
            d = min(devices, real)
            c = min(chunk, -(-real // d))
        else:
            d, c = devices, chunk
        idxs = list(range(lo, lo + real))
        idxs += [idxs[-1]] * (d * c - real)
        spans.append((idxs, real, d))
        lo += real
    return spans


def simulate_jbatch(tasksets: Sequence[List[TaskParams]],
                    programs: Dict[str, Program], policy: Policy, *,
                    seeds: Sequence[int], duration: float = 2e7,
                    overrun_prob: float = 0.3, cf: float = 2.0,
                    batch_size: int = 256,
                    demand_profile: str = "sampled",
                    devices: Optional[int] = None,
                    scenario=None) -> List[RunMetrics]:
    """Fully-compiled batch simulation: one ``lax.while_loop`` per
    superchunk of points, no host work inside the loop, the point axis
    sharded over ``devices`` logical devices (``None``: the
    ``REPRO_DEVICES`` default; see ``runtime.device_config``).

    Per-point keyed RNG draws make the result bit-identical at every
    device count — sharding is purely a throughput knob.

    Prefer :func:`repro.core.simulator_vec.simulate_vbatch` with
    ``select_backend="jit"`` — it validates arguments and routes here.
    See the module docstring for the RNG-equivalence contract.
    """
    require_jax()
    n = len(tasksets)
    if n != len(seeds):
        raise ValueError(f"{n} tasksets vs {len(seeds)} seeds")
    devices = resolve_device_count(devices)
    # small per-device chunks keep the lockstep state cache-resident
    # (64 measured fastest on the BENCH corpus — docs/performance.md)
    chunk = max(1, min(batch_size, _STREAM_CHUNK))
    out: List[RunMetrics] = []
    for idxs, real, d in _plan_spans(n, chunk, devices):
        part = _run_chunk([tasksets[i] for i in idxs], programs, policy,
                          [int(seeds[i]) for i in idxs], duration,
                          overrun_prob, cf, demand_profile,
                          point_ids=idxs, devices=d, scenario=scenario)
        out.extend(part[:real])
    return out
