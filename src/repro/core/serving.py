"""MESC-scheduled model serving: the paper's mechanism driving real JAX
model execution.

Mapping (the TPU adaptation of SS IV/V, see docs/design.md):
  * accelerator instruction  = one bounded-latency jitted dispatch
                               (one decode step / one prefill chunk)
  * scratchpad banks         = a bounded pool of device-resident KV-cache
                               slots (HBM arena); the bank allocator decides
                               which requests stay resident
  * context save / restore   = moving a request's cache pytree to/from host
                               DRAM (step_wise_mvout/mvin analogue)
  * config-copy buffer       = the request's generation config + position
  * task monitor             = LO-budget timers -> mode switch

Every timestamp (``submitted_at``, ``started_at``, ``exec_s``
accumulation, LO-budget checks) is read through an injected *clock* — a
zero-arg callable returning seconds.  The default is the wall clock
(``time.monotonic``); under test and in the fig12 traffic harness a
``repro.serving.clock.VirtualClock`` makes LO-budget overruns, mode
switches and all SLO metrics deterministic (see docs/serving.md for
the clock-injection contract).

Each ``step()`` that runs an instruction appends a :class:`StepRecord`
to ``Request.steps`` of the request it ran: five clock stamps that
delimit the spans ``mesc.schedule`` (scheduler pass, with the
``mesc.save``/``mesc.restore`` context switches inside it),
``mesc.launch`` (input copy and program launch), ``mesc.wait`` (the
instruction in flight as the host sees it, up to its token on the
host) and ``mesc.finish`` (termination check, bank flush), all inside
``mesc.step``, and the number of blocking device reads.  The same spans
go into the profiler trace as ``jax.profiler.TraceAnnotation``s, which
cost well under a microsecond each while no trace is recorded.

Scheduling follows scheduler.Policy + mode rules: HI requests preempt LO
requests at instruction (= decode-step) boundaries; LO requests are never
dropped (imprecise-MCS stance), they run when no HI request is active.

Multi-accelerator scale-out (docs/scheduling.md): :class:`MultiLaneServer`
runs one :class:`MESCServer` dispatch lane per virtual accelerator, all
lanes drawing KV-cache residency from one shared :class:`KVSlotArena`
carved into per-lane quotas; requests are partitioned onto lanes with the
same first-fit / worst-fit / criticality-aware heuristics as
``core.platform.partition``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro.configs.base import ArchConfig
from repro.core.scheduler import Mode, Policy
from repro.core.task import Crit
from repro.models import lm
from repro.models.common import RuntimeConfig, CPU_RC

_span = jax.profiler.TraceAnnotation


class StepRecord(NamedTuple):
    """One instruction as ``MESCServer.step`` ran it; every stamp is
    read from the server's clock.  Spans: ``mesc.step`` [start, end),
    ``mesc.schedule`` [start, launch) of which ``cs_s`` went to context
    saves and restores, ``mesc.launch`` [launch, returned),
    ``mesc.wait`` [returned, token), ``mesc.finish`` [token, end)."""
    kind: str                       # "prefill" or "decode"
    tokens: int                     # prompt tokens fed (0 for a decode)
    start: float
    launch: float
    returned: float
    token: float
    end: float
    syncs: int                      # blocking device-to-host reads
    cs_s: float


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int
    priority: int
    crit: Crit
    lo_budget_s: float = 1e9        # LO-WCET analogue (wall clock)
    # runtime state
    generated: List[int] = dataclasses.field(default_factory=list)
    cache: Optional[dict] = None    # device (resident) or host (saved)
    resident: bool = False
    done: bool = False
    started_at: Optional[float] = None
    exec_s: float = 0.0
    first_token_at: Optional[float] = None
    # stamped by submit() from the server clock unless the caller (the
    # admission front door) already set the true arrival time
    submitted_at: Optional[float] = None
    finished_at: Optional[float] = None
    preemptions: int = 0
    saves: int = 0
    # spans and counters (module docstring): one record per instruction
    steps: List[StepRecord] = dataclasses.field(default_factory=list)
    save_s: float = 0.0             # context saves: time, bytes moved
    save_bytes: int = 0
    restore_s: float = 0.0
    restore_bytes: int = 0
    # HI only: time after submitted_at in which a LO instruction held
    # the lane, before this request's first dispatch
    blocked_s: float = 0.0


def _nbytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


def _cast_bytes(params, step_params) -> int:
    """Bytes of the leaves of ``step_params`` that are not ``params``'
    own: the copy ``lm.step_params`` made."""
    return sum(b.nbytes for a, b in zip(jax.tree_util.tree_leaves(params),
                                        jax.tree_util.tree_leaves(step_params))
               if a is not b)


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        tree)


def _order(fn, p, cache):
    """The dim order, major to minor, in which the compiler lays out
    each leaf of the cache that ``fn(p, tokens, cache)`` takes and
    returns, when the layout is left to it (``Layout.AUTO``).  Only
    compiled, never run: the order is read from the program."""
    auto = Format(Layout.AUTO)
    probe = jax.jit(fn, in_shardings=(None, None, auto),
                    out_shardings=(None, auto)).lower(
        p, jax.ShapeDtypeStruct((1,), jnp.int32), _shapes(cache)).compile()
    return {k: tuple(f.layout.major_to_minor)
            for k, f in probe.input_formats[0][2].items()}


def _permute(tree, order, inverse=False):
    """``tree``'s leaves transposed into ``order`` (or back out of it);
    a leaf already in its order is returned as it is."""
    def one(k, a):
        o = order[k]
        if o == tuple(range(a.ndim)):
            return a
        return jnp.transpose(a, np.argsort(o) if inverse else o)
    return {k: one(k, a) for k, a in tree.items()}


class _Decode:
    """``jit_mesc_decode``: one decode step that consumes the cache it
    is given.  On an accelerator the cache is donated and each step
    writes its K/V rows into it in place, so a caller must not use a
    cache again once it has passed it in.  The CPU backend, where the
    program is tested, keeps the caller's cache, so that a test may hand
    a decode's input back as its output (the benchmark's stale-cache
    fault, ``tests/bench/test_bench_faults.py``).

    Where the cache lies in memory is the compiler's choice: ``bind``
    compiles the step once with the cache's layout left to the compiler
    (``_order``) and keeps the dim order it chose in ``order``.  The
    cache the server holds is stored transposed into that order, so its
    plain row-major layout is the one the compiler chose, and the step
    transposes it back to (L, B, S, Hkv, Dh) inside the program, where
    that costs no copy.  Every program boundary thus has the default
    layout: an executable loaded from JAX's persistent compilation cache
    loses a non-default output layout, and the next call would refuse
    its own cache.  ``logical`` gives a stored cache's plain view."""

    def __init__(self, fn):
        self.__name__ = fn.__name__
        self._fn = fn
        self._jit = None
        self.order = None

    def bind(self, p, cache):
        """Choose the stored order for a cache of the logical shapes of
        ``cache``, once, for weights ``p``; the order.  The decode binds
        itself to the first cache it meets where no prefill has bound it,
        a cache that ``lm.init_cache`` made."""
        if self.order is None:
            order, fn = _order(self._fn, p, cache), self._fn
            donate = next(iter(jax.tree_util.tree_leaves(p)[0].sharding
                               .device_set)).platform != "cpu"

            def mesc_decode(p, t, c):
                logits, c = fn(p, t, _permute(c, order, inverse=True))
                return logits, _permute(c, order)
            self._jit = jax.jit(mesc_decode,
                                donate_argnums=(2,) if donate else ())
            self.order = order
        return self.order

    def stored(self, cache):
        """A logical cache in the stored order."""
        return _permute(cache, self.order)

    def logical(self, cache):
        """A stored cache's plain (L, B, S, Hkv, Dh) view."""
        return _permute(cache, self.order, inverse=True)

    def lower(self, p, t, c):
        self.bind(p, c)
        return self._jit.lower(p, t, c)

    def __call__(self, p, t, c):
        self.bind(p, c)
        return self._jit(p, t, c)


class _Prefill:
    """``jit_mesc_prefill``, emitting its cache in the decode's stored
    order, so that no request pays a relayout at its first decode."""

    def __init__(self, fn, decode: _Decode):
        self.__name__ = fn.__name__
        self._fn, self._decode = fn, decode
        self._jit = None

    def _bound(self, p, b):
        if self._jit is None:
            # a one-token prompt's cache has the shapes of every cache
            # that is decoded (a prompt of max_len or more is not)
            one = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape[:1] + (1,)
                                               + a.shape[2:], a.dtype), b)
            fn = self._fn
            order = self._decode.bind(p, jax.eval_shape(fn, p, one)[1])

            def mesc_prefill(p, b):
                logits, cache = fn(p, b)
                return logits, _permute(cache, order)
            self._jit = jax.jit(mesc_prefill)
        return self._jit

    def lower(self, p, b):
        return self._bound(p, b).lower(p, b)

    def __call__(self, p, b):
        return self._bound(p, b)(p, b)


def _programs(cfg: ArchConfig, rc: RuntimeConfig, max_len: int):
    """The ``(decode, prefill)`` pair, named so that XLA calls the
    modules ``jit_mesc_decode`` and ``jit_mesc_prefill``."""
    def mesc_decode(p, t, c):
        return lm.decode_step(cfg, p, t, c, rc)

    def mesc_prefill(p, b):
        return lm.prefill(cfg, p, b, rc, max_len=max_len)
    decode = _Decode(mesc_decode)
    return decode, _Prefill(mesc_prefill, decode)


class KVSlotArena:
    """Shared pool of device-resident KV-cache slots, carved into
    per-lane quotas (the multi-lane analogue of the scratchpad banks).

    Quotas statically partition the arena — ``sum(quotas) == total`` —
    so each lane's admission check is local (no cross-lane eviction),
    exactly like banklocked scratchpad banks partitioned across
    accelerator instances.
    """

    def __init__(self, total_slots: int, n_lanes: int = 1,
                 quotas: Optional[List[int]] = None):
        if quotas is None:
            base, rem = divmod(total_slots, n_lanes)
            quotas = [base + (1 if i < rem else 0) for i in range(n_lanes)]
        if len(quotas) != n_lanes or sum(quotas) != total_slots:
            raise ValueError(f"quotas {quotas} must partition "
                             f"{total_slots} slots over {n_lanes} lanes")
        if min(quotas) < 1:
            raise ValueError(f"every lane needs >= 1 slot, got {quotas}")
        self.total_slots = total_slots
        self.quotas = list(quotas)
        self._held: List[set] = [set() for _ in range(n_lanes)]

    def held(self, lane: int) -> int:
        return len(self._held[lane])

    def can_admit(self, lane: int) -> bool:
        return self.held(lane) < self.quotas[lane]

    def acquire(self, lane: int, rid: int) -> None:
        if rid not in self._held[lane] and not self.can_admit(lane):
            raise RuntimeError(f"lane {lane} over quota "
                               f"({self.quotas[lane]} slots)")
        self._held[lane].add(rid)

    def release(self, lane: int, rid: int) -> None:
        self._held[lane].discard(rid)


class MESCServer:
    """Single-model mixed-criticality serving loop (batch size 1 per
    request; the accelerator — one dispatch lane — is the shared
    resource).  Standalone it owns a private one-lane arena sized
    ``resident_slots``; under :class:`MultiLaneServer` it is one lane of
    a shared arena."""

    def __init__(self, cfg: ArchConfig, params, *, policy: Policy = None,
                 rc: RuntimeConfig = CPU_RC, max_len: int = 64,
                 resident_slots: int = 2,
                 arena: Optional[KVSlotArena] = None, lane: int = 0,
                 jit_fns=None,
                 clock: Callable[[], float] = time.monotonic,
                 cs_costs: Optional[Tuple[float, float]] = None):
        self.cfg = cfg
        self.params = params
        # the step programs read the weights in the compute dtype, cast
        # once here (lm.step_params) and not on every call; cast_bytes
        # is the size of that copy, 0 when nothing needed a cast
        self._step_params = lm.step_params(cfg, params, rc)
        self.cast_bytes = _cast_bytes(params, self._step_params)
        self.rc = rc
        self.policy = policy or Policy.mesc()
        self.max_len = max_len
        self.arena = arena or KVSlotArena(resident_slots, 1)
        self.lane = lane
        self.mode = Mode.LO
        self.requests: Dict[int, Request] = {}
        self.current: Optional[int] = None
        # the clock-injection contract (docs/serving.md): EVERY
        # timestamp below reads self.clock(), never time.monotonic()
        self.clock = clock
        self._cs_save_s, self._cs_restore_s = cs_costs or (0.0, 0.0)
        self._decode, self._prefill = jit_fns or _programs(cfg, rc, max_len)
        # what the current step() has counted so far
        self._syncs, self._cs_s = 0, 0.0
        # blocked_s bookkeeping: the last step, if it ran LO, as
        # (start, end); the HI requests submitted and not yet started
        self._last_lo: Optional[Tuple[float, float]] = None
        self._hi_waiting: Dict[int, Request] = {}

    def _charge(self, dt: float) -> None:
        """Charge a modeled context-switch cost to an advanceable
        (virtual) clock; a wall clock pays real save/restore latency
        through the jax transfers themselves, so this is a no-op."""
        adv = getattr(self.clock, "advance", None)
        if adv is not None and dt:
            adv(dt)

    # -- bank pool ----------------------------------------------------------
    def _resident(self) -> List[Request]:
        return [r for r in self.requests.values()
                if r.resident and not r.done]

    def _evict(self, victim: Request):
        t = self.clock()
        with _span("mesc.save"):
            victim.cache = jax.device_get(victim.cache)   # step_wise_mvout
            self._charge(self._cs_save_s)
        dt = self.clock() - t
        victim.save_s += dt
        victim.save_bytes += _nbytes(victim.cache)
        self._cs_s += dt
        victim.resident = False
        victim.saves += 1
        self.arena.release(self.lane, victim.rid)

    def _make_room(self, incoming: Request):
        """Evict (context-save) lowest-priority resident request if the
        lane's quota is full — zero work when a slot is free (Obs. 1)."""
        res = [r for r in self._resident() if r.rid != incoming.rid]
        while res and not self.arena.can_admit(self.lane):
            victim = max(res, key=lambda r: r.priority)
            self._evict(victim)
            res.remove(victim)

    def _restore(self, r: Request):
        self.arena.acquire(self.lane, r.rid)
        if r.cache is not None and not r.resident:
            t = self.clock()
            r.restore_bytes += _nbytes(r.cache)
            with _span("mesc.restore"):
                r.cache = jax.device_put(r.cache)         # step_wise_mvin
                self._charge(self._cs_restore_s)
            dt = self.clock() - t
            r.restore_s += dt
            self._cs_s += dt
        r.resident = True

    # -- scheduling ---------------------------------------------------------
    def submit(self, r: Request):
        if r.submitted_at is None:         # front door may pre-stamp the
            r.submitted_at = self.clock()  # true arrival time
        self.requests[r.rid] = r
        if r.crit == Crit.HI and r.started_at is None:
            # the LO step it arrived during ran before the server knew
            # of it: count that step's rest now
            if self._last_lo is not None:
                start, end = self._last_lo
                r.blocked_s += max(0.0, end - max(start, r.submitted_at))
            self._hi_waiting[r.rid] = r

    def _eligible(self) -> List[Request]:
        live = [r for r in self.requests.values() if not r.done]
        his = [r for r in live if r.crit == Crit.HI]
        out = []
        for r in live:
            if r.crit == Crit.HI or self.mode == Mode.LO:
                out.append(r)
            elif self.policy.drop_lo_in_hi:
                continue
            elif his:
                continue                   # LO only when no HI active
            else:
                out.append(r)
        return out

    def _pick(self) -> Optional[Request]:
        el = self._eligible()
        if not el:
            live = [r for r in self.requests.values() if not r.done]
            return min(live, key=lambda r: r.priority) if live else None
        return min(el, key=lambda r: r.priority)

    def eligible_order(self) -> List[Request]:
        """The lane's service order right now: eligible requests sorted
        the way successive ``_pick`` calls would drain them (priority,
        rid tiebreak), with a non-preemptive owner pinned first.  Used
        by the admission-invariant property tests — with the workload
        convention HI priorities < LO priorities, no LO request may
        ever precede a HI request here."""
        el = sorted(self._eligible(), key=lambda r: (r.priority, r.rid))
        if self.policy.preemption == "none" and self.current is not None:
            cur = self.requests.get(self.current)
            if cur is not None and not cur.done:
                el = [cur] + [r for r in el if r.rid != cur.rid]
        return el

    def _mode_tick(self):
        live = [r for r in self.requests.values() if not r.done]
        if not live:
            self.mode = Mode.LO            # idle -> revert
            return
        for r in live:                     # monitor: LO-budget timers
            # ANY request overrunning its LO-criticality budget trips
            # the switch: an overrunning HI request needs its HI budget
            # (the paper's rule), and an overrunning LO request is
            # demoted to run only when no HI request is active
            # (imprecise-MCS stance; regression-tested at a
            # deterministic virtual time in tests/test_serving.py)
            if r.exec_s > r.lo_budget_s and self.mode == Mode.LO:
                self.mode = Mode.HI        # (transition is instantaneous
                                           #  here: saves are synchronous)

    # -- the serve loop -----------------------------------------------------
    def _schedule(self) -> Optional[Request]:
        """The scheduler pass: the request to run next, made resident,
        or None if idle."""
        self._mode_tick()
        r = self._pick()
        # non-preemptive baseline: a started request owns the accelerator
        if (self.policy.preemption == "none" and self.current is not None):
            cur = self.requests.get(self.current)
            if cur is not None and not cur.done:
                r = cur
        if r is None:
            return None
        if r.rid != self.current and self.current is not None:
            prev = self.requests.get(self.current)
            if prev is not None and not prev.done:
                prev.preemptions += 1
        self.current = r.rid
        if not r.resident:
            self._make_room(r)
            self._restore(r)
        return r

    def _read(self, x) -> int:
        """``int(x)``; a read from the device is counted in the step."""
        self._syncs += isinstance(x, jax.Array)
        return int(x)

    def step(self) -> Optional[int]:
        """One scheduler invocation + one instruction, which emits one
        token: the prompt's prefill for a request's first token, one
        decode step for every later one.  Returns the rid that ran, or
        None if idle."""
        start = self.clock()
        self._syncs, self._cs_s = 0, 0.0
        with _span("mesc.step"):
            with _span("mesc.schedule"):
                r = self._schedule()
            if r is None:
                self._last_lo = None
                return None
            launch = self.clock()
            if r.started_at is None:
                r.started_at = launch
                self._hi_waiting.pop(r.rid, None)
            prefill = r.cache is None
            with _span("mesc.launch"):
                if prefill:                # last-position prefill logits
                    logits, r.cache = self._prefill(
                        self._step_params,
                        {"tokens": jnp.asarray(r.prompt[None])})
                else:
                    logits, r.cache = self._decode(
                        self._step_params,
                        jnp.asarray([r.generated[-1]], jnp.int32), r.cache)
            returned = self.clock()
            with _span("mesc.wait"):
                tok = self._read(jnp.argmax(logits[0]))
            token = self.clock()
            r.generated.append(tok)
            r.exec_s += token - launch
            if r.first_token_at is None:
                r.first_token_at = token
            with _span("mesc.finish"):
                if len(r.generated) >= r.max_new_tokens \
                        or self._read(r.cache["pos"]) >= self.max_len - 1:
                    r.done = True
                    r.finished_at = self.clock()
                    r.resident = False
                    r.cache = None         # flush banks
                    self.arena.release(self.lane, r.rid)
                    self.current = None
            end = self.clock()
        r.steps.append(StepRecord(
            "prefill" if prefill else "decode",
            len(r.prompt) if prefill else 0,
            start, launch, returned, token, end, self._syncs, self._cs_s))
        if r.crit == Crit.LO:
            self._last_lo = (start, end)
            for h in self._hi_waiting.values():
                h.blocked_s += max(0.0, end - max(start, h.submitted_at))
        else:
            self._last_lo = None
        return r.rid

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        for _ in range(max_steps):
            if self.step() is None:
                break
        return self.requests


# ----------------------------------------------------------------------
# Multi-accelerator serving: one dispatch lane per virtual accelerator
# ----------------------------------------------------------------------

class MultiLaneServer:
    """Partitioned MESC serving over N virtual accelerator lanes.

    Each lane is a full :class:`MESCServer` — its own SS IV mode
    machine, preemption policy, and slice of the shared
    :class:`KVSlotArena` — and all lanes share one pair of jitted
    prefill/decode dispatch functions (compiled once).  Requests are
    statically partitioned onto lanes at submit time with the platform
    heuristics (``core.platform``): ``crit_aware`` spreads HI requests
    round-robin and steers LO requests toward HI-light lanes,
    ``worst_fit`` balances live-request counts, ``first_fit`` packs.
    ``step()`` advances every lane by one instruction (= decode step),
    so lanes progress in lockstep rounds; a HI request only ever
    contends with its own lane's requests — the partitioned-blocking
    win the multi-accelerator analysis (``wcrt.analyze_partitioned``)
    quantifies.  The weights are cast to the compute dtype once, here
    (``lm.step_params``), and every lane is handed that one copy as its
    ``params``; ``cast_bytes`` is its size.
    """

    def __init__(self, cfg: ArchConfig, params, *, n_lanes: int = 2,
                 policy: Policy = None, rc: RuntimeConfig = CPU_RC,
                 max_len: int = 64, total_slots: Optional[int] = None,
                 heuristic: str = "crit_aware", jit_fns=None,
                 clocks: Optional[Sequence[Callable[[], float]]] = None,
                 cs_costs: Optional[Tuple[float, float]] = None):
        from repro.core.platform import HEURISTICS
        if heuristic not in HEURISTICS:
            raise ValueError(f"unknown heuristic {heuristic!r}")
        total_slots = total_slots if total_slots is not None else 2 * n_lanes
        self.arena = KVSlotArena(total_slots, n_lanes)
        self.heuristic = heuristic
        step_params = lm.step_params(cfg, params, rc)
        self.cast_bytes = _cast_bytes(params, step_params)
        # dispatch functions: one shared jitted pair by default; the
        # virtual-clock harness injects per-lane (decode, prefill)
        # pairs instead (each bound to its own lane clock)
        if jit_fns is None:
            per_lane_fns = [_programs(cfg, rc, max_len)] * n_lanes
        elif callable(jit_fns[0]):                     # one shared pair
            per_lane_fns = [tuple(jit_fns)] * n_lanes
        else:                                          # per-lane pairs
            if len(jit_fns) != n_lanes:
                raise ValueError(f"got {len(jit_fns)} jit_fns pairs "
                                 f"for {n_lanes} lanes")
            per_lane_fns = [tuple(fns) for fns in jit_fns]
        if clocks is None:
            per_lane_clocks: List[Callable[[], float]] = \
                [time.monotonic] * n_lanes
        elif callable(clocks):                         # one shared clock
            per_lane_clocks = [clocks] * n_lanes
        else:
            if len(clocks) != n_lanes:
                raise ValueError(f"got {len(clocks)} clocks for "
                                 f"{n_lanes} lanes")
            per_lane_clocks = list(clocks)
        self.lanes: List[MESCServer] = [
            MESCServer(cfg, step_params, policy=policy, rc=rc,
                       max_len=max_len,
                       arena=self.arena, lane=i, jit_fns=per_lane_fns[i],
                       clock=per_lane_clocks[i], cs_costs=cs_costs)
            for i in range(n_lanes)]
        self.lane_of: Dict[int, int] = {}
        # lanes currently inside a fault-scenario outage window: the
        # partitioner never places new requests on them (in-flight work
        # stays put and resumes when the driver unblocks the lane)
        self.blocked_lanes: set = set()

    # -- request -> lane partitioning ---------------------------------------
    def _live(self, lane: MESCServer, crit: Optional[Crit] = None) -> int:
        return sum(1 for r in lane.requests.values() if not r.done
                   and (crit is None or r.crit == crit))

    def _assign(self, r: Request) -> int:
        n = len(self.lanes)
        # blocked (outage-window) lanes are excluded while any healthy
        # lane exists; with every lane blocked fall back to all lanes
        # so a direct submit still lands somewhere deterministic
        cand = [i for i in range(n) if i not in self.blocked_lanes] \
            or list(range(n))
        if self.heuristic == "first_fit":
            return next((i for i in cand
                         if self._live(self.lanes[i]) < self.arena.quotas[i]),
                        min(cand,
                            key=lambda i: self._live(self.lanes[i])))
        if self.heuristic == "worst_fit":
            return min(cand, key=lambda i: self._live(self.lanes[i]))
        # crit_aware: spread HI (tiebreak on total load so a HI request
        # lands on an idle lane, not behind running LO work); LO avoids
        # HI-loaded lanes (x2 weight)
        if r.crit == Crit.HI:
            return min(cand,
                       key=lambda i: (self._live(self.lanes[i], Crit.HI),
                                      self._live(self.lanes[i])))
        return min(cand,
                   key=lambda i: self._live(self.lanes[i], Crit.LO)
                   + 2 * self._live(self.lanes[i], Crit.HI))

    def submit(self, r: Request) -> int:
        lane = self._assign(r)
        self.lane_of[r.rid] = lane
        self.lanes[lane].submit(r)
        return lane

    # -- the serve loop -----------------------------------------------------
    def step(self) -> List[Optional[int]]:
        """One lockstep round: each lane runs one scheduler invocation
        + one instruction.  Returns the rid that ran per lane."""
        return [lane.step() for lane in self.lanes]

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        for _ in range(max_steps):
            if all(r is None for r in self.step()):
                break
        return self.requests

    @property
    def requests(self) -> Dict[int, Request]:
        out: Dict[int, Request] = {}
        for lane in self.lanes:
            out.update(lane.requests)
        return out

    def platform_mode(self) -> Mode:
        from repro.core.scheduler import MODE_SEVERITY
        return max((lane.mode for lane in self.lanes),
                   key=MODE_SEVERITY.__getitem__)
