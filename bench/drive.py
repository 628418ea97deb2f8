"""Serve a cell's traffic for the measured window.

The loop is a copy of the open-loop serving loop
``repro.launch.serve.run_traffic_real``, kept here so that the program
cannot move it: arrivals go through the program's admission front door
(``repro.serving.FrontDoor``) into ``MESCServer``, and every
``MESCServer.step()`` runs one instruction, a prompt's prefill or one
decode step.  It differs in five ways:

* LO is a closed loop: ``clients`` requests are outstanding, and each
  one that finishes inside the window is replaced at once;
* HI arrivals are due at fixed times from the window's start, and
  every time is read from one clock (``time.perf_counter``), which the
  server is given too;
* it stamps each request's due time, first token and last token
  itself, from that clock, when the ``step()`` that made the token has
  returned (``step()`` has read the token back to the host by then), so
  that the end-to-end metrics read no timestamp the program sets;
* the window closes after ``seconds``; then no LO request is sent and
  the loop runs on only until every HI request that arrived in the
  window has finished (the drain);
* it records a span around each step and keeps the scheduler's
  guarantee under watch: a LO instruction dispatched while a HI
  request was admitted and unfinished is counted, and so is the time
  the garbage collector held the loop inside the window.

With ``annotate`` the steps, admissions and waits are profiler spans
(``bench.step``, ``bench.pump``, ``bench.wait``) inside ``bench.window``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from collections import deque
from typing import Dict, List

import jax
import numpy as np

from bench import loadgen
from repro.core.scheduler import Policy
from repro.core.serving import MESCServer, Request
from repro.core.task import Crit
from repro.serving import FrontDoor

DRAIN_LIMIT_S = 60.0          # a HI request unfinished this long after
                              # the window closed never finishes
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Step:
    t0: float
    t1: float
    kind: str                 # "prefill" or "decode"
    tokens: int               # tokens the instruction feeds
    kv_len: int               # keys a decode step attends to
    in_window: bool


@dataclasses.dataclass
class Window:
    t0: float = 0.0                    # the window's start, perf_counter
    window_s: float = 0.0
    steps: List[Step] = dataclasses.field(default_factory=list)
    hi: List[Request] = dataclasses.field(default_factory=list)
    lo: List[Request] = dataclasses.field(default_factory=list)
    # stamps taken here, by rid (perf_counter seconds)
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    first_at: Dict[int, float] = dataclasses.field(default_factory=dict)
    done_at: Dict[int, float] = dataclasses.field(default_factory=dict)
    lo_tokens: int = 0                 # LO tokens emitted in the window
    lo_sent: int = 0                   # LO requests sent in the window
    lo_while_hi: int = 0               # guarantee broken, count
    hi_unfinished: int = 0
    conservation_ok: bool = True
    admit_late_s: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0                  # compilations inside the window
    gc_s: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass(frozen=True)
class _Arrival:
    """What the front door reads of an arrival (``rid``, ``crit``)."""
    rid: int
    crit: Crit
    spec: loadgen.Spec
    due: float


def build_server(conf: dict, cfg, params, rc) -> MESCServer:
    s = conf["serving"]
    return MESCServer(cfg, params, policy=Policy.mesc(), rc=rc,
                      max_len=s["max_len"],
                      resident_slots=s["resident_slots"],
                      clock=time.perf_counter)


def warm(srv: MESCServer, prompt_lens) -> None:
    """Compile and run every prompt length's prefill, and decode, then
    forget the warm-up requests."""
    for i, n in enumerate(sorted(set(prompt_lens))):
        srv.submit(Request(rid=-1 - i, priority=0,
                           prompt=np.zeros(n, np.int32), max_new_tokens=2,
                           crit=Crit.LO))
    srv.run()
    srv.requests.clear()


def _span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


def drive(srv: MESCServer, conf: dict, mix: dict, seed: int,
          seconds: float, annotate: bool = False) -> Window:
    """Serve the traffic of ``(mix, seed)`` for ``seconds``, then drain."""
    clock = time.perf_counter
    vocab = conf["vocab_size"]
    out = Window()
    hi_specs = loadgen.hi_arrivals(seed, mix, seconds)
    hi_prompts = {s.index: loadgen.prompt_tokens(seed, "HI", s.index,
                                                 s.prompt_len, vocab)
                  for s in hi_specs}
    deck = loadgen.Deck(seed, mix["lo"], "LO")
    clients = mix["lo"]["clients"]
    if clients == "resident_slots":
        clients = conf["serving"]["resident_slots"]
    rids = iter(range(1 << 62))

    def make(a: _Arrival) -> Request:
        s = a.spec
        prompt = hi_prompts[s.index] if s.crit == "HI" else \
            loadgen.prompt_tokens(seed, "LO", s.index, s.prompt_len, vocab)
        r = Request(rid=a.rid, prompt=prompt,
                    max_new_tokens=s.max_new_tokens,
                    priority=s.index if s.crit == "HI"
                    else 1_000_000 + s.index,
                    crit=a.crit, submitted_at=a.due)
        (out.hi if s.crit == "HI" else out.lo).append(r)
        out.due[a.rid] = a.due
        return r

    front = FrontDoor(srv, make_request_fn=make)
    live_hi, live_lo = set(), 0

    def send_lo(now_abs: float) -> None:
        nonlocal live_lo
        front.arrive(_Arrival(next(rids), Crit.LO, deck.spec(out.lo_sent),
                              now_abs))
        out.lo_sent += 1
        live_lo += 1

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT and window_open:
            out.compiles += 1
    window_open = False
    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc_t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        elif window_open:
            out.gc_s.append(time.perf_counter() - gc_t0[0])
    gc.callbacks.append(on_gc)

    pending = deque(hi_specs)
    win = _span(annotate, "bench.window")
    win.__enter__()
    t0 = out.t0 = clock()
    window_open = True
    for _ in range(clients):
        send_lo(t0)
    try:
        while True:
            now = clock() - t0
            if window_open and now >= seconds:
                window_open = False
                out.window_s = now
                out.lo_tokens = sum(len(r.generated) for r in out.lo)
                win.__exit__(None, None, None)
            while pending and pending[0].t <= now:
                s = pending.popleft()
                front.arrive(_Arrival(next(rids), Crit.HI, s, t0 + s.t))
            with _span(annotate, "bench.pump"):
                admitted = front.pump()
            for rid in admitted:
                r = srv.requests[rid]
                if r.crit == Crit.HI:
                    live_hi.add(rid)
                    out.admit_late_s.append(clock() - out.due[rid])
            if not window_open and (not live_hi or
                                    now > seconds + DRAIN_LIMIT_S):
                break
            if live_hi or live_lo:
                hi_waiting = bool(live_hi)
                a = clock()
                with _span(annotate, "bench.step"):
                    rid = srv.step()
                b = clock()
                r = srv.requests[rid]
                n = len(r.generated)
                first = n == 1
                if first:
                    out.first_at[rid] = b
                if r.done:
                    out.done_at[rid] = b
                out.steps.append(Step(
                    a, b, "prefill" if first else "decode",
                    len(r.prompt) if first else 1, len(r.prompt) + n - 1,
                    window_open))
                if r.crit == Crit.LO and hi_waiting:
                    out.lo_while_hi += 1
                if r.done:
                    if r.crit == Crit.HI:
                        live_hi.discard(rid)
                    else:
                        live_lo -= 1
                        if window_open:
                            send_lo(b)
            elif pending:                  # idle: sleep to next arrival
                with _span(annotate, "bench.wait"):
                    time.sleep(max(0.0, min(pending[0].t - now, 0.05)))
            elif window_open:
                with _span(annotate, "bench.wait"):
                    time.sleep(max(0.0, min(seconds - now, 0.05)))
    finally:
        if window_open:
            win.__exit__(None, None, None)
        jax.monitoring.unregister_event_duration_listener(on_event)
        gc.callbacks.remove(on_gc)
    out.hi_unfinished = len(live_hi)
    try:
        front.check_conservation()
    except AssertionError:
        out.conservation_ok = False
    return out
