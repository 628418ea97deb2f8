"""The spans and counters ``MESCServer.step`` keeps on ``Request``: the
per-step records, ``blocked_s``, the context-switch times and bytes,
the named device programs and the profiler annotations.

A tiny dense model serves under a ``VirtualClock`` that its programs
advance by fixed service times, so every stamp is exact."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.scheduler import Policy
from repro.core.serving import MESCServer, MultiLaneServer, Request
from repro.core.task import Crit
from repro.models import lm
from repro.models.common import CPU_RC
from repro.serving.clock import VirtualClock

CFG = get_config("tinyllama-1.1b-smoke")
PARAMS = lm.init_params(CFG, jax.random.PRNGKey(0), CPU_RC)
MAX_LEN = 32
PREFILL_S, DECODE_S = 0.020, 0.010
SAVE_S, RESTORE_S = 0.003, 0.002
SPANS = ("mesc.schedule", "mesc.launch", "mesc.wait", "mesc.finish")


def _server(policy=None, slots=2, cs_costs=None):
    """A server whose programs advance its virtual clock."""
    clk = VirtualClock()
    probe = MESCServer(CFG, PARAMS, max_len=MAX_LEN)

    def decode(*a):
        clk.advance(DECODE_S)
        return probe._decode(*a)

    def prefill(*a):
        clk.advance(PREFILL_S)
        return probe._prefill(*a)
    return MESCServer(CFG, PARAMS, policy=policy or Policy.mesc(),
                      max_len=MAX_LEN, resident_slots=slots,
                      jit_fns=(decode, prefill), clock=clk,
                      cs_costs=cs_costs), clk


def _req(rid, crit, prio, n, plen=8, submitted_at=None):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, priority=prio, max_new_tokens=n, crit=crit,
                   prompt=rng.integers(0, CFG.vocab, plen, dtype=np.int32),
                   submitted_at=submitted_at)


def test_step_records_are_ordered_and_name_each_instruction():
    srv, _ = _server()
    r = _req(0, Crit.LO, 10, n=5, plen=11)
    srv.submit(r)
    srv.run()
    assert [s.kind for s in r.steps] == ["prefill"] + ["decode"] * 4
    assert [s.tokens for s in r.steps] == [11, 0, 0, 0, 0]
    for s in r.steps:
        assert s.start <= s.launch <= s.returned <= s.token <= s.end
        assert s.cs_s == 0.0
    # each program's service time falls inside its launch span
    assert [s.returned - s.launch for s in r.steps] == pytest.approx(
        [PREFILL_S] + [DECODE_S] * 4)
    assert r.steps[0].start == 0.0
    assert r.steps[-1].end == pytest.approx(PREFILL_S + 4 * DECODE_S)
    assert r.exec_s == pytest.approx(PREFILL_S + 4 * DECODE_S)


def test_two_blocking_reads_per_step_and_one_when_length_ends_it():
    # the token and the cache position are read back every step; the
    # step that reaches max_new_tokens ends the request without the
    # position read
    srv, _ = _server()
    r = _req(0, Crit.LO, 10, n=4)
    srv.submit(r)
    srv.run()
    assert [s.syncs for s in r.steps] == [2, 2, 2, 1]


def test_blocked_s_is_the_rest_of_one_lo_instruction_under_mesc():
    srv, _ = _server(Policy.mesc())
    lo = _req(0, Crit.LO, 10, n=12)
    srv.submit(lo)
    for _ in range(3):
        srv.step()
    last = lo.steps[-1]
    # the HI request arrived 4 ms before the LO decode step ended; the
    # server learns of it only now, after that step
    hi = _req(1, Crit.HI, 0, n=3, submitted_at=last.end - 0.004)
    srv.submit(hi)
    assert srv.step() == 1
    srv.run()
    assert hi.blocked_s == pytest.approx(0.004)
    assert hi.started_at == pytest.approx(last.end)
    assert lo.blocked_s == 0.0


def test_blocked_s_is_the_rest_of_the_lo_request_when_not_preemptive():
    srv, _ = _server(Policy.non_preemptive())
    lo = _req(0, Crit.LO, 10, n=12)
    srv.submit(lo)
    for _ in range(3):
        srv.step()
    arrived = lo.steps[-1].end - 0.004
    hi = _req(1, Crit.HI, 0, n=3, submitted_at=arrived)
    srv.submit(hi)
    srv.run()
    assert lo.done and hi.done
    assert hi.started_at == pytest.approx(lo.steps[-1].end)
    assert hi.blocked_s == pytest.approx(lo.steps[-1].end - arrived)
    # 0.004 s of the third step and nine whole decode steps
    assert hi.blocked_s == pytest.approx(0.004 + 9 * DECODE_S)


def test_hi_behind_hi_is_not_blocked_by_lo():
    srv, _ = _server(Policy.mesc())
    first = _req(1, Crit.HI, 0, n=4)
    srv.submit(first)
    srv.step()
    second = _req(2, Crit.HI, 1, n=2, submitted_at=first.steps[-1].start)
    srv.submit(second)
    srv.run()
    assert second.started_at > first.finished_at - 1e-12
    assert second.blocked_s == 0.0


def test_context_save_and_restore_times_and_bytes():
    # two slots: a LO request, a more urgent LO one, then a HI request
    # that needs a third slot and evicts the least urgent
    srv, _ = _server(Policy.mesc(), slots=2, cs_costs=(SAVE_S, RESTORE_S))
    a = _req(0, Crit.LO, 10, n=6)
    srv.submit(a)
    srv.step()
    srv.step()
    moved = sum(x.nbytes for x in jax.tree_util.tree_leaves(a.cache))
    b = _req(1, Crit.LO, 5, n=3)
    srv.submit(b)
    srv.step()
    hi = _req(2, Crit.HI, 0, n=2)
    srv.submit(hi)
    srv.run()
    assert a.saves == 1 and b.saves == 0 and hi.saves == 0
    assert a.save_s == pytest.approx(SAVE_S)
    assert a.restore_s == pytest.approx(RESTORE_S)
    assert a.save_bytes == a.restore_bytes == moved > 0
    assert b.save_s == b.restore_s == b.save_bytes == b.restore_bytes == 0
    # the save falls in the HI request's first step, the restore in the
    # LO request's first step after it; both inside mesc.schedule,
    # whose self time is zero on this clock
    assert hi.steps[0].cs_s == pytest.approx(SAVE_S)
    resumed = next(s for s in a.steps if s.cs_s)
    assert resumed.cs_s == pytest.approx(RESTORE_S)
    for s in a.steps + b.steps + hi.steps:
        assert s.launch - s.start - s.cs_s == pytest.approx(0.0, abs=1e-12)
    assert a.done and len(a.generated) == 6


def test_the_device_programs_are_named():
    srv = MESCServer(CFG, PARAMS, max_len=MAX_LEN)
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    text = srv._prefill.lower(PARAMS, batch).as_text()
    assert "jit_mesc_prefill" in text and "jit__lambda" not in text
    _, cache = srv._prefill(PARAMS, batch)
    text = srv._decode.lower(PARAMS, jnp.zeros((1,), jnp.int32),
                             cache).as_text()
    assert "jit_mesc_decode" in text and "jit__lambda" not in text
    multi = MultiLaneServer(CFG, PARAMS, n_lanes=2, max_len=MAX_LEN)
    assert {(ln._decode.__name__, ln._prefill.__name__)
            for ln in multi.lanes} == {("mesc_decode", "mesc_prefill")}


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_annotations_nest_inside_the_callers_span(tmp_path):
    srv = MESCServer(CFG, PARAMS, max_len=MAX_LEN)
    srv.submit(_req(0, Crit.LO, 10, n=3))
    srv.step()                              # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller.step"):
            srv.step()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    (c0, c1), = ev["caller.step"]
    (s0, s1), = ev["mesc.step"]
    assert c0 <= s0 <= s1 <= c1
    ends = []
    for name in SPANS:
        (a, b), = ev[name]
        assert s0 <= a <= b <= s1, name
        ends.append((a, b))
    # the four children follow one another
    for (_, b), (a, _) in zip(ends, ends[1:]):
        assert b <= a
