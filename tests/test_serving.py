"""MESC model-serving integration (core/serving.py) + int8 Adam +
the deterministic virtual-clock serving harness (ServingCase)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.scheduler import Mode, Policy
from repro.core.serving import MESCServer, Request
from repro.core.task import Crit
from repro.models import lm
from repro.models.common import CPU_RC
from repro.optim import OptConfig, adamw_update, init_opt_state

from harness import (ServingCase, assert_serving_deterministic,
                     run_serving_case)

CFG = get_config("tinyllama-1.1b-smoke")
PARAMS = lm.init_params(CFG, jax.random.PRNGKey(0), CPU_RC)


def _req(rid, crit, prio, n=6):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, priority=prio,
                   prompt=rng.integers(0, CFG.vocab, 8, dtype=np.int32),
                   max_new_tokens=n, crit=crit)


class TestMESCServing:
    def test_hi_preempts_lo_at_instruction_boundary(self):
        srv = MESCServer(CFG, PARAMS, policy=Policy.mesc(), max_len=32)
        lo = _req(0, Crit.LO, 10, n=12)
        srv.submit(lo)
        for _ in range(2):
            srv.step()
        hi = _req(1, Crit.HI, 0, n=3)
        srv.submit(hi)
        order = [srv.step() for _ in range(4)]
        assert order[0] == 1, order        # HI runs at the very next step
        srv.run()
        assert srv.requests[1].done and srv.requests[0].done  # LO not dropped

    def test_served_tokens_are_the_greedy_continuation(self):
        """The prefill's last-position logits give the first token and
        each decode step the next: the served tokens are the argmax of
        one forward pass over prompt + generated[:-1]."""
        srv = MESCServer(CFG, PARAMS, policy=Policy.mesc(), max_len=32)
        r = _req(0, Crit.LO, 10, n=6)
        srv.submit(r)
        srv.run()
        fed = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        logits = lm.forward(CFG, PARAMS, {"tokens": jnp.asarray(fed[None])},
                            CPU_RC)[0]
        want = np.asarray(logits[0, len(r.prompt) - 1:]).argmax(-1)
        assert r.generated == list(want)

    def test_non_preemptive_runs_to_completion(self):
        srv = MESCServer(CFG, PARAMS, policy=Policy.non_preemptive(),
                         max_len=32)
        lo = _req(0, Crit.LO, 10, n=8)
        srv.submit(lo)
        srv.step()
        srv.submit(_req(1, Crit.HI, 0, n=2))
        order = [srv.step() for _ in range(7)]
        assert all(r == 0 for r in order), order  # LO holds the accelerator

    def test_bank_pool_eviction_and_restore(self):
        """Cache save/restore across the bank pool is output-preserving."""
        # reference: uninterrupted generation
        srv = MESCServer(CFG, PARAMS, policy=Policy.mesc(), max_len=32,
                         resident_slots=1)
        a, b = _req(0, Crit.LO, 1, n=6), _req(1, Crit.LO, 2, n=6)
        srv.submit(a)
        [srv.step() for _ in range(3)]
        srv.submit(b)                      # same priority class; pool size 1
        srv.run()
        saves = a.saves + b.saves
        ref = MESCServer(CFG, PARAMS, policy=Policy.mesc(), max_len=32,
                         resident_slots=4)
        a2, b2 = _req(0, Crit.LO, 1, n=6), _req(1, Crit.LO, 2, n=6)
        ref.submit(a2)
        [ref.step() for _ in range(3)]
        ref.submit(b2)
        ref.run()
        assert a.generated == a2.generated
        assert b.generated == b2.generated


class TestMultiLaneServing:
    def test_lanes_partition_and_preserve_output(self):
        """Two dispatch lanes over a shared KV arena generate the same
        tokens as one lane, with HI requests spread across lanes."""
        from repro.core.serving import MultiLaneServer
        msrv = MultiLaneServer(CFG, PARAMS, n_lanes=2, max_len=32,
                               total_slots=2, heuristic="crit_aware")
        reqs = [_req(0, Crit.HI, 0), _req(1, Crit.HI, 1),
                _req(2, Crit.LO, 10), _req(3, Crit.LO, 11)]
        lanes = [msrv.submit(r) for r in reqs]
        assert sorted(lanes[:2]) == [0, 1]     # HI spread one per lane
        msrv.run()
        assert all(r.done for r in msrv.requests.values())
        # reference: single-lane serving of the same requests
        ref = MESCServer(CFG, PARAMS, max_len=32, resident_slots=4)
        ref_reqs = [_req(0, Crit.HI, 0), _req(1, Crit.HI, 1),
                    _req(2, Crit.LO, 10), _req(3, Crit.LO, 11)]
        for r in ref_reqs:
            ref.submit(r)
        ref.run()
        for r, rr in zip(reqs, ref_reqs):
            assert r.generated == rr.generated
        # the shared arena never exceeded per-lane quotas
        assert all(msrv.arena.held(i) == 0 for i in range(2))

    def test_non_preemptive_lane_isolation(self):
        """A LO request holding one lane cannot block a HI request
        partitioned onto the other lane (the fig11 story end-to-end)."""
        from repro.core.serving import MultiLaneServer
        msrv = MultiLaneServer(CFG, PARAMS, n_lanes=2, max_len=32,
                               policy=Policy.non_preemptive())
        lo = _req(0, Crit.LO, 10, n=10)
        msrv.submit(lo)
        msrv.step()                            # LO owns its lane
        hi = _req(1, Crit.HI, 0, n=2)
        hi_lane = msrv.submit(hi)
        assert hi_lane != msrv.lane_of[0]
        ran = msrv.step()
        assert ran[hi_lane] == 1               # HI runs immediately


class TestLaunchServe:
    """The serving entry point: model and runtime config by platform,
    cache length and runtime config threaded into the servers."""

    def test_load_model_off_tpu_is_the_f32_smoke_config(self):
        from repro.launch import serve
        cfg, _, rc = serve.load_model(None)
        assert cfg.name == "tinyllama-1.1b-smoke" and rc is CPU_RC

    def test_load_model_on_tpu_is_bf16_at_full_width(self, monkeypatch):
        from repro.launch import serve
        from repro.models.common import DEFAULT_RC
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(lm, "init_params", lambda cfg, key, rc: None)
        cfg, _, rc = serve.load_model(None)
        assert (cfg.name, cfg.n_layers, cfg.d_model) == \
            ("tinyllama-1.1b", 22, 2048)
        assert rc is DEFAULT_RC and rc.compute_dtype == jnp.bfloat16

    @pytest.mark.parametrize("lanes", [1, 2])
    def test_run_threads_rc_and_max_len(self, monkeypatch, lanes):
        from repro.launch import serve
        made = []

        def spy(*a, **kw):
            made.append(kw)
            return make(*a, **kw)
        make = serve._make_server
        monkeypatch.setattr(serve, "_make_server", spy)
        reqs = serve.make_requests(CFG, np.random.default_rng(0), n_lo=2,
                                   n_hi=1, lo_len=6, hi_len=2,
                                   prompt_len=12)
        out = serve.run(CFG, PARAMS, Policy.mesc(), reqs, lanes=lanes,
                        rc=CPU_RC, max_len=40)
        assert made == [dict(rc=CPU_RC, max_len=40, lanes=lanes,
                             heuristic="crit_aware")]
        assert all(len(r.prompt) == 12 and r.done
                   and len(r.generated) == r.max_new_tokens
                   for r in out.values())
        assert sorted(out) == [0, 1, 2]      # the warm-up requests left


def _cast_leaf_shapes(params):
    """Shapes of the leaves ``lm.step_params`` casts for a dense model."""
    sel = [params["embed"], params.get("lm_head")] + [
        params["blocks"][part][w] for part, ws in
        (("attn", ("wq", "wk", "wv", "wo")), ("mlp", ("w1", "w2", "w3")))
        for w in ws]
    return [x.shape for x in sel if x is not None]


class TestStepWeights:
    """The step programs read a compute-dtype copy of the weights made
    once at server build (``lm.step_params``), not the caller's f32
    tree: the same tokens, one copy for every lane."""

    def test_bf16_server_serves_the_tokens_of_the_f32_fed_programs(self):
        from repro.core.serving import _programs
        from repro.models.common import DEFAULT_RC
        srv = MESCServer(CFG, PARAMS, rc=DEFAULT_RC, max_len=32)
        assert srv.params is PARAMS
        reqs = [_req(0, Crit.LO, 10, n=6), _req(1, Crit.HI, 0, n=4),
                _req(2, Crit.LO, 11, n=5)]
        for r in reqs:
            srv.submit(r)
        srv.run()
        decode, prefill = _programs(CFG, DEFAULT_RC, 32)
        for r in reqs:
            logits, cache = prefill(PARAMS,
                                    {"tokens": jnp.asarray(r.prompt[None])})
            want = [int(jnp.argmax(logits[0]))]
            while len(want) < r.max_new_tokens:
                logits, cache = decode(PARAMS, jnp.asarray([want[-1]]),
                                       cache)
                want.append(int(jnp.argmax(logits[0])))
            assert r.generated == want

    def test_cast_bytes_counts_the_copy(self):
        from repro.models.common import DEFAULT_RC, RuntimeConfig
        srv = MESCServer(CFG, PARAMS, rc=DEFAULT_RC, max_len=32)
        assert srv.cast_bytes == sum(
            2 * int(np.prod(s)) for s in _cast_leaf_shapes(PARAMS))
        bf16 = lm.init_params(CFG, jax.random.PRNGKey(0),
                              RuntimeConfig(param_dtype=jnp.bfloat16))
        assert MESCServer(CFG, bf16, rc=DEFAULT_RC).cast_bytes == 0
        assert MESCServer(CFG, PARAMS, rc=CPU_RC).cast_bytes == 0

    def test_lanes_share_one_cast_copy(self):
        from repro.core.serving import MultiLaneServer
        from repro.models.common import DEFAULT_RC
        msrv = MultiLaneServer(CFG, PARAMS, n_lanes=2, rc=DEFAULT_RC,
                               max_len=32)
        one = MESCServer(CFG, PARAMS, rc=DEFAULT_RC, max_len=32)
        assert msrv.cast_bytes == one.cast_bytes > 0
        a, b = (jax.tree_util.tree_leaves(ln._step_params)
                for ln in msrv.lanes)
        assert all(x is y for x, y in zip(a, b))
        w1 = msrv.lanes[0]._step_params["blocks"]["mlp"]["w1"]
        assert w1.dtype == jnp.bfloat16

    def test_decode_program_takes_no_f32_weight(self):
        """The lowered ``jit_mesc_decode`` has no f32 parameter of a cast
        weight's shape; fed the caller's tree, as before, it has."""
        import re
        from repro.models.common import DEFAULT_RC
        srv = MESCServer(CFG, PARAMS, rc=DEFAULT_RC, max_len=32)
        cache = lm.init_cache(CFG, 1, 32, DEFAULT_RC)
        tok = jnp.zeros((1,), jnp.int32)
        shapes = {"x".join(map(str, s)) for s in _cast_leaf_shapes(PARAMS)}

        def f32_weight_args(p):
            text = srv._decode.lower(p, tok, cache).as_text()
            sig = next(ln for ln in text.splitlines()
                       if "func.func public @main(" in ln)
            return [s for s in re.findall(r"%arg\d+: tensor<([0-9x]+)xf32>",
                                          sig) if s in shapes]

        assert f32_weight_args(srv._step_params) == []
        assert f32_weight_args(PARAMS)


SERVING_CASES = [
    ServingCase("mesc-poisson-sat", policy="mesc", arrivals="poisson"),
    ServingCase("np-poisson-sat", policy="np", arrivals="poisson"),
    ServingCase("mesc-heavytail-capped", policy="mesc",
                arrivals="heavy_tail", max_live_lo=2),
]


class TestVirtualServing:
    """The deterministic serving harness over the fig12 stack: virtual
    clocks, CRN traffic, admission front door, SLO summary."""

    @pytest.mark.parametrize("case", SERVING_CASES, ids=str)
    def test_serving_case_deterministic(self, case):
        rows = assert_serving_deterministic(case)
        summary = rows[-1]
        assert summary["hi_finished"] == case.n_hi     # nothing dropped
        assert summary["lo_finished"] == case.n_lo

    def test_crn_workload_shared_across_policies(self):
        """Common random numbers: both policies see byte-identical
        arrivals, so any SLO delta is a pure policy effect."""
        a = run_serving_case(SERVING_CASES[0])[:-1]
        b = run_serving_case(SERVING_CASES[1])[:-1]
        assert [(r["rid"], r["crit"], r["submitted_at"]) for r in a] \
            == [(r["rid"], r["crit"], r["submitted_at"]) for r in b]

    def test_mesc_bounds_hi_tail_under_saturation(self):
        """The fig12 headline as a gate: with LO offered load 1.2x
        capacity, MESC preemption keeps the HI p99 and miss rate below
        the non-preemptive baseline on the same workload."""
        mesc = run_serving_case(SERVING_CASES[0])[-1]
        base = run_serving_case(SERVING_CASES[1])[-1]
        assert mesc["hi_p99_latency_s"] < base["hi_p99_latency_s"]
        assert mesc["hi_miss_rate"] <= base["hi_miss_rate"]
        assert mesc["hi_preemptions"] + mesc["lo_preemptions"] > 0
        assert base["hi_preemptions"] + base["lo_preemptions"] == 0

    def test_front_door_lo_cap_holds_at_every_step(self):
        """max_live_lo bounds concurrently-live LO admissions at every
        observable instant; HI requests are never throttled."""
        case = SERVING_CASES[2]
        seen = []

        def watch(front, server):
            live_lo = sum(1 for r in server.requests.values()
                          if not r.done and r.crit == Crit.LO)
            seen.append(live_lo)
            assert live_lo <= case.max_live_lo
            front.check_conservation()

        rows = run_serving_case(case, on_step=watch)
        assert max(seen) == case.max_live_lo      # the cap actually binds
        assert rows[-1]["hi_finished"] == case.n_hi

    def test_lo_budget_mode_switch_at_virtual_time(self):
        """Regression (clock injection): a LO request overrunning its
        lo_budget_s trips the LO->HI mode switch at a *deterministic
        virtual* time — byte-identical across runs, no wall clock."""
        from repro.serving import VirtualClock, VirtualModel

        def run_once():
            clk = VirtualClock()
            model = VirtualModel(clk, seed=3, decode_mean_s=0.010,
                                 jitter=0.0)
            srv = MESCServer(None, None, policy=Policy.mesc(),
                             max_len=64, jit_fns=model.jit_fns,
                             clock=clk)
            lo = Request(rid=0, priority=10,
                         prompt=np.asarray([0], np.int32),
                         max_new_tokens=32, crit=Crit.LO,
                         lo_budget_s=0.035)     # < 4 decode steps
            srv.submit(lo)
            assert srv.mode == Mode.LO
            steps = 0
            while srv.mode == Mode.LO:
                srv.step()
                steps += 1
                assert steps < 64, "mode never switched"
            return steps, clk(), srv.requests[0].exec_s

        a, b = run_once(), run_once()
        assert a == b                          # deterministic switch
        steps, t_switch, exec_s = a
        assert exec_s > 0.035                  # budget actually exceeded
        # jitter=0: step 1 is the prefill (0.020), each later step one
        # decode (0.010); exec_s crosses 0.035 after step 3 (0.040);
        # the monitor trips at the NEXT step's tick, so the loop exits
        # after step 4 with the clock at 0.020 + 3 * 0.010
        assert steps == 4
        assert abs(t_switch - 0.050) < 1e-9
        assert abs(exec_s - 0.050) < 1e-9

    def test_wall_clock_is_the_default(self):
        """Production default unchanged: no clock injected means
        time.monotonic, and submit() stamps arrivals with it."""
        srv = MESCServer(CFG, PARAMS, policy=Policy.mesc(), max_len=32)
        assert srv.clock is time.monotonic
        t0 = time.monotonic()
        r = _req(7, Crit.LO, 5, n=2)
        srv.submit(r)
        assert t0 <= r.submitted_at <= time.monotonic()
        # a pre-stamped arrival time (front-door contract) is respected
        r2 = _req(8, Crit.LO, 6, n=2)
        r2.submitted_at = 123.0
        srv.submit(r2)
        assert r2.submitted_at == 123.0


class TestInt8Adam:
    def test_int8_moments_converge(self):
        cfg = OptConfig(lr=0.1, warmup_steps=5, decay_steps=200,
                        weight_decay=0.0, clip_norm=0, moments_int8=True)
        params = {"w": jnp.array([3.0, -2.0, 1.5])}
        state = init_opt_state(params, cfg)
        assert state["m"]["w"].dtype == jnp.int8
        for _ in range(150):
            g = {"w": 2 * params["w"]}
            params, state, _ = adamw_update(params, g, state, cfg)
        assert float(jnp.abs(params["w"]).max()) < 0.2

    def test_int8_state_is_quarter_size(self):
        params = {"w": jnp.zeros((128, 128))}
        s8 = init_opt_state(params, OptConfig(moments_int8=True))
        s16 = init_opt_state(params, OptConfig())
        b8 = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(s8))
        b16 = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(s16))
        assert b8 < b16 * 0.6
