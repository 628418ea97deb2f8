"""Campaign engine: spec hashing, caching, parallelism, seeding.

Tiny sweeps (short duration, few sets) keep each test fast while still
exercising the full plan -> fan-out -> cache -> collect path.
"""
import dataclasses
import math

import pytest

from repro.core import (Policy, generate_taskset, generate_taskset_batch,
                        point_seed, simulate, simulate_batch)
from repro.core.program import workload_library
from repro.experiments import (Campaign, FuncSweep, Sweep, frac, group_rows,
                               metrics_row, pooled_mean)

TINY = dict(utils=(0.7,), n_sets=3, duration=2e6)


def tiny_sweep(**kw):
    merged = {**TINY, **kw}
    return Sweep(name=merged.pop("name", "tiny"),
                 policies=merged.pop("policies", (Policy.mesc(),)),
                 **merged)


class TestSpecHash:
    def test_stable_across_instances(self):
        assert tiny_sweep().spec_hash() == tiny_sweep().spec_hash()

    def test_sensitive_to_every_axis(self):
        base = tiny_sweep()
        variants = [
            tiny_sweep(utils=(0.8,)),
            tiny_sweep(n_sets=4),
            tiny_sweep(duration=3e6),
            tiny_sweep(seed0=1),
            tiny_sweep(overrun_prob=0.5),
            tiny_sweep(policies=(Policy.non_preemptive(),)),
        ]
        hashes = {s.spec_hash() for s in [base] + variants}
        assert len(hashes) == len(variants) + 1

    def test_point_keys_content_addressed(self):
        """Same point content -> same key, even from different sweeps."""
        a = tiny_sweep(name="a").points()
        b = tiny_sweep(name="b").points()
        assert [p.key() for p in a] == [p.key() for p in b]
        keys = {p.key() for p in a}
        assert len(keys) == len(a)

    def test_duplicate_policy_names_rejected(self):
        with pytest.raises(ValueError):
            tiny_sweep(policies=(Policy.mesc(),
                                 Policy.mesc(use_banks=False)))


class TestCache:
    def test_hit_miss_and_row_identity(self, tmp_path):
        sweep = tiny_sweep()
        c1 = Campaign(sweep, cache_dir=tmp_path, workers=1)
        rows1 = c1.collect()
        assert c1.stats == {"hits": 0, "misses": 3}
        c2 = Campaign(sweep, cache_dir=tmp_path, workers=1)
        rows2 = c2.collect()
        assert c2.stats == {"hits": 3, "misses": 0}
        assert rows1 == rows2

    def test_partial_overlap_is_incremental(self, tmp_path):
        Campaign(tiny_sweep(), cache_dir=tmp_path, workers=1).run()
        grown = tiny_sweep(n_sets=5)        # supersets the first 3 points
        c = Campaign(grown, cache_dir=tmp_path, workers=1)
        c.run()
        assert c.stats == {"hits": 3, "misses": 2}

    def test_manifest_written(self, tmp_path):
        sweep = tiny_sweep()
        c = Campaign(sweep, cache_dir=tmp_path, workers=1)
        c.run()
        m = c.cache.read_manifest(sweep.spec_hash())
        assert m is not None
        assert m["name"] == "tiny"
        assert m["n_points"] == 3
        assert len(m["point_keys"]) == 3

    def test_no_cache_mode_writes_nothing(self, tmp_path):
        c = Campaign(tiny_sweep(), cache_dir=tmp_path, use_cache=False,
                     workers=1)
        c.collect()
        assert not any(tmp_path.iterdir())


class TestParallel:
    def test_parallel_equals_serial(self, tmp_path):
        sweep = tiny_sweep(n_sets=4)
        ser = Campaign(sweep, use_cache=False, workers=1).collect()
        par = Campaign(sweep, use_cache=False, workers=2).collect()
        assert ser == par

    def test_func_sweep_fans_out(self, tmp_path):
        fs = FuncSweep.over("echo", "repro.experiments.runner:_echo_point",
                            [{"i": i} for i in range(4)])
        rows = Campaign(fs, cache_dir=tmp_path, workers=2).collect()
        assert [r["i"] for r in rows] == [0, 1, 2, 3]
        assert all(r["echo"] for r in rows)


class TestSeeding:
    def test_point_seed_contract(self):
        assert point_seed(7, 5) == 12
        sweep = tiny_sweep(seed0=7)
        assert [p.seed for p in sweep.points()] == [7, 8, 9]

    def test_taskset_batch_matches_singles(self):
        lib = {k: v for k, v in workload_library().items()}
        batch = generate_taskset_batch(0.6, 3, seed0=4, programs=lib)
        singles = [generate_taskset(0.6, seed=4 + s, programs=lib)
                   for s in range(3)]
        assert batch == singles

    def test_simulate_batch_matches_singles(self):
        lib = workload_library()
        sets = generate_taskset_batch(0.6, 2, seed0=0, programs=lib)
        batch = simulate_batch(sets, lib, Policy.mesc(), seeds=[0, 1],
                               duration=2e6)
        singles = [simulate(ts, lib, Policy.mesc(), seed=s, duration=2e6)
                   for ts, s in zip(sets, [0, 1])]
        assert batch == singles

    def test_simulate_batch_length_mismatch(self):
        with pytest.raises(ValueError):
            simulate_batch([], {}, Policy.mesc(), seeds=[1])

    def test_engine_matches_legacy_serial_loop(self, tmp_path):
        """The acceptance property: engine rows == benchmarks.common
        run_many (the pre-engine serial reference), policy by policy."""
        from benchmarks.common import run_many
        for policy in (Policy.mesc(), Policy.non_preemptive()):
            sweep = tiny_sweep(policies=(policy,), duration=5e6)
            rows = Campaign(sweep, use_cache=False, workers=2).collect()
            legacy = run_many(policy, n_sets=3, u=0.7, duration=5e6)
            expected = [metrics_row(m, policy=policy.name, u=0.7, gamma=0.5,
                                    n_tasks=10, set_index=s, seed=s)
                        for s, m in enumerate(legacy)]
            assert rows == expected


class TestAggregation:
    def test_pre_mean_cached_rows_upgraded_on_read(self, tmp_path):
        """Rows cached before the {name}_mean columns existed must be
        backfilled on cache read — mixing schemas in one collect()
        would KeyError consumers of the new columns."""
        from repro.experiments.metrics import ensure_row_means
        from repro.experiments.runner import Campaign
        sweep = Sweep(name="t", policies=(Policy.mesc(),), n_sets=2,
                      duration=1e6)
        c1 = Campaign(sweep, cache_dir=tmp_path, workers=1)
        fresh = c1.collect()
        assert all("pi_mean" in r for r in fresh)
        # simulate a pre-upgrade cache: strip the mean columns in situ
        for key in [p.key() for p in sweep.points()]:
            row = c1.cache.get(key)
            for name in ("pi", "ci", "save", "restore"):
                row.pop(f"{name}_mean", None)
            c1.cache.put(key, row)
        replay = Campaign(sweep, cache_dir=tmp_path,
                          workers=1).collect()
        assert replay == fresh
        # non-sim rows (no sum/count keys) pass through untouched
        assert ensure_row_means({"x": 1}) == {"x": 1}
        assert ensure_row_means({"pi_sum": 0.0, "pi_n": 0})[
            "pi_mean"] is None

    def test_pooled_mean_matches_concatenated_lists(self):
        rows = [{"pi_sum": 10.0, "pi_n": 2}, {"pi_sum": 5.0, "pi_n": 3}]
        assert pooled_mean(rows, "pi") == pytest.approx(15.0 / 5)
        # zero events pools to NaN ("no data"), never ZeroDivisionError
        assert math.isnan(pooled_mean([{"pi_sum": 0.0, "pi_n": 0}], "pi"))

    def test_group_and_frac(self):
        rows = [{"u": 0.5, "success_all": 1}, {"u": 0.5, "success_all": 0},
                {"u": 0.9, "success_all": 0}]
        cells = group_rows(rows, "u")
        assert frac(cells[(0.5,)], "success_all") == 0.5
        assert frac(cells[(0.9,)], "success_all") == 0.0


class TestDeviceOwnership:
    """Only the campaign's own process may hold the accelerator."""

    def test_jit_chunks_never_reach_the_pool(self, tmp_path, monkeypatch):
        from repro.experiments import runner

        class NoPool:
            def __init__(self, *a, **kw):
                raise AssertionError("jit chunk sent to a worker pool")
        monkeypatch.setattr(runner, "ProcessPoolExecutor", NoPool)
        sweep = tiny_sweep(n_sets=2, engine="jit", duration=2e6)
        rows = Campaign(sweep, use_cache=False, workers=4).collect()
        assert len(rows) == 2

    def test_killed_jit_campaign_keeps_finished_chunks(self, tmp_path,
                                                       monkeypatch):
        from repro.experiments import runner
        from repro.experiments.cache import ResultCache
        run_chunk = runner._execute_chunk
        calls = []

        class Killed(Exception):
            pass

        def die_after_first(payloads):
            if calls:
                raise Killed
            calls.append(len(payloads))
            return run_chunk(payloads)
        monkeypatch.setattr(runner, "VEC_CHUNK", 1)
        monkeypatch.setattr(runner, "_execute_chunk", die_after_first)
        sweep = tiny_sweep(n_sets=2, engine="jit", duration=2e6)
        with pytest.raises(Killed):
            Campaign(sweep, cache_dir=tmp_path).run()
        cache = ResultCache(tmp_path)
        first, second = sweep.points()
        assert calls == [1]
        assert cache.get(first.key()) is not None
        assert cache.get(second.key()) is None

    @pytest.mark.parametrize("profile,scenario,salted", [
        ("sampled", None, True), ("nominal", "heavy_tail", True),
        ("nominal", None, False)])
    def test_off_cpu_jit_keys_name_the_platform(self, monkeypatch, profile,
                                                scenario, salted):
        """A TPU's f64 is not IEEE: jit rows with draws or scenario
        arithmetic must not share a key with the CPU's."""
        from repro.runtime import device_config
        pts = [tiny_sweep(n_sets=1, engine=e, demand_profile=profile,
                          scenario=scenario).points()[0]
               for e in ("jit", "vec", "event")]
        cpu = [p.key() for p in pts]
        monkeypatch.setattr(device_config, "jit_platform",
                            lambda devices=None: "tpu")
        tpu = [p.key() for p in pts]
        assert (tpu[0] != cpu[0]) == salted
        assert tpu[1:] == cpu[1:]

    def test_pool_workers_are_pinned_to_the_cpu(self, monkeypatch):
        # not inherited: the workers pin themselves
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        fs = FuncSweep.over("env", "os:getenv",
                            [{"key": "JAX_PLATFORMS", "default": d}
                             for d in ("unset", None)])
        rows = Campaign(fs, use_cache=False, workers=2).collect()
        assert [r["result"] for r in rows] == ["cpu", "cpu"]
