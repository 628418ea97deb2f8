"""Tests for graph-lint (tools/graphlint/): the jaxpr walker units,
the budget-manifest pin/tamper/repin workflow on throwaway trees (one
shared set of tiny-corpus compiles behind ``live_report``'s memo), the
committed manifest's own contracts (19 dtype-homogeneous carry
tensors, neutral-scenario equality, donation), the ``kernel_budget``
bridge perf_sim logs through, and the CLI's exit-code contract.
"""
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from tools.graphlint import (CANONICAL_CASE, IR_RULES, NEUTRAL_CASE,
                             budgets, kernel_budget, update_budgets)
from tools.graphlint import trace
from tools.lint.core import RULES, run_lint
import tools.lint.rules  # noqa: F401  (registers the rule families)

REPO = Path(__file__).resolve().parents[1]

#: a deliberately tiny corpus so the workflow tests compile toy graphs
#: (seconds, shared through the live_report memo), while exercising
#: the exact same trace/compare/repin path as the canonical manifest
TINY_SPEC = {"utils": [0.7], "n_seeds": 2, "n_tasks": 4,
             "duration": 2.0e5, "overrun_prob": 0.3, "cf": 2.0,
             "table_width": 16, "chunk": 64}

TINY_CASES = {
    CANONICAL_CASE: {
        "config": {"policy": "mesc", "demand_profile": "sampled",
                   "scenario": None, "devices": 1}},
    NEUTRAL_CASE: {
        "config": {"policy": "mesc", "demand_profile": "sampled",
                   "scenario": "faults@0", "devices": 1},
        "equals": CANONICAL_CASE},
}


def make_tree(tmp_path, cases=TINY_CASES):
    """A throwaway repo root with a freshly pinned tiny manifest."""
    path = tmp_path / "tools" / "graphlint" / "budgets.json"
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps({
        "version": budgets.BUDGETS_VERSION, "spec": dict(TINY_SPEC),
        "cases": json.loads(json.dumps(cases))}))
    update_budgets(tmp_path)
    return path


def tamper(path: Path, fn):
    data = json.loads(path.read_text())
    fn(data)
    path.write_text(json.dumps(data))


def ir_lint(root, rules=IR_RULES):
    report, _ = run_lint(root, ["tools/graphlint/budgets.json"],
                         rule_names=list(rules), use_baseline=False)
    return report


def rules_fired(report):
    return {f.rule for f in report.findings}


class TestRegistry:
    def test_ir_rules_registered_and_nondefault(self):
        for name in IR_RULES:
            assert name in RULES
            assert RULES[name].default is False
            assert len(RULES[name].contract) > 20

    def test_default_lint_run_excludes_ir_family(self, tmp_path):
        f = tmp_path / "clean.py"
        f.write_text('"""doc."""\n')
        report, _ = run_lint(REPO, [str(f)], use_baseline=False)
        assert not set(report.rules_run) & set(IR_RULES)


class TestJaxprWalker:
    """Toy traced functions — no engine, milliseconds."""

    def _closed(self, fn, *args):
        import jax
        return jax.make_jaxpr(jax.jit(fn))(*args)

    def test_find_while_through_pjit_wrapper(self):
        import jax.numpy as jnp
        from jax import lax

        def f(c):
            return lax.while_loop(lambda c: c[0] < 10,
                                  lambda c: (c[0] + 1, c[1] * 2.0), c)
        closed = self._closed(f, (jnp.int32(0), jnp.float32(1.0)))
        assert trace.find_while(closed.jaxpr).primitive.name == "while"

    def test_histogram_recurses_and_skips_wrappers(self):
        import jax.numpy as jnp
        from jax import lax

        def f(c):
            return lax.while_loop(lambda c: c[0] < 10,
                                  lambda c: (c[0] + 1, c[1] * 2.0), c)
        hist = trace.primitive_histogram(
            self._closed(f, (jnp.int32(0), jnp.float32(1.0))).jaxpr)
        assert hist.get("while") == 1
        assert "jit" not in hist
        assert hist.get("mul", 0) >= 1      # inside the body sub-jaxpr

    def test_find_while_raises_on_whileless_graph(self):
        import jax.numpy as jnp
        closed = self._closed(lambda x: x * 2, jnp.float32(3.0))
        with pytest.raises(ValueError, match="no while eqn"):
            trace.find_while(closed.jaxpr)

    def test_banned_detects_traced_rng(self):
        import jax

        def f(key):
            return jax.random.uniform(key)
        banned = trace.banned_primitives(
            self._closed(f, jax.random.PRNGKey(0)).jaxpr)
        assert banned, "threefry/random_* primitives not flagged"
        assert all(p.startswith(("threefry", "random_"))
                   for p in banned)

    def test_banned_clean_on_pure_arithmetic(self):
        import jax.numpy as jnp
        closed = self._closed(lambda x: jnp.sin(x) + 1, jnp.float32(0.))
        assert trace.banned_primitives(closed.jaxpr) == {}

    def test_dtype_summary_counts_float32_ops(self):
        import jax.numpy as jnp
        closed = self._closed(lambda x: x + 1, jnp.float32(0.0))
        assert trace.dtype_summary(closed.jaxpr)["float32_ops"] >= 1

    def test_dtype_summary_counts_f64_demotions(self):
        import jax
        import jax.numpy as jnp
        with jax.enable_x64(True):
            closed = self._closed(lambda x: x.astype(jnp.float32),
                                  jnp.float64(0.0))
            summary = trace.dtype_summary(closed.jaxpr)
        assert summary["f64_to_f32_demotions"] == 1

    def test_donation_summary_parses_alias_header(self):
        hlo = ("HloModule jit__run, input_output_alias={ {0}: "
               "(2, {}, may-alias), {1}: (3, {}, may-alias) }\n"
               "ENTRY main { ... }\n")
        assert trace.donation_summary(hlo, []) == \
            {"donated": 2, "dropped": 0}

    def test_donation_summary_counts_dropped_warnings(self):
        w = types.SimpleNamespace(
            message="Some donated buffers were not usable")
        assert trace.donation_summary("HloModule x\n", [w]) == \
            {"donated": 0, "dropped": 1}

    def test_retrace_surface_is_o1_in_corpus_size(self):
        surface = trace.retrace_surface(TINY_SPEC)
        for corpus, row in surface.items():
            assert row["signatures"] == 1, (corpus, row)


class TestBudgetDiff:
    def test_flatten_dotted_paths(self):
        flat = budgets.flatten("", {"carry": {"dtypes":
                                              {"ev_time": "float64"}},
                               "k": 1})
        assert flat == {"carry.dtypes.ev_time": "float64", "k": 1}

    def test_diff_reports_changed_and_missing_leaves(self):
        rows = budgets.diff_budget({"a": 1, "b": {"c": 2}},
                                   {"a": 1, "b": {"c": 3, "d": 4}})
        assert ("b.c", 2, 3) in rows
        assert ("b.d", None, 4) in rows
        assert not any(p == "a" for p, _, _ in rows)

    def test_diff_respects_field_slice_and_unpinned(self):
        pinned = {"while_body_kernels": 5}
        live = {"while_body_kernels": 6, "banned_primitives": {"x": 1},
                "donation": {"donated": 0}}
        rows = budgets.diff_budget(pinned, live,
                                   ("while_body_kernels",))
        assert [p for p, _, _ in rows] == ["while_body_kernels"]
        # banned_primitives is live-only diagnostics, never drift
        assert not any("banned" in p for p, _, _
                       in budgets.diff_budget(pinned, live))


class TestBudgetManifest:
    """Pin / tamper / repin on throwaway trees (tiny corpus)."""

    def test_update_budgets_pins_clean_tree(self, tmp_path):
        make_tree(tmp_path)
        report = ir_lint(tmp_path)
        assert report.findings == [], [f.message
                                       for f in report.findings]

    def test_kernel_count_tamper_names_engine_and_field(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               .__setitem__("while_body_kernels", 1))
        report = ir_lint(tmp_path)
        assert "ir-budget-drift" in rules_fired(report)
        msg = "\n".join(f.message for f in report.findings)
        assert CANONICAL_CASE in msg and "while_body_kernels" in msg
        assert "--update-budgets" in msg

    def test_histogram_tamper_fires_budget_drift(self, tmp_path):
        path = make_tree(tmp_path)

        def bump(d):
            h = d["cases"][CANONICAL_CASE]["budget"][
                "primitive_histogram"]
            h["add"] = h.get("add", 0) + 7
        tamper(path, bump)
        report = ir_lint(tmp_path, rules=("ir-budget-drift",))
        assert rules_fired(report) == {"ir-budget-drift"}
        assert any("primitive_histogram.add" in f.message
                   for f in report.findings)

    def test_total_bytes_is_budget_not_dtype(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               ["carry"].__setitem__("total_bytes", 1))
        report = ir_lint(tmp_path)
        assert rules_fired(report) == {"ir-budget-drift"}

    def test_carry_dtype_tamper_fires_dtype_rule(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               ["carry"]["dtypes"].__setitem__("ev_time", "float32"))
        report = ir_lint(tmp_path, rules=("ir-dtype-discipline",))
        assert rules_fired(report) == {"ir-dtype-discipline"}
        assert any("carry.dtypes.ev_time" in f.message
                   for f in report.findings)

    def test_carry_tensor_count_tamper_fires_dtype_rule(self, tmp_path):
        path = make_tree(tmp_path)
        carry = json.loads(path.read_text())["cases"][CANONICAL_CASE][
            "budget"]["carry"]
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               ["carry"].__setitem__("tensors", carry["tensors"] + 1))
        report = ir_lint(tmp_path, rules=("ir-dtype-discipline",))
        assert any("carry.tensors" in f.message
                   for f in report.findings)

    def test_donation_tamper_fires_donation_rule(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               ["donation"].__setitem__("donated", 0))
        report = ir_lint(tmp_path, rules=("ir-donation",))
        assert rules_fired(report) == {"ir-donation"}
        assert any("donation.donated" in f.message
                   for f in report.findings)

    def test_equals_divergence_fires_neutrality_finding(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][NEUTRAL_CASE]["budget"]
               .__setitem__("while_body_kernels", 999))
        report = ir_lint(tmp_path, rules=("ir-budget-drift",))
        msgs = [f.message for f in report.findings]
        assert any("graph-equal" in m and NEUTRAL_CASE in m
                   for m in msgs), msgs

    def test_retrace_pin_tamper_fires_retrace_rule(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["retrace"]["fig8-d1"]
               .__setitem__("signatures", 64))
        report = ir_lint(tmp_path, rules=("ir-retrace-surface",))
        assert rules_fired(report) == {"ir-retrace-surface"}

    def test_per_point_retrace_is_flagged(self, tmp_path, monkeypatch):
        path = make_tree(tmp_path, cases={})
        per_point = {"toy-d1": {"n_points": 8, "signatures": 8}}
        tamper(path, lambda d: d.__setitem__("retrace", per_point))
        monkeypatch.setattr(
            budgets, "live_report",
            lambda manifest, only=None: {"cases": {},
                                         "retrace": per_point})
        report = ir_lint(tmp_path, rules=("ir-retrace-surface",))
        assert any("retraces per point" in f.message
                   for f in report.findings)

    def test_update_budgets_repins_to_clean(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               .__setitem__("while_body_kernels", 1))
        changed = update_budgets(tmp_path)
        assert f"{CANONICAL_CASE}.while_body_kernels" in changed
        assert ir_lint(tmp_path).findings == []

    def test_unmeasurable_serving_probe_is_skipped(self, tmp_path):
        # in-process the engine compiles above already initialized a
        # backend, so the serving probe reports None -> no findings
        cases = dict(TINY_CASES)
        cases["serving-virtual"] = {"config": {"engine": "serving"},
                                    "budget": {"xla_compilations": 2}}
        make_tree(tmp_path, cases=cases)
        assert ir_lint(tmp_path).findings == []


class TestKernelBudget:
    """The manifest numbers perf_sim logs (BENCH_sim.json schema)."""

    def test_roundtrip_matches_pins(self, tmp_path):
        path = make_tree(tmp_path)
        data = json.loads(path.read_text())
        out = kernel_budget(tmp_path)
        assert set(out) == {"xla_kernels",
                            "xla_kernels_neutral_scenario"}
        assert out["xla_kernels"] == \
            data["cases"][CANONICAL_CASE]["budget"]["while_body_kernels"]
        assert out["xla_kernels"] == \
            out["xla_kernels_neutral_scenario"]

    def test_drift_exits_naming_the_repin_step(self, tmp_path):
        path = make_tree(tmp_path)
        tamper(path, lambda d: d["cases"][CANONICAL_CASE]["budget"]
               .__setitem__("while_body_kernels", 1))
        with pytest.raises(SystemExit, match="--update-budgets"):
            kernel_budget(tmp_path)

    def test_missing_manifest_exits_with_recipe(self, tmp_path):
        with pytest.raises(SystemExit, match="--update-budgets"):
            kernel_budget(tmp_path)


class TestCommittedManifest:
    """The real tools/graphlint/budgets.json: the acceptance-surface
    contracts, checked without tracing (pure JSON reads)."""

    @pytest.fixture(scope="class")
    def manifest(self):
        data = budgets.load_budgets(REPO)
        assert data is not None, "committed budgets.json missing"
        return data

    def test_canonical_cases_present(self, manifest):
        for name in (CANONICAL_CASE, NEUTRAL_CASE,
                     "jit-mesc-sampled-d2", "jit-np-sampled",
                     "serving-virtual"):
            assert name in manifest["cases"], name

    def test_neutral_scenario_pins_identical_budget(self, manifest):
        assert manifest["cases"][NEUTRAL_CASE]["equals"] == \
            CANONICAL_CASE
        assert manifest["cases"][NEUTRAL_CASE]["budget"] == \
            manifest["cases"][CANONICAL_CASE]["budget"]

    def test_carry_contract_19_homogeneous_tensors(self, manifest):
        # PR 5's 16 grouped tensors + the PR 8 scenario tensors
        # (sn/sw/sm) + the step counter; each a single dtype
        from repro.core.simulator_jit import _CARRY_KEYS
        for name, case in manifest["cases"].items():
            carry = case["budget"].get("carry")
            if carry is None:        # serving case
                continue
            assert carry["tensors"] == len(_CARRY_KEYS) == 19, name
            assert set(carry["dtypes"]) == set(_CARRY_KEYS), name
            for tensor, dtype in carry["dtypes"].items():
                assert dtype in ("float64", "int32", "int64",
                                 "uint64"), (name, tensor, dtype)

    def test_every_jit_case_donates_its_whole_carry(self, manifest):
        for name, case in manifest["cases"].items():
            donation = case["budget"].get("donation")
            if donation is None:
                continue
            assert donation == {"donated": 19, "dropped": 0}, name

    def test_dtype_counters_pinned_at_zero(self, manifest):
        for name, case in manifest["cases"].items():
            b = case["budget"]
            if "float32_ops" not in b:
                continue
            assert b["float32_ops"] == 0, name
            assert b["f64_to_f32_demotions"] == 0, name

    def test_retrace_surface_pinned_o1(self, manifest):
        for corpus, row in manifest["retrace"].items():
            assert row["signatures"] < row["n_points"] \
                or row["n_points"] <= 1, (corpus, row)


class TestCli:
    """Exit-code contract via subprocess (fresh jax-free processes)."""

    def gl(self, *args, cwd=REPO):
        # hermetic env: earlier tests (device_config) leave platform
        # overrides in os.environ that must not steer the subprocess
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORM_NAME")
               and not k.startswith("REPRO_")}
        return subprocess.run(
            [sys.executable, "-m", "tools.graphlint", *args],
            cwd=cwd, capture_output=True, text=True, env=env)

    def test_list_rules(self):
        p = self.gl("--list-rules")
        assert p.returncode == 0
        for name in IR_RULES:
            assert name in p.stdout

    def test_missing_manifest_is_invocation_error(self, tmp_path):
        p = self.gl("--root", str(tmp_path))
        assert p.returncode == 2
        assert "no manifest" in p.stderr

    def test_unknown_case_is_invocation_error(self):
        p = self.gl("--cases", "no-such-case")
        assert p.returncode == 2
        assert "unknown budget case" in p.stderr

    def test_unknown_rule_is_invocation_error(self):
        p = self.gl("--rules", "ir-nope")
        assert p.returncode == 2
        assert "unknown ir rule" in p.stderr

    def test_serving_probe_authoritative_in_fresh_process(self):
        # a fresh process measures the serving compilation ceiling for
        # real (no engine compile pollutes the eager-kernel cache) —
        # and json format round-trips the report
        p = self.gl("--cases", "serving-virtual", "--format", "json")
        assert p.returncode == 0, p.stdout + p.stderr
        data = json.loads(p.stdout)
        assert data["exit_code"] == 0 and data["findings"] == []
