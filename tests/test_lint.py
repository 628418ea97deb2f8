"""Tests for the repro-lint static-analysis framework (tools/lint/).

Covers, per rule, a golden violating fixture (the rule fires, and only
it) and a clean fixture (zero findings); plus the framework mechanics:
pragma suppression, baseline round-trip with stale-entry detection,
the salt-drift pin/mutate/bump/re-pin workflow on a throwaway tree,
and the CLI's exit-code contract (0 clean / 1 findings / 2 bad
invocation) including ``--format json``.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tools.lint import RULES, run_lint
from tools.lint.core import (Context, pragma_disabled,
                             pragma_justification, write_baseline)
from tools.lint.rules.salt_drift import (normalized_fingerprint,
                                         update_salts)

REPO = Path(__file__).resolve().parents[1]
TESTDATA = REPO / "tools" / "lint" / "testdata"
BAD = TESTDATA / "bad"
GOOD = TESTDATA / "good"
TREES = TESTDATA / "trees"

#: the default (stdlib-only, AST/text) family
EXPECTED_RULES = {
    "doc-link", "env-validation", "except-breadth", "jit-purity",
    "module-docstring", "no-host-rng", "no-wall-clock", "salt-drift",
    "xp-generic",
}

#: the non-default jax-costing family (tools/graphlint); registered in
#: the same registry, excluded from no---rules runs
IR_RULES = {
    "ir-budget-drift", "ir-donation", "ir-dtype-discipline",
    "ir-graph-purity", "ir-retrace-surface",
}


def lint(paths, root=REPO, rules=None, baseline=None):
    report, _ = run_lint(root, [str(p) for p in paths],
                         rule_names=rules, baseline_path=baseline,
                         use_baseline=baseline is not None)
    return report


def cli(*args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.lint", *args],
                          cwd=cwd, capture_output=True, text=True)


class TestRegistry:
    def test_all_rules_registered(self):
        assert set(RULES) == EXPECTED_RULES | IR_RULES

    def test_every_rule_states_its_contract(self):
        for rule in RULES.values():
            assert len(rule.contract) > 20, rule.name

    def test_default_family_is_exactly_the_ast_rules(self):
        assert {n for n, r in RULES.items() if r.default} == \
            EXPECTED_RULES


class TestViolatingFixtures:
    """Each bad fixture fires exactly its rule, nothing else."""

    CASES = [
        ("except_breadth_bad.py", "except-breadth", 3),
        ("host_rng_bad.py", "no-host-rng", 3),
        ("jit_purity_bad.py", "jit-purity", 4),
        ("xp_generic_bad.py", "xp-generic", 2),
        ("env_validation_bad.py", "env-validation", 4),
        ("doc_link_bad.md", "doc-link", 2),
    ]

    @pytest.mark.parametrize("fname,rule,count", CASES)
    def test_fixture_fires_its_rule(self, fname, rule, count):
        report = lint([BAD / fname])
        assert {f.rule for f in report.findings} == {rule}
        assert len(report.findings) == count
        assert report.exit_code == 1

    def test_jit_purity_names_every_host_construct(self):
        msgs = "\n".join(f.message for f in
                         lint([BAD / "jit_purity_bad.py"]).findings)
        for expect in ("python if", "float()", ".item()",
                       "numpy.maximum"):
            assert expect in msgs

    def test_env_validation_checks_real_registry(self):
        msgs = [f.message for f in
                lint([BAD / "env_validation_bad.py"]).findings]
        enum = [m for m in msgs if "ENGINES" in m]
        assert len(enum) == 1 and "'evnet'" in enum[0]


class TestCleanFixtures:
    @pytest.mark.parametrize("fname", sorted(
        p.name for p in GOOD.iterdir() if p.name != "pragma_good.py"))
    def test_clean_fixture_has_no_findings(self, fname):
        report = lint([GOOD / fname])
        assert report.findings == []
        assert report.exit_code == 0

    def test_pragmas_suppress_but_are_counted(self):
        report = lint([GOOD / "pragma_good.py"])
        assert report.findings == []
        assert {f.rule for f in report.suppressed} == \
            {"no-host-rng", "except-breadth"}

    def test_suppressed_findings_carry_justifications(self):
        report = lint([GOOD / "pragma_good.py"])
        assert report.suppressed_justifications == \
            ["fixture"] * len(report.suppressed)

    def test_suppressed_findings_in_json_output(self):
        p = cli("--no-baseline", "--format", "json",
                str(GOOD / "pragma_good.py"))
        assert p.returncode == 0
        data = json.loads(p.stdout)
        assert data["findings"] == []
        assert data["suppressed"] == 2
        rows = data["suppressed_findings"]
        assert {r["rule"] for r in rows} == \
            {"no-host-rng", "except-breadth"}
        for r in rows:
            assert r["justification"] == "fixture"
            assert r["path"].endswith("pragma_good.py") and r["line"]


class TestZoneTrees:
    """Zone-scoped rules keyed off --root-relative paths."""

    CASES = [
        ("crn_zone_bad", "no-host-rng"),
        ("wall_clock_bad", "no-wall-clock"),
        ("docstring_bad", "module-docstring"),
        ("salt_bad", "salt-drift"),
    ]

    @pytest.mark.parametrize("tree,rule", CASES)
    def test_tree_fires_its_zone_rule(self, tree, rule):
        report = lint(["src"], root=TREES / tree)
        assert {f.rule for f in report.findings} == {rule}

    def test_clean_tree(self):
        report = lint(["src"], root=TREES / "wall_clock_good")
        assert report.findings == []

    def test_zone_rules_inert_outside_their_zone(self):
        # the same wall-clock-calling file, linted as a path under the
        # real repo root (tools/...), is outside the pure zones
        report = lint([TREES / "wall_clock_bad/src/repro/core/stamp.py"])
        assert report.findings == []


class TestPragmaParsing:
    def test_single_and_multi_rule(self):
        assert pragma_disabled("x  # repro-lint: disable=a") == {"a"}
        assert pragma_disabled("x  # repro-lint: disable=a, b") == \
            {"a", "b"}

    def test_trailing_justification_in_parens(self):
        line = "x  # repro-lint: disable=no-host-rng (why: boundary)"
        assert pragma_disabled(line) == {"no-host-rng"}

    def test_all_sentinel_and_absence(self):
        assert "all" in pragma_disabled("# repro-lint: disable=all")
        assert pragma_disabled("plain line # comment") == frozenset()

    def test_justification_extracted_from_parens(self):
        line = "x  # repro-lint: disable=no-host-rng (why: boundary)"
        assert pragma_justification(line) == "why: boundary"

    def test_justification_empty_when_absent(self):
        assert pragma_justification(
            "x  # repro-lint: disable=no-host-rng") == ""
        assert pragma_justification("plain line") == ""


class TestBaseline:
    def test_roundtrip_then_new_finding_then_stale(self, tmp_path):
        target = tmp_path / "legacy.py"
        target.write_text(textwrap.dedent("""\
            def f():
                try:
                    return 1
                except Exception:
                    return None
        """))
        bpath = tmp_path / "baseline.json"

        fresh = lint([target])
        assert len(fresh.findings) == 1

        ctx = Context(REPO, [])
        assert write_baseline(bpath, fresh.findings, ctx) == 1

        grandfathered = lint([target], baseline=bpath)
        assert grandfathered.findings == []
        assert len(grandfathered.baselined) == 1
        assert grandfathered.stale_baseline == []

        # a NEW broad handler is not covered by the old baseline
        target.write_text(target.read_text() + textwrap.dedent("""\

            def g():
                try:
                    return 2
                except BaseException:
                    return None
        """))
        drifted = lint([target], baseline=bpath)
        assert len(drifted.findings) == 1
        assert "BaseException" in drifted.findings[0].message
        assert len(drifted.baselined) == 1

        # fixing the original finding leaves a stale entry behind
        target.write_text("def f():\n    return 1\n")
        healed = lint([target], baseline=bpath)
        assert healed.findings == []
        assert len(healed.stale_baseline) == 1


def make_salt_tree(tmp_path):
    """A throwaway repo root with one salted engine module."""
    eng = tmp_path / "src" / "repro" / "core" / "engine.py"
    eng.parent.mkdir(parents=True)
    eng.write_text(textwrap.dedent('''\
        """Tiny salted engine for salt-drift workflow tests."""

        ENGINE_SEMANTICS_VERSION = 1


        def step(state):
            return state + 1
    '''))
    salts = tmp_path / "tools" / "lint" / "salts.json"
    salts.parent.mkdir(parents=True)
    salts.write_text(json.dumps({
        "version": 1,
        "salts": {"ENGINE_SEMANTICS_VERSION": {
            "defined_in": "src/repro/core/engine.py",
            "surface": ["src/repro/core/engine.py"],
            "surface_hash": "bootstrap", "value": 0}}}))
    update_salts(tmp_path)
    return eng


class TestSaltDrift:
    def test_pinned_tree_is_clean(self, tmp_path):
        make_salt_tree(tmp_path)
        assert lint(["src"], root=tmp_path).findings == []

    def test_comment_and_docstring_edits_stay_clean(self, tmp_path):
        eng = make_salt_tree(tmp_path)
        text = eng.read_text().replace(
            "Tiny salted engine", "Rewritten docstring, same tokens")
        eng.write_text(text + "\n# trailing comment\n\n")
        assert lint(["src"], root=tmp_path).findings == []

    def test_semantic_edit_without_bump_fires(self, tmp_path):
        eng = make_salt_tree(tmp_path)
        eng.write_text(eng.read_text().replace("state + 1", "state + 2"))
        found = lint(["src"], root=tmp_path).findings
        assert [f.rule for f in found] == ["salt-drift"]
        assert "without a salt bump" in found[0].message

    def test_bump_without_repin_names_the_regen_step(self, tmp_path):
        eng = make_salt_tree(tmp_path)
        eng.write_text(eng.read_text().replace(
            "ENGINE_SEMANTICS_VERSION = 1",
            "ENGINE_SEMANTICS_VERSION = 2"))
        found = lint(["src"], root=tmp_path).findings
        assert [f.rule for f in found] == ["salt-drift"]
        assert "engine_point_hashes.json" in found[0].message

    def test_update_salts_repins_to_clean(self, tmp_path):
        eng = make_salt_tree(tmp_path)
        eng.write_text(eng.read_text().replace("state + 1", "state + 3"))
        assert update_salts(tmp_path) == ["ENGINE_SEMANTICS_VERSION"]
        assert lint(["src"], root=tmp_path).findings == []

    def test_normalized_fingerprint_ignores_formatting_only(self):
        base = normalized_fingerprint("x = 1\ny = x + 2\n")
        same = normalized_fingerprint(
            '"""doc"""\n# comment\nx = 1\n\ny = x + 2\n')
        assert base != normalized_fingerprint("x = 1\ny = x + 3\n")
        # docstring/comment/blank-line edits hash identically apart
        # from the docstring-free vs docstring'd module header
        assert same == normalized_fingerprint(
            '"""other doc"""\nx = 1\ny = x + 2   # note\n')

    @pytest.mark.parametrize("literal", [
        'f"a{b!r:>{w}}"',
        "f'''x{f\"{y}\"}\n  z{{w}}'''",
    ])
    def test_fstring_fingerprint_is_one_literal(self, literal):
        # the pre-PEP-701 token stream: the f-string is a single STRING
        # token, so pins taken on any CPython version agree
        import hashlib
        h = hashlib.sha256()
        for name, s in (("NAME", "v"), ("OP", "="), ("STRING", literal),
                        ("NEWLINE", "\n")):
            h.update(f"{name}\x1f{s}\x1e".encode())
        assert normalized_fingerprint(f"v = {literal}\n") == h.hexdigest()


def make_git_tree(tmp_path):
    """A committed throwaway git repo with one clean lintable file."""
    def git(*args):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             *args], cwd=tmp_path, check=True, capture_output=True)
    (tmp_path / "mod.py").write_text('"""Clean module."""\n')
    git("init", "-q")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")
    return tmp_path


class TestChangedMode:
    """``--changed``: lint only files touched since HEAD."""

    def test_clean_worktree_lints_nothing(self, tmp_path):
        make_git_tree(tmp_path)
        p = cli("--changed", "--no-baseline", "--root", str(tmp_path))
        assert p.returncode == 0, p.stdout + p.stderr
        assert "no changed lintable files" in p.stdout

    def test_modified_file_is_linted(self, tmp_path):
        root = make_git_tree(tmp_path)
        (root / "mod.py").write_text(textwrap.dedent('''\
            """Module with a broad handler."""
            try:
                pass
            except Exception:
                pass
        '''))
        p = cli("--changed", "--no-baseline", "--root", str(root))
        assert p.returncode == 1
        assert "except-breadth" in p.stdout

    def test_untracked_file_is_linted(self, tmp_path):
        root = make_git_tree(tmp_path)
        (root / "new.py").write_text(textwrap.dedent('''\
            """Untracked module with a broad handler."""
            try:
                pass
            except BaseException:
                pass
        '''))
        p = cli("--changed", "--no-baseline", "--root", str(root))
        assert p.returncode == 1
        assert "new.py" in p.stdout

    def test_changed_with_explicit_paths_is_an_error(self, tmp_path):
        root = make_git_tree(tmp_path)
        p = cli("--changed", "src", "--root", str(root))
        assert p.returncode == 2
        assert "--changed" in p.stderr

    def test_outside_a_git_repo_is_invocation_error(self, tmp_path):
        p = cli("--changed", "--root", str(tmp_path))
        assert p.returncode == 2


class TestCheckDocsShim:
    def test_main_warns_deprecation_and_delegates(self):
        import tools.check_docs as cd
        with pytest.warns(DeprecationWarning, match="tools.lint"):
            rc = cd.main([])
        assert rc == 0

    def test_warning_is_fatal_under_w_error(self):
        p = subprocess.run(
            [sys.executable, "-W", "error::DeprecationWarning",
             "tools/check_docs.py"],
            cwd=REPO, capture_output=True, text=True)
        assert p.returncode != 0
        assert "DeprecationWarning" in p.stderr


class TestCli:
    def test_merged_tree_is_clean(self):
        p = cli("src", "tools", "benchmarks")
        assert p.returncode == 0, p.stdout + p.stderr

    def test_violating_fixture_exits_nonzero(self):
        p = cli("--no-baseline",
                str(BAD / "except_breadth_bad.py"))
        assert p.returncode == 1
        assert "except-breadth" in p.stdout

    def test_unknown_rule_is_invocation_error(self):
        p = cli("--rules", "no-such-rule", "tools/lint/core.py")
        assert p.returncode == 2
        assert "unknown rule" in p.stderr

    def test_json_format(self):
        p = cli("--no-baseline", "--format", "json",
                str(BAD / "host_rng_bad.py"))
        data = json.loads(p.stdout)
        assert data["exit_code"] == p.returncode == 1
        assert {f["rule"] for f in data["findings"]} == {"no-host-rng"}

    def test_salt_tree_via_root_flag(self):
        p = cli("--root", str(TREES / "salt_bad"), "--no-baseline",
                "src")
        assert p.returncode == 1
        assert "salt-drift" in p.stdout

    def test_check_docs_shim_still_passes(self):
        p = subprocess.run([sys.executable, "tools/check_docs.py"],
                           cwd=REPO, capture_output=True, text=True)
        assert p.returncode == 0, p.stdout + p.stderr
