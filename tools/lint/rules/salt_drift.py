"""salt-drift: every ``*_SEMANTICS_VERSION`` cache salt is pinned
against a normalized content hash of its engine's semantic surface.

Contract (PRs 3-8): campaign cache keys embed a per-engine semantics
version, so editing an engine without bumping its salt silently serves
stale cached results — byte-compatible, wrong, and invisible until a
figure disagrees with a fresh run.  Keeping salts honest was manual
chore-work in PRs 5, 6 and 8 (every jit carry change meant "bump
``JIT_SIM_SEMANTICS_VERSION``, regenerate
``tests/data/engine_point_hashes.json``"); this rule mechanizes it.

``tools/lint/salts.json`` pins, per salt:

  * ``defined_in`` + ``value`` — the constant and where it lives;
  * ``surface`` — the files whose semantics the salt covers (engine
    modules plus the shared scenario/CRN code compiled into them);
  * ``surface_hash`` — sha256 over the *normalized* token streams of
    the surface files.

Normalization (:func:`normalized_fingerprint`) strips comments, blank
lines and docstrings via ``tokenize`` + AST docstring positions, so
formatting/comment/doc edits never fire the rule, while any token the
interpreter sees does.  Workflow on a genuine semantic edit: bump the
salt(s) whose engines changed, regenerate
``tests/data/engine_point_hashes.json`` if spec hashes moved, then
``python -m tools.lint --update-salts`` to re-pin; for a provably
semantics-neutral refactor, ``--update-salts`` alone re-pins without
a bump (a conscious, diff-visible decision — which is the point).
"""
from __future__ import annotations

import ast
import hashlib
import io
import json
import tokenize
from pathlib import Path
from typing import Dict, Iterable, List, Optional

from tools.lint.core import (Context, Finding, LintConfigError, Rule,
                             register)

SALTS_REL = Path("tools/lint/salts.json")
SALTS_VERSION = 1

_SKIP_TOKENS = {tokenize.COMMENT, tokenize.NL, tokenize.ENCODING,
                tokenize.ENDMARKER}


def _docstring_positions(tree: ast.Module):
    """(lineno, col) of every docstring constant, to drop from the
    token stream (docstrings are semantics-neutral)."""
    out = set()
    nodes = [tree] + [n for n in ast.walk(tree)
                      if isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef,
                                        ast.ClassDef))]
    for n in nodes:
        body = getattr(n, "body", [])
        if body and isinstance(body[0], ast.Expr) and \
                isinstance(body[0].value, ast.Constant) and \
                isinstance(body[0].value.value, str):
            c = body[0].value
            out.add((c.lineno, c.col_offset))
    return out


# PEP 701 (Python 3.12) tokenizes an f-string as FSTRING_START ...
# FSTRING_END with its replacement fields as ordinary tokens; older
# tokenizers emit the whole literal as one STRING.  Absent on < 3.12.
_FSTRING_START = getattr(tokenize, "FSTRING_START", None)
_FSTRING_END = getattr(tokenize, "FSTRING_END", None)


def _source_tokens(text: str):
    """``(type, start, string)`` of every token of ``text``, with each
    f-string folded back into one ``STRING`` token that carries the
    literal's source text, so the stream is the same on every CPython
    version."""
    lines = text.splitlines(keepends=True)

    def span(start, end) -> str:
        (r0, c0), (r1, c1) = start, end
        if r0 == r1:
            return lines[r0 - 1][c0:c1]
        return (lines[r0 - 1][c0:] + "".join(lines[r0:r1 - 1])
                + lines[r1 - 1][:c1])

    depth, fstart = 0, None
    for tok in tokenize.generate_tokens(io.StringIO(text).readline):
        if _FSTRING_START is not None and tok.type == _FSTRING_START:
            if depth == 0:
                fstart = tok.start
            depth += 1
            continue
        if depth:
            if tok.type == _FSTRING_END:
                depth -= 1
                if depth == 0:
                    yield tokenize.STRING, fstart, span(fstart, tok.end)
            continue
        yield tok.type, tok.start, tok.string


def normalized_fingerprint(text: str) -> str:
    """sha256 over the comment-/docstring-/formatting-insensitive
    token stream of one Python source text.

    Token *names* (not version-dependent numeric codes) key the
    stream, and f-strings hash as one literal (:func:`_source_tokens`),
    so the hash is stable across CPython minor versions.
    """
    doc_pos = _docstring_positions(ast.parse(text))
    h = hashlib.sha256()
    for typ, start, string in _source_tokens(text):
        if typ in _SKIP_TOKENS:
            continue
        if typ == tokenize.STRING and start in doc_pos:
            continue
        h.update(tokenize.tok_name[typ].encode())
        h.update(b"\x1f")
        h.update(string.encode())
        h.update(b"\x1e")
    return h.hexdigest()


def surface_hash(root: Path, files: Iterable[str]) -> str:
    """Combined normalized hash of a salt's semantic surface."""
    h = hashlib.sha256()
    for rel in sorted(files):
        h.update(rel.encode())
        h.update(b"\x00")
        h.update(normalized_fingerprint(
            (root / rel).read_text(encoding="utf-8")).encode())
        h.update(b"\x00")
    return h.hexdigest()


def load_salts(root: Path) -> Optional[Dict]:
    path = root / SALTS_REL
    if not path.exists():
        return None
    data = json.loads(path.read_text(encoding="utf-8"))
    if data.get("version") != SALTS_VERSION:
        raise LintConfigError(
            f"{path}: salts config version {data.get('version')!r} "
            f"!= {SALTS_VERSION}")
    return data


def _find_salt_assignment(tree: ast.Module, name: str):
    """(lineno, int value) of ``NAME = <int>`` at module level."""
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                any(isinstance(t, ast.Name) and t.id == name
                    for t in node.targets) and \
                isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, int):
            return node.lineno, node.value.value
    return None


def update_salts(root: Path) -> List[str]:
    """Re-pin every salt's value and surface hash; returns the names
    whose pins changed.  Used by ``python -m tools.lint
    --update-salts``."""
    root = Path(root).resolve()
    data = load_salts(root)
    if data is None:
        raise LintConfigError(f"no salts config at {root / SALTS_REL}")
    changed = []
    for name, pin in sorted(data["salts"].items()):
        tree = ast.parse((root / pin["defined_in"]
                          ).read_text(encoding="utf-8"))
        found = _find_salt_assignment(tree, name)
        if found is None:
            raise LintConfigError(
                f"{pin['defined_in']}: no module-level integer "
                f"assignment for {name}")
        _, value = found
        new_hash = surface_hash(root, pin["surface"])
        if value != pin["value"] or new_hash != pin["surface_hash"]:
            changed.append(name)
        pin["value"] = value
        pin["surface_hash"] = new_hash
    (root / SALTS_REL).write_text(
        json.dumps(data, indent=1, sort_keys=True) + "\n",
        encoding="utf-8")
    return changed


@register
class SaltDriftRule(Rule):
    name = "salt-drift"
    contract = ("*_SEMANTICS_VERSION salts are pinned to a normalized "
                "hash of their engine's semantic surface")

    def check_repo(self, ctx: Context) -> Iterable[Finding]:
        data = load_salts(ctx.root)
        if data is None:
            return                        # fixture roots without pins
        for name, pin in sorted(data["salts"].items()):
            defined_in = pin["defined_in"]
            path = ctx.root / defined_in
            if not path.exists():
                yield Finding(self.name, defined_in, 0,
                              f"salt {name} pinned but its defining "
                              "module is gone; update "
                              "tools/lint/salts.json")
                continue
            try:
                found = _find_salt_assignment(ctx.source(path).tree,
                                              name)
            except SyntaxError:
                continue                  # parse-error reported already
            if found is None:
                yield Finding(self.name, defined_in, 0,
                              f"salt {name} not found as a module-"
                              "level integer assignment; update "
                              "tools/lint/salts.json")
                continue
            lineno, value = found
            missing = [f for f in pin["surface"]
                       if not (ctx.root / f).exists()]
            if missing:
                yield Finding(self.name, defined_in, lineno,
                              f"salt {name}: surface file(s) "
                              f"{missing} missing; update "
                              "tools/lint/salts.json")
                continue
            actual = surface_hash(ctx.root, pin["surface"])
            if value != pin["value"]:
                yield Finding(
                    self.name, defined_in, lineno,
                    f"{name} = {value} but the pin records "
                    f"{pin['value']}: after bumping a salt, "
                    "regenerate tests/data/engine_point_hashes.json "
                    "(engine cache keys moved) and re-pin with "
                    "`python -m tools.lint --update-salts`")
            elif actual != pin["surface_hash"]:
                yield Finding(
                    self.name, defined_in, lineno,
                    f"semantic surface of {name} changed without a "
                    f"salt bump (files: {', '.join(pin['surface'])})"
                    ": bump the salt and regenerate "
                    "tests/data/engine_point_hashes.json, or — only "
                    "for a semantics-neutral refactor — re-pin via "
                    "`python -m tools.lint --update-salts`")
