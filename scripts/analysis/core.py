"""Shared pass framework for the program-stability analysis suite
(DESIGN-ANALYSIS.md).

Every static check in ``scripts/analysis/`` runs over ONE
:class:`Codebase`: one file walk, one ``ast.parse`` per module, with
per-line ``# lint: allow(<pass>): <reason>`` suppressions collected up
front so each pass reports violations uniformly and the suppression
ledger (who silenced what, and why) stays on record.

A pass is a module with two attributes:

* ``NAME`` — kebab-case pass name (what ``allow(...)`` keys on),
* ``run(cb: Codebase) -> List[Violation]`` — the check itself.

``run_pass`` applies suppressions; ``scripts/lint.py`` additionally
enforces suppression hygiene (reason required, pass name must exist,
unused suppressions are themselves violations).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
PKG_REL = "paddle_tpu"

# Modules outside paddle_tpu/ that wire env knobs (bench A/B harness);
# README.md rides along as text for the staleness check.
EXTRA_MODULES = ("bench.py",)
TEXT_FILES = ("README.md",)

# same-line suppression: ``code  # lint: allow(pass-name): reason``
SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*allow\(([a-z0-9_-]+)\)(?::\s*(.*\S))?")


class Violation(NamedTuple):
    rel: str          # path relative to the repo root
    line: int
    message: str
    pass_name: str = ""


class Suppression:
    __slots__ = ("rel", "line", "pass_name", "reason", "used")

    def __init__(self, rel: str, line: int, pass_name: str,
                 reason: Optional[str]):
        self.rel = rel
        self.line = line
        self.pass_name = pass_name
        self.reason = reason
        self.used = False


class Module:
    """One parsed production module: source, AST, suppressions."""

    __slots__ = ("rel", "source", "tree", "suppressions")

    def __init__(self, rel: str, source: str, tree: ast.Module):
        self.rel = rel
        self.source = source
        self.tree = tree
        self.suppressions: List[Suppression] = []
        for i, text in enumerate(source.splitlines(), start=1):
            m = SUPPRESS_RE.search(text)
            if m:
                self.suppressions.append(
                    Suppression(rel, i, m.group(1), m.group(2)))


class Codebase:
    """The one-walk, one-parse-per-module view every pass shares."""

    def __init__(self, modules: Dict[str, Module],
                 broken: Dict[str, Tuple[int, str]],
                 texts: Dict[str, str], repo: str = REPO):
        self.modules = modules
        self.broken = broken        # rel -> (lineno, syntax-error msg)
        self.texts = texts
        self.repo = repo

    @classmethod
    def load(cls, repo: str = REPO) -> "Codebase":
        modules: Dict[str, Module] = {}
        broken: Dict[str, Tuple[int, str]] = {}
        pkg = os.path.join(repo, PKG_REL)
        paths = []
        for dirpath, dirnames, filenames in os.walk(pkg):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            paths.extend(os.path.join(dirpath, f)
                         for f in sorted(filenames) if f.endswith(".py"))
        paths.extend(os.path.join(repo, rel) for rel in EXTRA_MODULES)
        for path in paths:
            if not os.path.exists(path):
                continue
            rel = os.path.relpath(path, repo)
            with open(path) as fh:
                source = fh.read()
            try:
                tree = ast.parse(source, filename=path)
            except SyntaxError as e:
                broken[rel] = (e.lineno or 0, e.msg or "syntax error")
                continue
            modules[rel] = Module(rel, source, tree)
        texts = {}
        for rel in TEXT_FILES:
            path = os.path.join(repo, rel)
            if os.path.exists(path):
                with open(path) as fh:
                    texts[rel] = fh.read()
        return cls(modules, broken, texts, repo)

    @classmethod
    def from_sources(cls, sources: Dict[str, str],
                     texts: Optional[Dict[str, str]] = None
                     ) -> "Codebase":
        """Synthetic codebase for the negative-control tests: map of
        repo-relative path -> python source."""
        modules: Dict[str, Module] = {}
        broken: Dict[str, Tuple[int, str]] = {}
        for rel, source in sources.items():
            try:
                tree = ast.parse(source, filename=rel)
            except SyntaxError as e:
                broken[rel] = (e.lineno or 0, e.msg or "syntax error")
                continue
            modules[rel] = Module(rel, source, tree)
        return cls(modules, broken, dict(texts or {}), repo=REPO)

    # -- access ----------------------------------------------------------
    def get(self, rel: str) -> Optional[Module]:
        return self.modules.get(rel)

    def iter_modules(self, prefix: str = PKG_REL + os.sep
                     ) -> Iterator[Module]:
        for rel in sorted(self.modules):
            if rel.startswith(prefix):
                yield self.modules[rel]

    def all_suppressions(self) -> Iterator[Suppression]:
        for rel in sorted(self.modules):
            yield from self.modules[rel].suppressions

    def suppressions_at(self, rel: str, line: int, pass_name: str
                        ) -> List[Suppression]:
        mod = self.modules.get(rel)
        if mod is None:
            return []
        return [s for s in mod.suppressions
                if s.line == line and s.pass_name == pass_name]


# -- shared AST helpers ------------------------------------------------------

def call_name(call: ast.Call) -> str:
    """Terminal name of a call: ``f(...)`` / ``obj.f(...)`` -> 'f'."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    return getattr(f, "id", "")


def enclosing_chains(tree: ast.Module) -> Tuple[list, Dict[int, list]]:
    """All function defs plus ``id(node) -> [enclosing functions]``
    (outermost first, innermost last) — the one walk every
    function-scoped rule shares."""
    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    chains: Dict[int, list] = {}
    for fn in funcs:
        for n in ast.walk(fn):
            chains.setdefault(id(n), []).append(fn)
    return funcs, chains


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def module_str_constants(tree: ast.Module) -> Dict[str, str]:
    """Module-level ``NAME = "literal"`` bindings (e.g. the
    ``_DP_COMPRESS_ENV = "PADDLE_TPU_DP_COMPRESS"`` idiom)."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            val = const_str(node.value)
            if val is not None:
                out[node.targets[0].id] = val
    return out


# -- runner ------------------------------------------------------------------

def run_pass(cb: Codebase, pass_mod) -> List[Violation]:
    """Run one pass and apply same-line suppressions (marking them
    used).  Suppression *hygiene* is lint.py's job, not the pass's."""
    out: List[Violation] = []
    for v in pass_mod.run(cb):
        sups = cb.suppressions_at(v.rel, v.line, pass_mod.NAME)
        if sups:
            for s in sups:
                s.used = True
        else:
            out.append(v._replace(pass_name=pass_mod.NAME))
    return out


def suppression_violations(cb: Codebase, known_passes,
                           ran_passes) -> List[Violation]:
    """The suppression ledger's own rules: every ``allow`` names a real
    pass, carries a reason, and silences something that still fires."""
    out: List[Violation] = []
    ran = set(ran_passes)
    for s in cb.all_suppressions():
        if s.pass_name not in known_passes:
            out.append(Violation(
                s.rel, s.line,
                f"lint: allow({s.pass_name}) names an unknown pass "
                f"(known: {', '.join(sorted(known_passes))})",
                "suppressions"))
            continue
        if not s.reason:
            out.append(Violation(
                s.rel, s.line,
                f"lint: allow({s.pass_name}) has no reason — every "
                "suppression carries its justification on record",
                "suppressions"))
        if s.pass_name in ran and not s.used:
            out.append(Violation(
                s.rel, s.line,
                f"unused suppression: allow({s.pass_name}) silences "
                "nothing the pass still reports — remove it",
                "suppressions"))
    return out


def format_report(violations: List[Violation]) -> str:
    lines = []
    for v in violations:
        tag = f" [{v.pass_name}]" if v.pass_name else ""
        lines.append(f"  {v.rel}:{v.line}: {v.message}{tag}")
    return "\n".join(lines)
