"""env-knobs pass: every ``PADDLE_TPU_*`` environment read resolves
through the central registry (``paddle_tpu/framework/env_knobs.py``)
and the registry itself stays live and documented
(DESIGN-ANALYSIS.md §env-knobs).

Rules:

1. **No direct reads of the prefix.**  ``os.environ.get(...)`` /
   ``os.environ[...]`` / ``os.getenv(...)`` of a ``PADDLE_TPU_*``
   name anywhere outside ``env_knobs.py`` is a violation — those
   reads are exactly the scattered, undocumented knobs the registry
   exists to end.  Names are resolved through module-level string
   constants (``_DP_COMPRESS_ENV = "PADDLE_TPU_..."``).  Writes
   (``env["PADDLE_TPU_X"] = ...``, subprocess env dicts) are exempt:
   handing a knob to a child process is wiring, not reading.
2. **Registered names only.**  A literal name passed to
   ``env_knobs.get_raw/get_bool/get_int/get_float`` must be in the
   registry (the accessors also enforce this at runtime with
   KeyError); a *computed* name defeats the census and is rejected.
3. **No dead registry entries.**  Every registered knob's name must
   appear in production wiring — ``paddle_tpu/`` or the bench A/B
   harness (``bench.py``) — as a string literal.  An entry nothing
   mentions is documentation rot.
4. **README freshness.**  The block between the
   ``<!-- env-knobs:begin -->`` / ``<!-- env-knobs:end -->`` markers
   must equal ``env_knobs.render_table()`` output (regenerate with
   ``python scripts/lint.py --write-env-table``).
"""

from __future__ import annotations

import ast
import importlib.util
import os
from typing import Dict, List, Optional, Set

from . import core
from .core import Codebase, Violation

NAME = "env-knobs"
OK_MESSAGE = ("env-knob coverage OK: every PADDLE_TPU_* read resolves "
              "through the registry, every entry is wired, README "
              "table fresh")
REPORT_HEADER = "env-knob violations:"

PREFIX = "PADDLE_TPU_"
REGISTRY_MOD = os.path.join(core.PKG_REL, "framework", "env_knobs.py")
_ACCESSORS = {"get_raw", "get_bool", "get_int", "get_float"}

BEGIN_MARK = "<!-- env-knobs:begin -->"
END_MARK = "<!-- env-knobs:end -->"


def load_registry() -> Dict[str, object]:
    """The KNOBS dict, loaded straight from the file — stdlib-only by
    design, so no package import (and no jax) is paid here."""
    path = os.path.join(core.REPO, REGISTRY_MOD)
    spec = importlib.util.spec_from_file_location("_env_knobs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return dict(mod.KNOBS), mod.render_table()


def _env_read_name(node: ast.Call, consts: Dict[str, str]
                   ) -> Optional[str]:
    """The knob name read by an ``os.environ.get`` / ``os.getenv``
    call, resolved through module constants; None if not an env
    read."""
    f = node.func
    if isinstance(f, ast.Attribute):
        if f.attr == "getenv":
            pass
        elif f.attr == "get" and isinstance(f.value, ast.Attribute) \
                and f.value.attr == "environ":
            pass
        elif f.attr == "get" and isinstance(f.value, ast.Name) \
                and f.value.id == "environ":
            pass
        else:
            return None
    else:
        return None
    if not node.args:
        return None
    return _resolve(node.args[0], consts)


def _resolve(node: ast.AST, consts: Dict[str, str]) -> Optional[str]:
    val = core.const_str(node)
    if val is not None:
        return val
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _mentioned_names(mod) -> Set[str]:
    """Every PADDLE_TPU_* string literal in the module's AST — the
    wiring census for rule 3."""
    out = set()
    for node in ast.walk(mod.tree):
        val = core.const_str(node)
        if val is not None and val.startswith(PREFIX):
            out.add(val)
    return out


def run(cb: Codebase, registry=None) -> List[Violation]:
    if registry is None:
        knobs, table = load_registry()
    else:
        knobs, table = registry
    violations: List[Violation] = []
    wired: Set[str] = set()
    for mod in sorted(cb.modules.values(), key=lambda m: m.rel):
        is_registry = mod.rel == REGISTRY_MOD
        if not is_registry:
            wired |= _mentioned_names(mod)
        consts = core.module_str_constants(mod.tree)
        for node in ast.walk(mod.tree):
            # rule 1: direct env reads of the prefix
            if isinstance(node, ast.Call) and not is_registry:
                name = _env_read_name(node, consts)
                if name and name.startswith(PREFIX):
                    violations.append(Violation(
                        mod.rel, node.lineno,
                        f"direct os.environ read of {name} — resolve "
                        "through framework.env_knobs (the registry is "
                        "the one place a knob's name/default/doc "
                        "live)"))
            if isinstance(node, ast.Subscript) and not is_registry \
                    and isinstance(node.ctx, ast.Load) and \
                    isinstance(node.value, ast.Attribute) and \
                    node.value.attr == "environ":
                name = _resolve(node.slice, consts)
                if name and name.startswith(PREFIX):
                    violations.append(Violation(
                        mod.rel, node.lineno,
                        f"direct os.environ[{name!r}] read — resolve "
                        "through framework.env_knobs"))
            # rule 2: accessor names must be registered literals
            if isinstance(node, ast.Call) and not is_registry and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _ACCESSORS and \
                    isinstance(node.func.value, ast.Name) and \
                    "env_knobs" in node.func.value.id and node.args:
                name = _resolve(node.args[0], consts)
                if name is None:
                    violations.append(Violation(
                        mod.rel, node.lineno,
                        f"computed knob name passed to env_knobs."
                        f"{node.func.attr}() — knob reads must be "
                        "statically auditable literals"))
                elif name not in knobs:
                    violations.append(Violation(
                        mod.rel, node.lineno,
                        f"{name} is not in the env_knobs registry — "
                        "register it (name/default/doc) or fix the "
                        "typo (get_raw would raise KeyError at "
                        "runtime)"))
    # rule 3: dead registry entries
    for name in sorted(knobs):
        if name not in wired:
            violations.append(Violation(
                REGISTRY_MOD, 0,
                f"registered knob {name} has no production wiring — "
                "nothing in paddle_tpu/ or the bench harness mentions "
                "it (dead entry, or the consumer was removed)"))
    # rule 4: README table freshness
    readme = cb.texts.get("README.md")
    if readme is not None:
        if BEGIN_MARK not in readme or END_MARK not in readme:
            violations.append(Violation(
                "README.md", 0,
                f"missing env-knob table markers ({BEGIN_MARK} / "
                f"{END_MARK}) — run python scripts/lint.py "
                "--write-env-table"))
        else:
            start = readme.index(BEGIN_MARK) + len(BEGIN_MARK)
            end = readme.index(END_MARK)
            current = readme[start:end].strip("\n")
            if current != table.strip("\n"):
                line = readme[:readme.index(BEGIN_MARK)].count("\n") + 1
                violations.append(Violation(
                    "README.md", line,
                    "env-knob table is stale (registry and README "
                    "disagree) — regenerate with python "
                    "scripts/lint.py --write-env-table"))
    return violations


def write_env_table(repo: str = core.REPO) -> bool:
    """Regenerate the README block between the markers; returns True
    when the file changed."""
    _, table = load_registry()
    path = os.path.join(repo, "README.md")
    with open(path) as fh:
        readme = fh.read()
    block = f"{BEGIN_MARK}\n{table}{END_MARK}"
    if BEGIN_MARK in readme and END_MARK in readme:
        start = readme.index(BEGIN_MARK)
        end = readme.index(END_MARK) + len(END_MARK)
        new = readme[:start] + block + readme[end:]
    else:
        section = (
            "\n## Environment knobs\n\n"
            "Every `PADDLE_TPU_*` variable the package reads, "
            "generated from the registry\n"
            "(`paddle_tpu/framework/env_knobs.py`) by `python "
            "scripts/lint.py --write-env-table`;\n"
            "the `env-knobs` lint pass fails when this table goes "
            "stale.\n\n" + block + "\n")
        new = readme.rstrip("\n") + "\n" + section
    if new != readme:
        with open(path, "w") as fh:
            fh.write(new)
        return True
    return False
