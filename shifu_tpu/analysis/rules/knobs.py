"""undeclared-knob: the SHIFU_TPU_* env surface must round-trip
through the central registry in `config/environment.py`.

Flags, per file:
  * a literal `SHIFU_TPU_*` name read via `os.environ.get` /
    `os.environ[...]` / `os.getenv` / bare `getenv`/`environ` that is
    not declared in `config.environment.KNOBS` — declare it (name,
    type, default, doc) and read it through a `knob_*` accessor;
  * a raw environ read of a DECLARED knob outside the registry module
    itself — route it through `knob_int`/`knob_float`/`knob_str`/
    `knob_bool`/`knob_raw` so typing and defaults live in one place.

Flags, cross-file (finalize): a registry entry that no scanned file
ever references by name — a dead knob.
"""

from __future__ import annotations

import ast
import os
from typing import List, Set

from shifu_tpu.analysis.engine import Finding, const_str, dotted

RULES = ("undeclared-knob",)

_PREFIX = "SHIFU_TPU_"
_READ_FUNCS = {"os.environ.get", "environ.get", "os.getenv", "getenv"}


def _registry():
    from shifu_tpu.config import environment
    return environment.KNOBS


def _is_registry_module(path: str) -> bool:
    return path.replace(os.sep, "/").endswith("config/environment.py")


def check(tree: ast.Module, path: str, ctx: dict) -> List[Finding]:
    findings: List[Finding] = []
    knobs = _registry()
    seen: Set[str] = ctx.setdefault("knob-refs", set())
    in_registry = _is_registry_module(path)
    if in_registry:
        # the dead-entry sweep is only meaningful when the scan covers
        # the package (a single-file scan references almost nothing)
        ctx["knob-registry-scanned"] = True

    # docstring constants don't count as live references
    doc_ids = {id(n.value) for n in ast.walk(tree)
               if isinstance(n, ast.Expr)
               and isinstance(n.value, ast.Constant)
               and isinstance(n.value.value, str)}

    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and \
                node.value.startswith(_PREFIX) and \
                not in_registry and id(node) not in doc_ids:
            seen.add(node.value)

        name = None
        if isinstance(node, ast.Call) and \
                dotted(node.func) in _READ_FUNCS and node.args:
            ok, name = const_str(node.args[0])
            name = name if ok else None
        elif isinstance(node, ast.Subscript) and \
                dotted(node.value) in ("os.environ", "environ") and \
                isinstance(node.ctx, ast.Load):
            ok, name = const_str(node.slice)
            name = name if ok else None
        if name is None or not name.startswith(_PREFIX):
            continue
        if name not in knobs:
            findings.append(Finding(
                "undeclared-knob", path, node.lineno, node.col_offset,
                f"{name} is read from the environment but not declared "
                "in the knob registry (config/environment.py) — add a "
                "Knob entry (name/type/default/doc)"))
        elif not in_registry:
            findings.append(Finding(
                "undeclared-knob", path, node.lineno, node.col_offset,
                f"raw environ read of declared knob {name}; use "
                "config.environment.knob_" + knobs[name].type.replace(
                    "flag", "bool") +
                "(...) so the type/default live in the registry"))
    return findings


def finalize(ctx: dict) -> List[Finding]:
    findings: List[Finding] = []
    if not ctx.get("knob-registry-scanned"):
        return findings
    seen: Set[str] = ctx.get("knob-refs", set())
    for name in sorted(_registry()):
        if name not in seen:
            findings.append(Finding(
                "undeclared-knob", "config/environment.py", 0, 0,
                f"dead registry entry: {name} is declared but never "
                "referenced by any scanned file — delete the entry or "
                "wire up the read"))
    return findings
