"""DL009 — shard_map collective discipline.

Contract (ISSUE 10 / ROADMAP "named candidate rules"): XLA collectives
(`all_gather` / `all_to_all` / `psum` / `pmax` / `pmin` / `ppermute` /
`psum_scatter`) are the mesh programs' ONLY cross-shard channel, and
where they may appear is a closed, declared set: only inside the
scopes declared in `COLLECTIVE_SITES` (parallel/mesh.py) — the mesh
helpers (gather/exchange/reduction) whose collective use IS their
purpose.  Concentrating the call sites keeps every cross-shard byte
visible in one reviewable list (the ICI traffic model of ARCHITECTURE
§8).

Attribution: a call is charged to its OUTERMOST enclosing scope —
leading class names plus the first function name, qualified by the
module stem ("fused_sharded._repartition",
"sharded_db.ShardedDB._join") — so nested closure bodies (`body`,
`kernel`, `build`) charge to the helper that owns them.  Both
directions are pinned: an undeclared collective call fails lint, and a
declared scope that no longer contains a collective is a stale entry.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from das_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    module_assign,
    register,
    str_collection,
)

#: the XLA cross-shard communication primitives this rule pins
COLLECTIVE_NAMES = frozenset((
    "all_gather",
    "all_to_all",
    "psum",
    "pmax",
    "pmin",
    "ppermute",
    "psum_scatter",
))


def _find_registry(ctx: AnalysisContext):
    for sf in ctx.modules():
        keys = str_collection(module_assign(sf.tree, "COLLECTIVE_SITES"))
        if keys is not None:
            return sf, keys
    return None


def _is_collective_call(node: ast.Call) -> Optional[str]:
    """The collective's name when `node` calls one (lax.psum /
    jax.lax.all_gather / a from-imported bare name), else None."""
    fn = node.func
    if isinstance(fn, ast.Attribute) and fn.attr in COLLECTIVE_NAMES:
        return fn.attr
    if isinstance(fn, ast.Name) and fn.id in COLLECTIVE_NAMES:
        return fn.id
    return None


def _collective_sites(sf) -> Iterable[Tuple[int, str, str]]:
    """(line, collective name, outermost qualified scope) per call."""

    def walk(node: ast.AST, classes: List[str], func: Optional[str]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                # a class nested under a function charges to the func
                yield from walk(
                    (child),
                    (classes + [child.name]) if func is None else classes,
                    func,
                )
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from walk(
                    child, classes,
                    func if func is not None else child.name,
                )
            else:
                if isinstance(child, ast.Call):
                    name = _is_collective_call(child)
                    if name is not None:
                        scope = (
                            ".".join([sf.name] + classes + [func])
                            if func is not None else "<module>"
                        )
                        yield child.lineno, name, scope
                yield from walk(child, classes, func)

    yield from walk(sf.tree, [], None)


@register("DL009", "shard_map collective discipline")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    registry = _find_registry(ctx)
    used_scopes: Set[str] = set()
    any_calls = False
    for sf in ctx.modules():
        for line, name, scope in _collective_sites(sf):
            any_calls = True
            if registry is None:
                yield Finding(
                    "DL009", sf.posix, line,
                    f"collective `{name}` but no COLLECTIVE_SITES "
                    "registry in the analyzed set (das_tpu/parallel/"
                    "mesh.py declares it)",
                )
                continue
            used_scopes.add(scope)
            if scope not in registry[1]:
                yield Finding(
                    "DL009", sf.posix, line,
                    f"collective `{name}` in undeclared scope "
                    f"`{scope}` — collectives belong in the declared "
                    f"lowered helpers (COLLECTIVE_SITES, "
                    f"{registry[0].short}), where every cross-shard "
                    "byte stays reviewable in one list",
                )
    # stale entries are only provable against the FULL set — a partial
    # (--changed-only) run may simply not include a scope's module
    if registry is not None and any_calls and not ctx.partial:
        reg_sf, declared = registry
        line = next(
            (
                n.lineno for n in reg_sf.tree.body
                if isinstance(n, ast.Assign)
                and any(
                    getattr(t, "id", None) == "COLLECTIVE_SITES"
                    for t in n.targets
                )
            ),
            1,
        )
        for scope in declared:
            if scope not in used_scopes:
                yield Finding(
                    "DL009", reg_sf.posix, line,
                    f"COLLECTIVE_SITES declares `{scope}` but no "
                    "collective call lives there — stale entry (the "
                    "helper moved, got renamed, or lost its collective)",
                )
