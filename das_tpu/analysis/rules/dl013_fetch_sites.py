"""DL013 — fetch-site registry: every host transfer is declared and
tallied.

Contract (ISSUE 11; ARCHITECTURE §10): every `jax.device_get` is a
host sync that waits for the device, and the serving pipeline's latency
story is literally the count of them — "one transfer per settle round"
(FETCH_COUNTS pins it in the bench/pipeline suites).  Until now that
was enforced only where someone thought to pin a delta; a new
device_get anywhere else (a debug fetch in a join helper, a
convenience `.tolist()` path) silently adds a sync per query with no
test failing.

The DL009 COLLECTIVE_SITES idiom, applied to transfers:
`FETCH_SITES` (query/fused.py, next to FETCH_COUNTS) declares the
closed set of scopes allowed to call `jax.device_get`; calls attribute
to their OUTERMOST enclosing function qualified by module
("fused.settle_pending_iter", "fused.FusedExecutor.execute" —
`__init__` modules take their package name, so planner/__init__.py is
"planner").  Three legs:

  * an undeclared device_get fails lint — every host transfer stays
    reviewable in one list;
  * a declared scope with no device_get is a stale entry (full-set
    runs only — a --changed-only run may not include the module);
  * a declared scope whose outermost function does NOT also increment
    a fetch tally (`FETCH_COUNTS[...] += ..` or starcount's
    `FETCHES[...]`) fails: the fetches-per-query telemetry the bench
    decomposes host latency with must not undercount, so the registry
    is pinned BOTH ways against the counter.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, Optional, Set, Tuple

from das_tpu.analysis.callgraph import scope_module
from das_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    attr_chain,
    module_assign,
    register,
    str_collection,
)

#: the host-transfer primitives this registry closes over
_FETCH_CALLS = frozenset(("jax.device_get", "device_get"))

#: counter dicts that count as a fetch tally
_TALLY_NAMES = frozenset(("FETCH_COUNTS", "FETCHES"))


def _find_registry(ctx: AnalysisContext):
    for sf in ctx.modules():
        keys = str_collection(module_assign(sf.tree, "FETCH_SITES"))
        if keys is not None:
            return sf, keys
    return None


def _is_fetch_call(node: ast.Call) -> bool:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id in _FETCH_CALLS
    chain = attr_chain(fn)
    return chain in _FETCH_CALLS


def _outermost_scopes(sf) -> Iterable[Tuple[str, ast.AST]]:
    """(qualified scope, def node) for every OUTERMOST function, class
    methods qualified ("mod.Class.meth") — the DL009 attribution."""
    mod = scope_module(sf)

    def walk(node: ast.AST, classes):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                yield from walk(child, classes + [child.name])
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield ".".join([mod] + classes + [child.name]), child
            else:
                yield from walk(child, classes)

    yield from walk(sf.tree, [])


def _fetches_in(fn: ast.AST) -> Iterable[int]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and _is_fetch_call(node):
            yield node.lineno


def _toplevel_fetches(sf) -> Iterable[int]:
    """device_get calls OUTSIDE any function — module level or a class
    body, i.e. import-time transfers.  There is no scope to declare for
    these (FETCH_SITES entries are functions), and an import-time fetch
    is never legitimate: it fires unconditionally."""

    def walk(node: ast.AST):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(child, ast.Call) and _is_fetch_call(child):
                yield child.lineno
            yield from walk(child)

    yield from walk(sf.tree)


def _has_tally(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.AugAssign)
            and isinstance(node.target, ast.Subscript)
        ):
            base = node.target.value
            name = base.id if isinstance(base, ast.Name) else (
                base.attr if isinstance(base, ast.Attribute) else None
            )
            if name in _TALLY_NAMES:
                return True
    return False


@register("DL013", "host-transfer sites vs FETCH_SITES registry")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    registry = _find_registry(ctx)
    used: Set[str] = set()
    any_fetch = False
    for sf in ctx.modules():
        for line in _toplevel_fetches(sf):
            any_fetch = True
            yield Finding(
                "DL013", sf.posix, line,
                "jax.device_get outside any function (module/class "
                "body) — an import-time host transfer fires "
                "unconditionally and has no declarable FETCH_SITES "
                "scope; move it into a declared fetch function",
            )
        for scope, fn in _outermost_scopes(sf):
            lines = list(_fetches_in(fn))
            if not lines:
                continue
            any_fetch = True
            if registry is None:
                yield Finding(
                    "DL013", sf.posix, lines[0],
                    "jax.device_get but no FETCH_SITES registry in the "
                    "analyzed set (query/fused.py declares it, next to "
                    "FETCH_COUNTS)",
                )
                continue
            used.add(scope)
            if scope not in registry[1]:
                yield Finding(
                    "DL013", sf.posix, lines[0],
                    f"jax.device_get in undeclared scope `{scope}` — "
                    f"every host transfer is a device sync and must be "
                    f"declared in FETCH_SITES ({registry[0].short}) so "
                    "the one-transfer-per-settle-round contract stays "
                    "reviewable",
                )
                continue
            if not _has_tally(fn):
                yield Finding(
                    "DL013", sf.posix, lines[0],
                    f"declared fetch scope `{scope}` pays a device_get "
                    "without tallying FETCH_COUNTS — the fetches-per-"
                    "query telemetry (bench latency decomposition) "
                    "would undercount this site",
                )
    if registry is not None and any_fetch and not ctx.partial:
        reg_sf, declared = registry
        line = next(
            (
                n.lineno for n in reg_sf.tree.body
                if isinstance(n, ast.Assign)
                and any(
                    getattr(t, "id", None) == "FETCH_SITES"
                    for t in n.targets
                )
            ),
            1,
        )
        for scope in declared:
            if scope not in used:
                yield Finding(
                    "DL013", reg_sf.posix, line,
                    f"FETCH_SITES declares `{scope}` but no device_get "
                    "lives there — stale entry (the function moved, got "
                    "renamed, or stopped fetching)",
                )
