"""DL016 — program-construction sites vs the PROGRAM_SITES registry
(ISSUE 14).

Contract: the program ledger's coverage claim — "every device program
the serving path compiles is compile/cost/memory-observable" — is only
as good as the registry.  A new `jax.jit(...)`
entry point added without a registry decision is a program whose
compile time, FLOPs and HBM footprint silently go dark (exactly the
blind spot ISSUE 14 closes); an instrumented scope whose
`instrument(...)` hook was refactored away keeps promising ledger
coverage that no longer exists.

The DL013 FETCH_SITES idiom, applied to program construction.
`PROGRAM_SITES` (das_tpu/obs/proflog.py) is a dict mapping every scope
that constructs a device program — attributed to its OUTERMOST
enclosing function, module-qualified like DL013 ("fused.build_fused",
"fused.build_fused_tree") — to its ledger site label, or None for a
DECLARED-EXEMPT scope (per-op staged programs, ingest-time builders).
Five legs:

  * a jit reference in an UNdeclared scope fails lint — every
    program-construction site stays a reviewed decision in one list;
  * a declared scope with a non-None label must contain a ledger hook
    call (`instrument(...)`) passing EXACTLY
    that label literal — an instrumented site cannot silently drop its
    ledger coverage;
  * every `instrument("<label>")` literal
    anywhere must be a declared label — a typo'd site records into a
    lane nobody aggregates (the DL004/DL014 failure mode);
  * a declared scope with NO jit reference is a stale entry
    (full-set runs only — a --changed-only subset may not include the
    module);
  * where the analyzed set declares PROGRAM_NAMES (obs/registry.py), a
    scope that calls `instrument("<label>")` must name its program
    `named_program("das_<label>", ...)`: the ledger site and the module
    name the device trace shows stay one vocabulary (DL014 pins the
    name literals against PROGRAM_NAMES in both directions).

Attribution counts ANY AST reference to `jax.jit`
(call, decorator, `partial(jax.jit, ...)` argument) — the construction
primitive reaching a scope at all is what makes it a program site.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from das_tpu.analysis.callgraph import scope_module
from das_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    attr_chain,
    const_str,
    module_assign,
    register,
    str_collection,
)

#: the program-construction primitives this registry closes over —
#: dotted references and the bare name a `from jax import jit` binds
_PROGRAM_CHAINS = frozenset(("jax.jit",))
_PROGRAM_NAMES = frozenset(("jit",))

#: ledger hook call names whose first string argument is a site label
_HOOK_CALLS = frozenset(("instrument",))


def _find_registry(ctx: AnalysisContext):
    """(SourceFile, {scope: label-or-None}) of the PROGRAM_SITES dict —
    first declaring module wins (das_tpu/obs/proflog.py in the real
    tree; fixtures declare their own)."""
    for sf in ctx.modules():
        node = module_assign(sf.tree, "PROGRAM_SITES")
        if isinstance(node, ast.Dict):
            out: Dict[str, Optional[str]] = {}
            ok = True
            for k, v in zip(node.keys, node.values):
                key = const_str(k) if k is not None else None
                if key is None:
                    ok = False
                    break
                if isinstance(v, ast.Constant) and v.value is None:
                    out[key] = None
                else:
                    lab = const_str(v)
                    if lab is None:
                        ok = False
                        break
                    out[key] = lab
            if ok:
                return sf, out
    return None


def _program_refs(fn: ast.AST) -> Iterable[int]:
    """Lines where a program-construction primitive is referenced
    anywhere under `fn` — calls, decorators, and partial(...) args all
    contain the same Attribute/Name node."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            if attr_chain(node) in _PROGRAM_CHAINS:
                yield node.lineno
        elif isinstance(node, ast.Name) and node.id in _PROGRAM_NAMES:
            yield node.lineno


def _toplevel_refs(sf) -> Iterable[int]:
    """Program-construction references OUTSIDE any function — module or
    class body, i.e. import-time program construction.  There is no
    scope to declare for these (PROGRAM_SITES entries are functions):
    an import-time jit is an unconditional compile with no ledger seam
    — the DL013 toplevel-fetch leg, applied to construction."""

    def walk(node: ast.AST):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(child, ast.Attribute):
                if attr_chain(child) in _PROGRAM_CHAINS:
                    yield child.lineno
            elif (
                isinstance(child, ast.Name)
                and child.id in _PROGRAM_NAMES
                and not isinstance(getattr(child, "ctx", None), ast.Store)
            ):
                yield child.lineno
            yield from walk(child)

    yield from walk(sf.tree)


def _outermost_scopes(sf) -> Iterable[Tuple[str, ast.AST]]:
    """(qualified scope, def node) for every OUTERMOST function — the
    DL013 attribution (class methods "mod.Class.meth")."""
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


def _call_literals(fn: ast.AST, names) -> Iterable[Tuple[int, str]]:
    """(line, first-argument literal) for every call under `fn` to a
    function whose bare name is in `names`."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None
        )
        if name in names and node.args:
            lit = const_str(node.args[0])
            if lit is not None:
                yield node.lineno, lit


def _hook_literals(fn: ast.AST) -> Iterable[Tuple[int, str]]:
    """(line, label literal) for every ledger hook call under `fn`."""
    return _call_literals(fn, _HOOK_CALLS)


@register("DL016", "program-construction sites vs PROGRAM_SITES registry")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    registry = _find_registry(ctx)
    names_declared = any(
        str_collection(module_assign(sf.tree, "PROGRAM_NAMES")) is not None
        for sf in ctx.modules()
    )
    used_scopes: Set[str] = set()
    used_labels: Set[str] = set()
    any_ref = False
    for sf in ctx.modules():
        for line in _toplevel_refs(sf):
            any_ref = True
            yield Finding(
                "DL016", sf.posix, line,
                "program construction (jax.jit) outside "
                "any function — an import-time compile fires "
                "unconditionally and has no declarable PROGRAM_SITES "
                "scope; move it into a declared builder function",
            )
        for scope, fn in _outermost_scopes(sf):
            ref_lines = list(_program_refs(fn))
            hooks = list(_hook_literals(fn))
            for line, lit in hooks:
                used_labels.add(lit)
                if registry is not None and lit not in set(
                    v for v in registry[1].values() if v is not None
                ):
                    yield Finding(
                        "DL016", sf.posix, line,
                        f"ledger hook label {lit!r} is not a declared "
                        f"PROGRAM_SITES label ({registry[0].short}) — a "
                        "typo'd site records into an aggregate nobody "
                        "reads while the declared lane goes silent",
                    )
            if names_declared:
                named = {
                    lit for _l, lit in _call_literals(fn, ("named_program",))
                }
                for line, lit in _call_literals(fn, ("instrument",)):
                    if "das_" + lit not in named:
                        yield Finding(
                            "DL016", sf.posix, line,
                            f"scope `{scope}` instruments ledger site "
                            f"{lit!r} but names no program "
                            f"named_program('das_{lit}', ...) — its "
                            "module shows as jit_fn in the device trace "
                            "and the benchmark's device-time readers "
                            "cannot tell it from a merge",
                        )
            if not ref_lines:
                continue
            any_ref = True
            if registry is None:
                yield Finding(
                    "DL016", sf.posix, ref_lines[0],
                    "program construction (jax.jit) but "
                    "no PROGRAM_SITES registry in the analyzed set "
                    "(das_tpu/obs/proflog.py declares it)",
                )
                continue
            used_scopes.add(scope)
            if scope not in registry[1]:
                yield Finding(
                    "DL016", sf.posix, ref_lines[0],
                    f"program construction in undeclared scope `{scope}` "
                    "— every jit entry point must be declared in "
                    f"PROGRAM_SITES ({registry[0].short}) as instrumented "
                    "(ledger label) or reviewed-exempt (None), or its "
                    "compile/cost/memory telemetry silently goes dark",
                )
                continue
            label = registry[1][scope]
            if label is not None and label not in {
                lit for _line, lit in hooks
            }:
                yield Finding(
                    "DL016", sf.posix, ref_lines[0],
                    f"scope `{scope}` is declared as ledger-instrumented "
                    f"(label {label!r}) but contains no "
                    f"instrument call passing that label — "
                    "the site's programs would compile unobserved while "
                    "the registry promises coverage",
                )
    if registry is not None and any_ref and not ctx.partial:
        reg_sf, declared = registry
        line = next(
            (
                n.lineno for n in reg_sf.tree.body
                if isinstance(n, (ast.Assign, ast.AnnAssign))
                and any(
                    getattr(t, "id", None) == "PROGRAM_SITES"
                    for t in (
                        n.targets if isinstance(n, ast.Assign)
                        else [n.target]
                    )
                )
            ),
            1,
        )
        for scope in declared:
            if scope not in used_scopes:
                yield Finding(
                    "DL016", reg_sf.posix, line,
                    f"PROGRAM_SITES declares `{scope}` but no jit "
                    "construction lives there — stale entry (the builder "
                    "moved, got renamed, or stopped constructing "
                    "programs)",
                )
