"""DL012 — retrace hygiene at program-construction sites.

Contract (ISSUE 11): every compiled-program construction site —
`jax.jit(...)`, `shard_map(...)` (calls and
decorators) — keys its executable cache on the STATIC inputs of the
traced callable: its closure and static arguments.  The codebase's
idiom is the frozen-`*Sig` builder (`build_fused(sig: FusedPlanSig)`)
— everything the traced function closes over derives from the frozen
signature that IS the cache key — plus explicit `static_argnames` on
module-level wrappers.  A per-request python value slipping into that
closure (the DL002 lesson, dynamic edition) silently keys a
recompile-per-query: no functional test fails, the serving pipeline
just compiles forever.

Two legs, both shape checks in the house style (they force the idiom
where review can see the keying, not prove a dataflow theorem):

  * **keying discipline** — an inner construction site must be one of:
    a module-level decorator/assignment (statics are explicit), inside
    a builder (a function with a `*Sig`-annotated parameter, or named
    `build_*`/`make_*` — the declared factory idiom), or its result
    must visibly flow to a cache (`X[key] =
    fn`), a `return`, or a call in the same function.  A constructed
    program that does none of those has no reviewable cache key;
  * **per-request taint** — a parameter of the enclosing function
    chain that is annotated as a mutable container (`dict`/`list`/
    `set`/`Dict[..]`/..), defaulted to a mutable literal, or taken as
    `**kwargs` must not reach the traced callable's free variables or
    the construction call's arguments.  Those are exactly the values
    whose identity/content change per request: closing over one keys
    the trace on it (or worse, on nothing).

Frozen `*Sig` parameters and module-level constants remain the blessed
origins; plain positional values (ints, tuples, arrays) pass — arrays
are traced operands, and hashable statics are the jit cache's job.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from das_tpu.analysis.core import AnalysisContext, Finding, attr_chain, register

_CONSTRUCTORS = frozenset(("jit", "shard_map"))

_MUTABLE_ANNOTATIONS = frozenset((
    "dict", "list", "set", "Dict", "List", "Set", "DefaultDict",
    "MutableMapping", "MutableSequence", "Any", "object",
))


def _ctor_name(fn: ast.AST) -> Optional[str]:
    if isinstance(fn, ast.Name) and fn.id in _CONSTRUCTORS:
        return fn.id
    if isinstance(fn, ast.Attribute) and fn.attr in _CONSTRUCTORS:
        return attr_chain(fn) or fn.attr
    return None


def _is_ctor_call(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Call):
        name = _ctor_name(node.func)
        if name is not None:
            return name
        # partial(jax.jit, static_argnames=...) decorator form
        if (
            isinstance(node.func, ast.Name) and node.func.id == "partial"
            and node.args
        ):
            return _ctor_name(node.args[0])
    return None


def _sig_param(fn: ast.AST) -> bool:
    for p in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
        ann = p.annotation
        name = None
        if isinstance(ann, ast.Name):
            name = ann.id
        elif isinstance(ann, ast.Attribute):
            name = ann.attr
        elif isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            name = ann.value.split(".")[-1].split("[")[0]
        if name is not None and name.endswith("Sig"):
            return True
    return False


def _is_builder(fn: ast.AST) -> bool:
    return (
        fn.name.startswith(("build_", "make_", "_build", "_make"))
        or _sig_param(fn)
    )


def _ann_name(ann: ast.AST) -> Optional[str]:
    if isinstance(ann, ast.Name):
        return ann.id
    if isinstance(ann, ast.Attribute):
        return ann.attr
    if isinstance(ann, ast.Subscript):
        return _ann_name(ann.value)
    return None


def _banned_params(fn: ast.AST) -> Dict[str, str]:
    """param name -> why it is a per-request mutable origin."""
    out: Dict[str, str] = {}
    a = fn.args
    params = a.posonlyargs + a.args + a.kwonlyargs
    defaults = list(a.defaults)
    # align defaults with the tail of positional params
    pos = a.posonlyargs + a.args
    for p, d in zip(pos[len(pos) - len(defaults):], defaults):
        if isinstance(d, (ast.Dict, ast.List, ast.Set)) or (
            isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
            and d.func.id in ("dict", "list", "set")
        ):
            out[p.arg] = "mutable default"
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if isinstance(d, (ast.Dict, ast.List, ast.Set)):
            out[p.arg] = "mutable default"
    for p in params:
        name = _ann_name(p.annotation) if p.annotation is not None else None
        if name in _MUTABLE_ANNOTATIONS:
            out[p.arg] = f"param annotated `{name}`"
    if a.kwarg is not None:
        out[a.kwarg.arg] = "**kwargs"
    return out


def _propagate(fn: ast.AST, banned: Dict[str, str]) -> Dict[str, str]:
    """One forward pass: locals assigned from banned names inherit the
    reason (x = opts; ... closes over x)."""
    out = dict(banned)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Name):
            why = out.get(node.value.id)
            if why:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.setdefault(t.id, why)
    return out


def _local_defs(fn: ast.AST) -> Dict[str, ast.AST]:
    out: Dict[str, ast.AST] = {}
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = node
    return out


def _free_names(fn: ast.AST) -> Set[str]:
    """Names a nested def loads but does not bind itself (approximate:
    its own params + assigned names are bound; everything else is free
    and resolved against the enclosing chain by the caller)."""
    bound: Set[str] = set()
    a = fn.args
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        bound.add(p.arg)
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)
    loads: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load):
                loads.add(node.id)
            else:
                bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                bound.add(node.name)
    return loads - bound


def _names_in(e: ast.AST) -> Set[str]:
    return {
        n.id for n in ast.walk(e)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


def _enclosing_chains(tree: ast.Module):
    """Yield (site node, ctor name, kind, chain) for every construction
    site, chain = enclosing defs outermost-first ([] = module level).
    kind is 'call' or 'decorated' (the decorated def is the callable)."""

    def walk(node: ast.AST, chain: List[ast.AST]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in child.decorator_list:
                    name = (
                        _ctor_name(dec) if not isinstance(dec, ast.Call)
                        else _is_ctor_call(dec)
                    )
                    if name:
                        yield child, name, "decorated", list(chain)
                walk_chain = chain + [child]
                yield from walk(child, walk_chain)
            else:
                if isinstance(child, ast.Call):
                    name = _ctor_name(child.func)
                    if name:
                        yield child, name, "call", list(chain)
                yield from walk(child, chain)

    yield from walk(tree, [])


def _keyed_ok(site: ast.Call, chain: List[ast.AST], sf) -> bool:
    if not chain:
        return True  # module-level: statics are explicit in the def
    if any(_is_builder(fn) for fn in chain):
        return True
    inner = chain[-1]
    # the statement owning the site: Return is fine (factory idiom)
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(inner):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node
    stmt = site
    while id(stmt) in parents and not isinstance(stmt, ast.stmt):
        stmt = parents[id(stmt)]
    if isinstance(stmt, ast.Return):
        return True
    if isinstance(stmt, ast.Assign):
        targets: Set[str] = set()
        for t in stmt.targets:
            targets.update(
                n.id for n in ast.walk(t) if isinstance(n, ast.Name)
            )
        for node in ast.walk(inner):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in targets
            ):
                return True  # constructed-and-called in place
            if isinstance(node, ast.Assign) and isinstance(
                node.targets[0], ast.Subscript
            ) and targets & _names_in(node.value):
                return True  # stored into a cache under a key
            if isinstance(node, ast.Return) and node.value is not None and (
                targets & _names_in(node.value)
            ):
                return True
    return False


def _decorated_ok(fn_def: ast.AST, chain: List[ast.AST], sf) -> bool:
    if not chain:
        return True
    if any(_is_builder(f) for f in chain):
        return True
    # a nested jitted def that the enclosing function actually calls
    inner = chain[-1]
    for node in ast.walk(inner):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == fn_def.name
            and node is not fn_def
        ):
            return True
    return False


@register("DL012", "retrace hygiene at jit/shard_map sites")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    for sf in ctx.modules():
        for site, ctor, kind, chain in _enclosing_chains(sf.tree):
            # per-request taint leg
            tainted: Dict[str, str] = {}
            for fn in chain:
                tainted.update(_propagate(fn, _banned_params(fn)))
            if tainted:
                if kind == "decorated":
                    callable_defs = [site]
                    arg_names: Set[str] = set()
                else:
                    defs = {}
                    for fn in chain:
                        defs.update(_local_defs(fn))
                    callable_defs = [
                        defs[n.id] for n in ast.walk(site)
                        if isinstance(n, ast.Name) and n.id in defs
                    ]
                    arg_names = set()
                    for a in list(site.args) + [
                        k.value for k in site.keywords
                    ]:
                        if not isinstance(a, (ast.Lambda,)):
                            arg_names |= _names_in(a)
                hits: Dict[str, str] = {}
                for d in callable_defs:
                    for name in _free_names(d):
                        if name in tainted:
                            hits[name] = tainted[name]
                for name in arg_names:
                    if name in tainted and name not in {
                        d.name for d in callable_defs
                        if isinstance(d, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))
                    }:
                        hits[name] = tainted[name]
                for name, why in sorted(hits.items()):
                    yield Finding(
                        "DL012", sf.posix, site.lineno,
                        f"per-request mutable value `{name}` ({why}) "
                        f"reaches this {ctor} site's traced closure — "
                        "static/closure inputs must derive from frozen "
                        "*Sig fields or module constants, else every "
                        "request silently keys a fresh compile",
                    )
            # keying-discipline leg
            if kind == "call":
                ok = _keyed_ok(site, chain, sf)
            else:
                ok = _decorated_ok(site, chain, sf)
            if not ok:
                yield Finding(
                    "DL012", sf.posix, site.lineno,
                    f"{ctor} program constructed with no reviewable "
                    "cache keying — build it in a *Sig builder "
                    "(build_*/make_*), store it in a keyed cache, "
                    "return it, or call it in place (the executable "
                    "must not be re-created per request)",
                )
