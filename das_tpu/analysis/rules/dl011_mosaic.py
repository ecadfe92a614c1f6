"""DL011 — Mosaic readiness of kernel bodies.

Contract (ISSUE 11; ARCHITECTURE §9 "what still needs a real TPU"): no
kernel in das_tpu/kernels/ Mosaic-compiles yet (tests/test_tpu_compile.py
pins each verdict) — every body runs off-TPU by direct ref-discharge,
which accepts strictly MORE programs than the Mosaic lowering will.  The
hazards §9 enumerates are exactly the ones that surface only at the
chip's compiler, so they are enforced at lint time as well:

  * **ref access discipline** — a `*_ref` parameter of a kernel body
    (the KERNEL_BUFFERS naming convention, which the shared helpers
    keep: `_emit_window(.., fvals_ref, perm_ref, ..)`) may only be
    subscripted (`ref[...]` load / `ref[...] = ...` store) or
    forwarded to a repo-local helper that binds it to another `*_ref`
    parameter.  Handing the raw ref to `jnp.*`, aliasing it, or
    passing it into an unresolvable callee works under the discharge
    (`_Ref` quacks enough) and fails or silently misbehaves under
    Mosaic, where a Ref is a memory space, not an array;
  * **no python control flow on traced values** — `if`/`while`/`for`
    whose condition derives from a ref load concretizes a tracer:
    an error under jit, but under the python-loop grid discharge it
    can EXECUTE (step index and hoisted host values mix in), taking
    one trace path and silently diverging from the Mosaic lowering.
    Dataflow: values loaded from refs taint through assignments and
    calls; `.shape`/`.ndim`/`.dtype` access and `len()` break taint
    (static under tracing), and `x is None` tests are exempt
    (identity on the python cell, never a concretization);
  * **no float64/unpriced dtypes** — the byte models price int32/
    int64/bool (and TPUs have no f64); a float64/complex/f16 constant
    or cast inside a kernel module is either a Mosaic lowering error
    or a silent x2 on the VMEM footprint the planner budgeted;
  * **lane-tiled chunk_rows** — every grid-chunked layout's chunk_rows
    must be PROVABLY a multiple of the (8,128) tiling's 128-lane
    minor axis at every budget.py emission site: `chunk_rows_for`'s
    returns and every `StagePlan(...)` chunk argument must reduce to
    lane-aligned arithmetic (literals divisible by 128, `_lane_floor`/
    `_lane_ceil`/`chunk_rows_for` results, min/max/products of
    those).  kernels/budget.py ships lane-aligned in this PR; this
    leg keeps it that way.

Scope: the ref/control-flow legs run on any function with a `*_ref`
parameter (the convention IS the marker, so fixtures and helpers
outside das_tpu/kernels/ are covered too); the dtype leg additionally
sweeps whole modules under a kernels/ directory; the lane legs run on
modules that define `chunk_rows_for` or declare `KERNEL_BUFFERS`.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from das_tpu.analysis.callgraph import callgraph, module_table
from das_tpu.analysis.core import AnalysisContext, Finding, register

LANE_ROWS = 128

_BANNED_DTYPES = frozenset((
    "float64", "complex64", "complex128", "float16",
))

#: callables whose results are lane-aligned by contract
_ALIGNED_CALLS = frozenset((
    "chunk_rows_for", "_lane_floor", "_lane_ceil", "lane_floor", "lane_ceil",
))

#: builtins whose results are static under tracing (taint breakers)
_TAINT_BREAKERS = frozenset(("len", "range", "isinstance", "enumerate"))

_STATIC_ATTRS = frozenset(("shape", "ndim", "dtype"))


def _ref_params(fn: ast.AST) -> Tuple[str, ...]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    return tuple(n for n in names if n.endswith("_ref"))


def _kernel_functions(sf) -> Iterable[Tuple[ast.AST, Tuple[str, ...]]]:
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            refs = _ref_params(node)
            if refs:
                yield node, refs


def _parents(root: ast.AST) -> Dict[int, ast.AST]:
    out: Dict[int, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            out[id(child)] = node
    return out


def _callee_params(ctx, sf, call: ast.Call) -> Optional[List[str]]:
    """Parameter names of a repo-resolvable callee (for checking that a
    forwarded ref lands on a `*_ref` parameter)."""
    q = callgraph(ctx).resolve_call(sf, call, None)
    if q is None:
        return None
    fn = callgraph(ctx).functions[q].node
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args]
    if names and names[0] in ("self", "cls"):
        names = names[1:]
    return names + [p.arg for p in a.kwonlyargs]


# -- ref access discipline ---------------------------------------------------


def _check_refs(ctx, sf, fn, refs) -> Iterable[Finding]:
    parents = _parents(fn)
    nested_params: Set[int] = set()  # param Name nodes of nested defs
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Name) and node.id in refs):
            continue
        if id(node) in nested_params:
            continue
        parent = parents.get(id(node))
        if isinstance(parent, ast.Subscript) and parent.value is node:
            continue
        if isinstance(parent, ast.Call) and node in parent.args:
            params = _callee_params(ctx, sf, parent)
            if params is not None:
                idx = parent.args.index(node)
                if idx < len(params) and params[idx].endswith("_ref"):
                    continue
                yield Finding(
                    "DL011", sf.posix, node.lineno,
                    f"ref `{node.id}` forwarded to a parameter not named "
                    "`*_ref` — the ref naming convention is what keeps "
                    "the access discipline (and KERNEL_BUFFERS) checkable "
                    "through helpers",
                )
                continue
            yield Finding(
                "DL011", sf.posix, node.lineno,
                f"ref `{node.id}` passed to an unresolvable callee — a "
                "raw Ref is a memory space under Mosaic, not an array; "
                "load `{0}[...]` first or forward to a repo-local "
                "`*_ref` parameter".format(node.id),
            )
            continue
        if isinstance(parent, ast.keyword):
            if parent.arg is not None and parent.arg.endswith("_ref"):
                continue
            yield Finding(
                "DL011", sf.posix, node.lineno,
                f"ref `{node.id}` passed as keyword "
                f"`{parent.arg}` (not `*_ref`) — refs may only be "
                "subscripted or forwarded to `*_ref` parameters",
            )
            continue
        yield Finding(
            "DL011", sf.posix, node.lineno,
            f"ref `{node.id}` used outside the subscript discipline — "
            "Mosaic refs must be loaded/stored via `[...]`; aliasing or "
            "wrapping the raw ref diverges between the discharge and "
            "Mosaic lowerings",
        )


# -- python control flow on traced values ------------------------------------


def _is_none_test(test: ast.AST) -> bool:
    return isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
    )


def _tainted_expr(e: ast.AST, tainted: Set[str], refs) -> bool:
    if isinstance(e, ast.Name):
        return e.id in tainted
    if isinstance(e, ast.Subscript):
        base = e.value
        if isinstance(base, ast.Name) and base.id in refs:
            return True
        return _tainted_expr(base, tainted, refs)
    if isinstance(e, ast.Attribute):
        if e.attr in _STATIC_ATTRS:
            return False
        return _tainted_expr(e.value, tainted, refs)
    if isinstance(e, ast.Call):
        fn = e.func
        if isinstance(fn, ast.Name) and fn.id in _TAINT_BREAKERS:
            return False
        if isinstance(fn, ast.Attribute) and _tainted_expr(
            fn.value, tainted, refs
        ):
            return True
        return any(_tainted_expr(a, tainted, refs) for a in e.args) or any(
            _tainted_expr(k.value, tainted, refs) for k in e.keywords
        )
    if isinstance(e, (ast.BinOp,)):
        return (
            _tainted_expr(e.left, tainted, refs)
            or _tainted_expr(e.right, tainted, refs)
        )
    if isinstance(e, ast.BoolOp):
        return any(_tainted_expr(v, tainted, refs) for v in e.values)
    if isinstance(e, ast.Compare):
        return _tainted_expr(e.left, tainted, refs) or any(
            _tainted_expr(c, tainted, refs) for c in e.comparators
        )
    if isinstance(e, ast.UnaryOp):
        return _tainted_expr(e.operand, tainted, refs)
    if isinstance(e, ast.IfExp):
        return (
            _tainted_expr(e.body, tainted, refs)
            or _tainted_expr(e.orelse, tainted, refs)
        )
    if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
        return any(_tainted_expr(v, tainted, refs) for v in e.elts)
    return False


def _target_names(t: ast.AST) -> List[str]:
    if isinstance(t, ast.Name):
        return [t.id]
    if isinstance(t, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in t.elts:
            out.extend(_target_names(e))
        return out
    return []


def _taint_set(fn: ast.AST, refs) -> Set[str]:
    """Names holding ref-derived (traced) values — two passes to settle
    chains across nested defs (the hoisted-prologue closures)."""
    tainted: Set[str] = set()
    for _ in range(3):
        before = len(tainted)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                if _tainted_expr(node.value, tainted, refs):
                    for t in node.targets:
                        tainted.update(_target_names(t))
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if node.value is not None and _tainted_expr(
                    node.value, tainted, refs
                ):
                    tainted.update(_target_names(node.target))
            elif isinstance(node, ast.For):
                if _tainted_expr(node.iter, tainted, refs):
                    tainted.update(_target_names(node.target))
        if len(tainted) == before:
            break
    return tainted


def _check_control_flow(sf, fn, refs) -> Iterable[Finding]:
    tainted = _taint_set(fn, refs)

    def flag(test: ast.AST, what: str, line: int):
        if _is_none_test(test):
            return None
        if _tainted_expr(test, tainted, refs):
            return Finding(
                "DL011", sf.posix, line,
                f"python `{what}` on a traced (ref-derived) value inside "
                "a kernel body — data-dependent python control flow "
                "concretizes under jit and silently diverges between the "
                "discharge and Mosaic lowerings; use jnp.where/@pl.when",
            )
        return None

    for node in ast.walk(fn):
        f = None
        if isinstance(node, ast.If):
            f = flag(node.test, "if", node.lineno)
        elif isinstance(node, ast.While):
            f = flag(node.test, "while", node.lineno)
        elif isinstance(node, ast.IfExp):
            f = flag(node.test, "if-expression", node.lineno)
        elif isinstance(node, ast.Assert):
            f = flag(node.test, "assert", node.lineno)
        elif isinstance(node, ast.For):
            if _tainted_expr(node.iter, tainted, refs):
                f = Finding(
                    "DL011", sf.posix, node.lineno,
                    "python `for` over a traced (ref-derived) value "
                    "inside a kernel body — trip counts must be static",
                )
        if f is not None:
            yield f


# -- dtype sweep -------------------------------------------------------------


def _check_dtypes(sf, root: ast.AST, skip_docstrings: bool) -> Iterable[Finding]:
    doc_ids = set()
    if skip_docstrings:
        for node in ast.walk(root):
            if isinstance(node, (ast.Module, ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.ClassDef)):
                body = getattr(node, "body", [])
                if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant
                ):
                    doc_ids.add(id(body[0].value))
    for node in ast.walk(root):
        name = None
        if isinstance(node, ast.Attribute) and node.attr in _BANNED_DTYPES:
            name = node.attr
        elif isinstance(node, ast.Name) and node.id in _BANNED_DTYPES:
            name = node.id
        elif (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and node.value in _BANNED_DTYPES
            and id(node) not in doc_ids
        ):
            name = node.value
        if name is not None:
            yield Finding(
                "DL011", sf.posix, node.lineno,
                f"dtype `{name}` in kernel code — unpriced by the "
                "kernels/budget.py byte models and unsupported/emulated "
                "under Mosaic (models price int32/int64/bool/float32)",
            )


# -- lane-tiled chunk_rows ---------------------------------------------------


def _module_int_consts(tree: ast.Module) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Constant):
            v = node.value.value
            if isinstance(v, int) and not isinstance(v, bool):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = v
    return out


def _aligned(e: ast.AST, env: Dict[str, bool], consts: Dict[str, int]) -> bool:
    if isinstance(e, ast.Constant):
        return isinstance(e.value, int) and not isinstance(e.value, bool) \
            and e.value % LANE_ROWS == 0
    if isinstance(e, ast.Name):
        if env.get(e.id):
            return True
        v = consts.get(e.id)
        return v is not None and v % LANE_ROWS == 0
    if isinstance(e, ast.Call):
        fn = e.func
        fname = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if fname in _ALIGNED_CALLS:
            return True
        if fname in ("min", "max"):
            return bool(e.args) and all(
                _aligned(a, env, consts) for a in e.args
            )
        return False
    if isinstance(e, ast.BinOp):
        if isinstance(e.op, ast.Mult):
            return _aligned(e.left, env, consts) or _aligned(
                e.right, env, consts
            )
        if isinstance(e.op, (ast.Add, ast.Sub)):
            return _aligned(e.left, env, consts) and _aligned(
                e.right, env, consts
            )
        return False
    if isinstance(e, ast.UnaryOp) and isinstance(e.op, ast.USub):
        return _aligned(e.operand, env, consts)
    if isinstance(e, ast.IfExp):
        return _aligned(e.body, env, consts) and _aligned(
            e.orelse, env, consts
        )
    return False


def _stmt_seq(fn: ast.AST) -> Iterable[ast.stmt]:
    """Statements of a function in source order, descending into
    compound bodies (good enough for the straight-line budget code)."""
    def rec(body):
        for s in body:
            yield s
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(s, attr, None)
                if sub:
                    yield from rec(sub)
    yield from rec(fn.body)


def _check_lane_alignment(sf) -> Iterable[Finding]:
    consts = _module_int_consts(sf.tree)
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        env: Dict[str, bool] = {}
        for stmt in _stmt_seq(node):
            if isinstance(stmt, ast.Assign):
                ok = _aligned(stmt.value, env, consts)
                for name in _target_names(
                    stmt.targets[0] if len(stmt.targets) == 1 else ast.Tuple(
                        elts=list(stmt.targets), ctx=ast.Load()
                    )
                ):
                    env[name] = ok
            elif isinstance(stmt, ast.Return) and node.name == "chunk_rows_for":
                if stmt.value is not None and not _aligned(
                    stmt.value, env, consts
                ):
                    yield Finding(
                        "DL011", sf.posix, stmt.lineno,
                        "chunk_rows_for returns a value not provably a "
                        "multiple of the 128-lane tiling — grid-chunked "
                        "blocks must round to the (8,128) TPU tile "
                        "(ARCHITECTURE §9)",
                    )
            for sub in ast.walk(stmt):
                if (
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Name)
                    and sub.func.id == "StagePlan"
                ):
                    chunk = None
                    if len(sub.args) >= 2:
                        chunk = sub.args[1]
                    for kw in sub.keywords:
                        if kw.arg == "chunk_rows":
                            chunk = kw.value
                    if chunk is not None and not _aligned(chunk, env, consts):
                        yield Finding(
                            "DL011", sf.posix, sub.lineno,
                            "StagePlan chunk_rows emission not provably a "
                            "multiple of the 128-lane tiling — size "
                            "chunks via chunk_rows_for/_lane_floor "
                            "(ARCHITECTURE §9)",
                        )


# -- the rule ----------------------------------------------------------------


def _in_kernels(sf) -> bool:
    return "kernels" in sf.path.parts


@register("DL011", "Mosaic readiness of kernel bodies")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    for sf in ctx.modules():
        module_table(sf)  # prime (also validates the walk on odd files)
        kernel_fns = list(_kernel_functions(sf))
        scanned_ids = set()
        for fn, refs in kernel_fns:
            yield from _check_refs(ctx, sf, fn, refs)
            yield from _check_control_flow(sf, fn, refs)
            if not _in_kernels(sf):
                if id(fn) not in scanned_ids:
                    scanned_ids.add(id(fn))
                    yield from _check_dtypes(sf, fn, skip_docstrings=True)
        if _in_kernels(sf):
            yield from _check_dtypes(sf, sf.tree, skip_docstrings=True)
        if (
            "chunk_rows_for" in module_table(sf).defs
            or any(
                isinstance(n, ast.Assign) and any(
                    getattr(t, "id", None) == "KERNEL_BUFFERS"
                    for t in n.targets
                )
                for n in sf.tree.body
            )
        ):
            yield from _check_lane_alignment(sf)
