"""DL001 — no host synchronization on a dispatch path.

Contract (PR 2/3, ARCHITECTURE §10): the serving pipeline's throughput
comes from dispatch being PURELY asynchronous — the coalescer keeps
pipeline_depth batches in flight precisely because dispatch_many
enqueues device programs without paying a host transfer.  One stray
`.item()` / `np.asarray` / `jax.device_get` (or a float()/int()/bool()
coercion, which jax resolves by blocking on the device value) inside a
dispatch half silently serializes the whole window: every query waits
for the device at dispatch time and the depth-N pipeline degrades to
serial without failing a single functional test.  Transfers belong in
settle — `settle_pending` pays exactly one `jax.device_get` per retry
round, which FETCH_COUNTS pins.

Scope (mechanical): function bodies, nested defs included, of
  * functions named `dispatch_many`, `dispatch_pending`, its per-round
    loop `_dispatch_round` and the group hook `dispatch_group`, or
    matching `*_dispatch` (execute_fused_many_dispatch,
    query_many_dispatch, ...);
  * methods named `dispatch` on classes that also define `settle` — the
    _ExecJob / _ShardedExecJob dispatch/settle split; a bare function
    named `dispatch` (query/compiler.py's per-query router) legitimately
    does host work and is NOT scanned;
  * `__init__` of a class that defines `settle` but no `dispatch`
    (_QueryManyJob dispatches at construction).

Flagged constructs: `.item()` / `.tolist()` / `.block_until_ready()` /
`.copy_to_host_async()`, `jax.device_get(...)`, `np.asarray` /
`np.array`, and builtin float()/int()/bool() coercions.  A coercion of
a genuinely host-side value is a legitimate keep: suppress per file or
grandfather it in the baseline with its justification.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Tuple

from das_tpu.analysis.core import AnalysisContext, Finding, attr_chain, register

_BANNED_METHODS = {
    "item", "tolist", "block_until_ready", "copy_to_host_async",
}
_BANNED_CALLS = {
    "jax.device_get", "device_get",
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "onp.asarray", "onp.array",
}
_BANNED_BUILTINS = {"float", "int", "bool"}


def _dispatch_functions(tree: ast.Module) -> List[Tuple[str, ast.AST]]:
    """(qualified name, def node) for every dispatch-path function."""
    out: List[Tuple[str, ast.AST]] = []

    def visit(node: ast.AST, cls: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                visit(child, child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = child.name
                is_dispatch = (
                    name in ("dispatch_many", "dispatch_pending",
                             "_dispatch_round", "dispatch_group")
                    or name.endswith("_dispatch")
                )
                if (
                    name in ("dispatch", "__init__")
                    and cls
                    and isinstance(node, ast.ClassDef)
                ):
                    methods = {
                        m.name for m in node.body
                        if isinstance(m, ast.FunctionDef)
                    }
                    if name == "dispatch":
                        is_dispatch = "settle" in methods
                    else:  # __init__ dispatches when there is no dispatch()
                        is_dispatch = (
                            "settle" in methods and "dispatch" not in methods
                        )
                if is_dispatch:
                    out.append(
                        (f"{cls}.{name}" if cls else name, child)
                    )
                else:
                    visit(child, cls)  # nested defs may still qualify

    visit(tree, "")
    return out


def _banned_in(fn: ast.AST) -> Iterable[Tuple[int, str]]:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _BANNED_METHODS:
            yield node.lineno, f".{func.attr}()"
            continue
        chain = attr_chain(func)
        if chain in _BANNED_CALLS:
            yield node.lineno, f"{chain}()"
        elif (
            isinstance(func, ast.Name)
            and func.id in _BANNED_BUILTINS
            and node.args
        ):
            yield node.lineno, f"{func.id}() coercion"


@register("DL001", "host sync on a dispatch path")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    for sf in ctx.modules():
        for qname, fn in _dispatch_functions(sf.tree):
            for lineno, what in _banned_in(fn):
                yield Finding(
                    "DL001", sf.posix, lineno,
                    f"{what} inside dispatch-path function `{qname}` — "
                    "dispatch must stay transfer-free; host "
                    "synchronization belongs in the settle half",
                )
