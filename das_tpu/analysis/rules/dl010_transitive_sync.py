"""DL010 — transitive host sync on a dispatch path (DL001, call-graph
edition).

Contract (ISSUE 11 tentpole): DL001 bans host synchronization inside
the dispatch halves SYNTACTICALLY — which a one-line refactor escapes:
move the `.item()` into a helper and the dispatch body is clean while
every query still waits for the device at dispatch time, the depth-N
pipeline silently degrades to serial, and no functional test fails
(the silent-serialization failure mode tensor-runtime query engines
live or die on).  This rule runs the same dispatch-root discovery as
DL001 and then FOLLOWS repo-local calls (analysis/callgraph.py):
a dispatch root reaching `jax.device_get` / `.item()` / `.tolist()` /
`.block_until_ready()` / `.copy_to_host_async()` / `np.asarray` /
`np.array` through ANY chain of resolvable helpers fires, with the
offending call path rendered in the finding.

Scope notes:

  * depth >= 1 only — the root's own direct constructs are DL001's
    findings; reporting them twice would just double the baseline;
  * the builtin float()/int()/bool() coercions DL001 flags directly
    are NOT propagated: transitively, "some helper coerces an int"
    is almost always host arithmetic (capacity math, env parsing),
    and a rule that cries wolf gets suppressed.  The unambiguous
    transfer primitives propagate; the weak heuristic stays local;
  * resolution under-approximates (parameters holding callables and
    unknown attribute chains don't resolve — see callgraph.py), so a
    clean verdict is "no REACHABLE sync", not a proof.  What it does
    report is a real dispatch->transfer path.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Tuple

from das_tpu.analysis.callgraph import callgraph
from das_tpu.analysis.core import AnalysisContext, Finding, attr_chain, register
from das_tpu.analysis.rules.dl001_host_sync import _dispatch_functions

#: the unambiguous host-transfer constructs that propagate through
#: calls (DL001's set minus the weak builtin-coercion heuristic)
_SYNC_METHODS = {
    "item", "tolist", "block_until_ready", "copy_to_host_async",
}
_SYNC_CALLS = {
    "jax.device_get", "device_get",
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "onp.asarray", "onp.array",
}


def _direct_syncs(fn: ast.AST) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _SYNC_METHODS:
            out.append((node.lineno, f".{func.attr}()"))
            continue
        chain = attr_chain(func)
        if chain in _SYNC_CALLS:
            out.append((node.lineno, f"{chain}()"))
    return out


def _render_path(root: str, path) -> str:
    """`dispatch -> helper_a -> helper_b` with the short name of each
    hop (qnames carry full modules; the file is in the finding head)."""
    hops = [root] + [q.split("::", 1)[1] for _line, q in path]
    return " -> ".join(hops)


@register("DL010", "transitive host sync on a dispatch path")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    graph = callgraph(ctx)
    for sf in ctx.modules():
        for qname, fn in _dispatch_functions(sf.tree):
            cls = qname.split(".")[0] if "." in qname else None
            for info, path in graph.walk(sf, fn, cls):
                for line, what in _direct_syncs(info.node):
                    yield Finding(
                        "DL010", sf.posix, path[0][0],
                        f"dispatch path `{qname}` reaches {what} at "
                        f"{info.sf.short}:{line} via "
                        f"`{_render_path(qname, path)}` — dispatch must "
                        "stay transfer-free through every helper; host "
                        "synchronization belongs in the settle half",
                    )
