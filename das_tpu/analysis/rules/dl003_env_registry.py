"""DL003 — every DAS_TPU_* env read maps to the declared registry.

Contract (PR 0..4 accumulation): configuration flags drifted in both
directions — module-local `os.environ.get("DAS_TPU_...")` reads grew
outside DasConfig (DAS_TPU_STAR, DAS_TPU_HOST_COUNT,
DAS_TPU_FINALIZE_VERBOSE, ...) with no single place an operator could
enumerate, and nothing stopped a registered name from losing its last
reader and rotting in the docs.  `ENV_REGISTRY` in core/config.py is
now the one declared set (scripts/gen_env_table.py renders it into
ARCHITECTURE.md §11 so the docs cannot drift either); this rule pins
code <-> registry:

  * every `os.environ.get`/`os.environ[...]`/`os.getenv` read of a `DAS_TPU_*`
    name in the analyzed set must be a key of ENV_REGISTRY;
  * every ENV_REGISTRY key must be read somewhere in the analyzed set,
    unless listed in ENV_DECLARED_EXTERNAL (read outside das_tpu/ —
    e.g. tests/conftest.py's DAS_TPU_TEST_PLATFORM);
  * a registry entry naming a DasConfig field must match a declared
    field of the DasConfig dataclass (same module).

Registry shape (parsed statically, never imported):

    ENV_REGISTRY = {
        "DAS_TPU_PLANNER": ("use_planner", "cost-based planner ..."),
        "DAS_TPU_PLANNER_DP_MAX": (None, "DP clause ceiling ..."),
    }
    ENV_DECLARED_EXTERNAL = ("DAS_TPU_TEST_PLATFORM",)
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Tuple

from das_tpu.analysis.core import (
    AnalysisContext,
    Finding,
    attr_chain,
    const_str,
    module_assign,
    register,
    str_collection,
)

_PREFIX = "DAS_TPU_"


def _find_registry(ctx: AnalysisContext):
    """(posix, line, {name: field-or-None}, external names) or None."""
    for sf in ctx.modules():
        node = module_assign(sf.tree, "ENV_REGISTRY")
        if not isinstance(node, ast.Dict):
            continue
        reg: Dict[str, Optional[str]] = {}
        for k, v in zip(node.keys, node.values):
            name = const_str(k) if k is not None else None
            if name is None:
                continue
            fld = None
            if isinstance(v, ast.Tuple) and v.elts:
                fld = const_str(v.elts[0])
            reg[name] = fld
        ext = str_collection(
            module_assign(sf.tree, "ENV_DECLARED_EXTERNAL")
        ) or ()
        return sf, node.lineno, reg, ext
    return None


def _env_reads(sf) -> Iterable[Tuple[int, str]]:
    for node in ast.walk(sf.tree):
        name = None
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            if chain in (
                "os.environ.get", "os.getenv", "environ.get", "getenv",
                "_os.environ.get", "_os.getenv",
            ) and node.args:
                name = const_str(node.args[0])
        elif isinstance(node, ast.Subscript):
            chain = attr_chain(node.value)
            if chain in ("os.environ", "environ", "_os.environ"):
                name = const_str(node.slice)
        if name is not None and name.startswith(_PREFIX):
            yield node.lineno, name


def _dasconfig_fields(tree: ast.Module) -> Optional[List[str]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "DasConfig":
            return [
                s.target.id
                for s in node.body
                if isinstance(s, ast.AnnAssign)
                and isinstance(s.target, ast.Name)
            ]
    return None


@register("DL003", "DAS_TPU_* env reads vs ENV_REGISTRY")
def check(ctx: AnalysisContext) -> Iterable[Finding]:
    found = _find_registry(ctx)
    reads: List[Tuple[str, int, str]] = []  # posix, line, name
    for sf in ctx.modules():
        for line, name in _env_reads(sf):
            reads.append((sf.posix, line, name))
    if found is None:
        for posix, line, name in reads:
            yield Finding(
                "DL003", posix, line,
                f"env read of {name} but no ENV_REGISTRY in the analyzed "
                "set (core/config.py declares the flag registry)",
            )
        return
    reg_sf, reg_line, registry, external = found
    for posix, line, name in reads:
        if name not in registry:
            yield Finding(
                "DL003", posix, line,
                f"undeclared env var {name} — add it to ENV_REGISTRY "
                f"({reg_sf.short}) so operators can enumerate every flag",
            )
    read_names = {name for _p, _l, name in reads}
    # read-less entries are only provable on the FULL set — a partial
    # (--changed-only) run may simply not include a flag's reader
    for name in registry if not ctx.partial else ():
        if name not in read_names and name not in external:
            yield Finding(
                "DL003", reg_sf.posix, reg_line,
                f"ENV_REGISTRY declares {name} but nothing in the "
                "analyzed set reads it — dead flag (or move it to "
                "ENV_DECLARED_EXTERNAL with its out-of-tree reader)",
            )
    fields = _dasconfig_fields(reg_sf.tree)
    if fields is not None:
        for name, fld in registry.items():
            if fld is not None and fld not in fields:
                yield Finding(
                    "DL003", reg_sf.posix, reg_line,
                    f"ENV_REGISTRY maps {name} to DasConfig.{fld} but "
                    "DasConfig declares no such field",
                )
