"""das_tpu — TPU-native Distributed AtomSpace.

A knowledge-hypergraph store + conjunctive pattern-matching query engine
with the capabilities of the reference DAS (tanksha/das), re-designed for
TPU: the AtomSpace lives as device-resident int32/int64 tensors (row-id
link tables, sorted probe indexes, incoming-set CSR) and queries execute as
batched searchsorted range probes + vectorized binding-table joins, sharded
over a `jax.sharding.Mesh`.  See SURVEY.md for the reference analysis.
"""

import os

import jax

# Device handles and probe keys are int64 (md5-derived); enable wide ints.
# All kernels use explicit dtypes, so this does not change float behavior
# for user code that follows JAX's explicit-dtype conventions.
jax.config.update("jax_enable_x64", True)

_compile_cache_checked = False


def cache_root():
    """Where this checkout keeps what it learns across processes — the
    persistent XLA cache (`xla/` below it) and the CapStore's learned
    capacities (query/fused.py): `JAX_COMPILATION_CACHE_DIR` when the
    environment places the cache, else `<checkout>/.jax_cache` (a fixed
    path: the directory is part of the cache key, so one that moved
    would never hit).  None when DAS_TPU_XLA_CACHE=0 turns both off."""
    if os.environ.get("DAS_TPU_XLA_CACHE") == "0":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )


def compile_cache_dir():
    """The persistent XLA cache directory in effect (None = off)."""
    return jax.config.jax_compilation_cache_dir


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache: fused query programs are large
    (every probe/join/anti-join of a plan shape in one executable) and a
    cold TPU compile takes seconds to tens of seconds; caching across
    processes makes service restarts and repeated runs start warm.

    Called lazily at first device-table construction, when the backend is
    known: accelerator platforms only — XLA:CPU AOT results are
    machine-feature sensitive (reloading across feature-detection
    differences risks SIGILL) and CPU compiles are cheap anyway.  With
    JAX_COMPILATION_CACHE_DIR set, JAX's own handling of it stands and
    nothing here sets a directory; otherwise the cache lives under
    `cache_root()`.  DAS_TPU_XLA_CACHE=0 disables it."""
    global _compile_cache_checked
    if _compile_cache_checked:
        return
    _compile_cache_checked = True
    root = cache_root()
    if root is None or os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.devices()[0].platform == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", os.path.join(root, "xla"))


__version__ = "0.1.0"

from das_tpu.core.config import DasConfig  # noqa: E402,F401
