"""Device mesh helpers.

The reference's distribution substrate is a 3-node Redis cluster sharding
the index keyspace by hash slot (SURVEY.md §2.10 P1).  Here the substrate
is a `jax.sharding.Mesh`: atom-table rows are partitioned over the mesh
axis, probes run shard-local under `shard_map`, and fan-in happens with
XLA collectives over ICI (`all_gather` / `psum`) instead of RESP/TCP
round-trips.  Multi-host pods extend the same mesh over DCN via
`jax.distributed.initialize` — no separate communication backend."""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

SHARD_AXIS = "shards"

#: THE declared set of collective call sites (daslint rule DL009 —
#: shard_map collective discipline): every XLA collective call
#: (all_gather / all_to_all / psum / pmax / pmin / ppermute /
#: psum_scatter) in das_tpu/ must live inside one of these
#: "module.qualname" scopes — mesh helpers whose collective use is the
#: point.  The rule pins both directions: an undeclared collective call fails lint,
#: and so does a declared scope that no longer contains one.
COLLECTIVE_SITES = (
    "fused_sharded._repartition",
    "fused_sharded._gather_packed",
    "fused_sharded._global_sum",
    "fused_sharded._worst_shard",
    "sharded_db.ShardedDB._join",
    "sharded_db.ShardedDB._anti_join",
    "sharded_tree.ShardedTreeOps._gather_table",
    "sharded_tree.ShardedTreeOps._replicate_fn",
)


def make_mesh(n_devices: Optional[int] = None, axis_name: str = SHARD_AXIS) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"Requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))


def table_program(mesh: Mesh, fn, n_in: int, n_out: int, replicated_in=(),
                  replicated_out: bool = False, check_vma: bool = True):
    """ONE compiled program of a mesh table operation: `fn` under
    `shard_map` over operands sharded on their leading axis (replicated
    where `replicated_in` says; every output replicated with
    `replicated_out`), jitted whole.  A bare `shard_map` is not a
    compiled program: its body dispatches primitive by primitive on
    every call.  The caller keeps the result under the statics `fn`
    closes over (`ShardedTreeOps._fn_cache`, `ShardedDB._staged_programs`)."""
    spec = PartitionSpec(SHARD_AXIS)
    in_specs = tuple(
        PartitionSpec() if i in replicated_in else spec for i in range(n_in)
    )
    out_spec = PartitionSpec() if replicated_out else spec
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=in_specs,
        out_specs=(out_spec,) * n_out if n_out > 1 else out_spec,
        check_vma=check_vma,
    ))


def row_sharding(mesh: Mesh, axis_name: str = SHARD_AXIS) -> NamedSharding:
    """Shard the leading (shard-stack) dimension over the mesh."""
    return NamedSharding(mesh, PartitionSpec(axis_name))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def multihost_initialize(**kwargs) -> None:
    """Join a multi-host pod (DCN).  Thin veneer over
    `jax.distributed.initialize` so callers stay backend-agnostic."""
    jax.distributed.initialize(**kwargs)
