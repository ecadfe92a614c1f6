"""Bytes a query of each shape must send between chips when the link
rows are partitioned over S shards: a lower bound kept with the
benchmark, beside `hbm_model`, so that no PR that claims a gain can
change it.

Every link row lives on exactly one shard, dealt there without regard
to its content.  The rows a query matches (`hbm_model` counts them: the
key's memberships, its interactions, the rows of the answer) therefore
lie on all shards alike, and a join brings rows of different clauses
together on one chip: of the rows that take part, the share that does
not already live where it is needed is (S-1)/S.  Each such row crosses
once, as `hbm_model.ROW_BYTES` (its key and two targets).

The model leaves out what the program adds on purpose: capacity
padding, the stats reductions, a table broadcast to every shard where
one copy would do.  The share of the interconnect roofline says how far
those put it from the bound.
"""

from __future__ import annotations

from benchmark.harness import hbm_model


def matched_rows(shape: str, result_rows: int, store: dict) -> float:
    """The link rows `hbm_model.query_bytes` counts for one query."""
    k = int(store["members_per_gene"])
    d = float(store.get("mean_out_degree", 1.0))
    if shape == "grounded3":
        return k + d + result_rows
    if shape == "shared2":
        return k + result_rows
    raise KeyError(f"the interconnect model has no shape {shape!r}")


def query_bytes(shape: str, result_rows: int, store: dict,
                n_shards: int) -> float:
    """Least bytes one query sends between chips on `n_shards` shards."""
    if n_shards < 2:
        return 0.0
    share = (n_shards - 1) / n_shards
    return share * matched_rows(shape, result_rows, store) * hbm_model.ROW_BYTES
