"""Bytes the whole-store conjunction's verified join must send between
chips when BOTH its sides live row-partitioned over S shards: a lower
bound kept with the benchmark, beside `ici_model` and `pair_join_model`,
so that no PR that claims a gain can change it.

three_var = Interacts($1,$2), Member($1,$3), Member($2,$3).  The step
that brings the second `Member` clause in joins on two variables; its
left rows (`pair_join_model.left_rows`: Interacts x Member, three int32
columns) lie where the first join wrote them and its right rows
(`pair_join_model.right_rows`: the `Member` table, `hbm_model.ROW_BYTES`
a row) where the store dealt them, neither by the join's key.  A pair
can only be verified on one chip, so of the rows that take part the
share that does not already live there, (S-1)/S, crosses; each row
crosses ONCE.

Left out on purpose, as in `ici_model`: capacity padding, the stats
reductions, a side gathered onto every chip where one copy would do,
and the first join's own traffic (its left side is the small one).
"""

from __future__ import annotations

from benchmark.harness import hbm_model, pair_join_model

LEFT_ROW_BYTES = pair_join_model.LEFT_COLUMNS * pair_join_model.COLUMN_BYTES


def query_bytes(shape: str, store: dict, n_shards: int) -> float:
    """Least bytes one query's verified join sends between chips on
    `n_shards` shards, all chips together."""
    if shape != "three_var":
        raise KeyError(f"the join interconnect model has no shape {shape!r}")
    if n_shards < 2:
        return 0.0
    share = (n_shards - 1) / n_shards
    return share * (pair_join_model.left_rows(store) * LEFT_ROW_BYTES
                    + pair_join_model.right_rows(store) * hbm_model.ROW_BYTES)
