"""Bytes the whole-store conjunction's verified join must move through
HBM: a lower bound from table sizes and the answer's row count, kept
with the benchmark beside `hbm_model` so that no PR that claims a gain
can change it.

three_var = Interacts($1,$2), Member($1,$3), Member($2,$3).  Whatever
order the program joins in and however it joins, the step that brings
the second `Member` clause in has to see every (pair, process) row of
Interacts x Member once, the `Member` table once, and write each kept
row once:

  * left rows: n_genes x mean_out_degree interaction rows (both
    orientations) x members_per_gene, three int32 columns each;
  * the right table: n_genes x members_per_gene `Member` rows of
    `hbm_model.ROW_BYTES` (key + two targets);
  * the kept rows: three int32 columns each.

Sorting, padding to a capacity class, candidates of one variable that
the second one rejects: all the program's choices, left out on purpose;
the share of the roofline says how far they put it from the bound.
"""

from __future__ import annotations

from benchmark.harness import hbm_model

COLUMN_BYTES = 4
LEFT_COLUMNS = OUT_COLUMNS = 3


def left_rows(store: dict) -> float:
    """Rows of Interacts x Member, the side offered to the verified
    join with the one join order that keeps the intermediate smallest."""
    return (float(store["n_genes"]) * float(store["mean_out_degree"])
            * int(store["members_per_gene"]))


def right_rows(store: dict) -> float:
    return float(store["n_genes"]) * int(store["members_per_gene"])


def query_bytes(shape: str, result_rows: float, store: dict) -> float:
    if shape != "three_var":
        raise KeyError(f"the pair-join model has no shape {shape!r}")
    return (left_rows(store) * LEFT_COLUMNS * COLUMN_BYTES
            + right_rows(store) * hbm_model.ROW_BYTES
            + result_rows * OUT_COLUMNS * COLUMN_BYTES)
