"""Bytes a query of each shape must move through HBM: a lower bound
from table sizes and the answer's row count, kept with the benchmark so
that no PR that claims a gain can change it.

The store keeps links as rows of int32 targets under sorted int64 probe
keys.  Whatever the program does, answering needs at least:

  * one binary search per probe: ceil(log2 N) keys of 8 bytes, N the
    rows of the arity-2 table;
  * the matched rows: 8 bytes of key + 2 x 4 bytes of targets each;
  * the answer written out: 8 bytes per bound variable per row.

grounded3(g) = Member(g,$3), Member($2,$3), Interacts(g,$2): probe g's
memberships (k rows) and g's interactions (d rows, d = the store's mean
out-degree), then one pair probe (x, p) per candidate.
shared2(g) = Member(g,$3), Member($2,$3): probe g's memberships, then
one probe per process and every matched row — the answer itself.

The model ignores capacity padding, sorting and compaction inside the
program on purpose: those are the program's choices, and the share of
the roofline says how far they put it from the bound.
"""

from __future__ import annotations

import math

KEY_BYTES = 8
ROW_BYTES = KEY_BYTES + 2 * 4
OUT_BYTES_PER_VAR = 8


def _probe(n_rows: int) -> int:
    return math.ceil(math.log2(max(2, n_rows))) * KEY_BYTES


def query_bytes(shape: str, result_rows: int, store: dict) -> float:
    """`store`: {"links": rows of the link tables, "members_per_gene": k,
    "mean_out_degree": d}."""
    n = int(store["links"])
    k = int(store["members_per_gene"])
    d = float(store.get("mean_out_degree", 1.0))
    out = result_rows * 2 * OUT_BYTES_PER_VAR
    if shape == "grounded3":
        probes = 2 + k * d
        rows = k + d + result_rows
    elif shape == "shared2":
        probes = 1 + k
        rows = k + result_rows
    else:
        raise KeyError(f"the bytes model has no shape {shape!r}")
    return probes * _probe(n) + rows * ROW_BYTES + out
