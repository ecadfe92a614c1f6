"""mesh: slots all_gathered onto every chip as the LEFT side of a join
into a whole-type term (counter `mesh.left_gathered_rows`: per
dispatched mesh program, from the gathered operand's traced shape,
summed over its such joins), per correct answer of the window.  A join
that partitions its left side adds nothing; a plan that gathers the
first join's whole output for the verified join reads tens of millions.
Nothing where the program has no such counter."""


def read(spans, counters, trace, window):
    rows = counters.get("obs.mesh.left_gathered_rows")
    if rows is None or not window.get("answered"):
        return None
    return rows / window["answered"]
