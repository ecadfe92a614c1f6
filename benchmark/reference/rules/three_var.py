"""And(Interacts($1,$2), Member($1,$3), Member($2,$3)): every interacting
pair of genes with each process they share, asked of the whole store
(`bench.py three_var_query`; reference scripts/benchmark.py QUERY_1 with
no gene grounded).  A row's stamp is the commit that completed it."""

COLUMNS = (("$1", "Gene"), ("$2", "Gene"), ("$3", "BiologicalProcess"))
KEY = None


def rows(kb, _key=None) -> dict:
    rows = {}
    for a in range(kb.store.n_genes):
        out = kb.out_of(a)
        if not out:
            continue
        mine = kb.procs_of(a)
        for b, s_int in out.items():
            for p, s_b in kb.procs_of(b).items():
                if p in mine:
                    rows[(a, b, p)] = max(s_int, s_b, mine[p])
    return rows
