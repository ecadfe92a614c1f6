"""And(Member(g,$3), Member($2,$3)): every gene that shares a process
with g (g itself among them), each with that process.  A row's stamp is
the commit that completed it."""

COLUMNS = (("$2", "Gene"), ("$3", "BiologicalProcess"))
KEY = "gene"


def rows(kb, g: int) -> dict:
    rows = {}
    for p, s_p in kb.procs_of(g).items():
        for x, s_x in kb.genes_of(p).items():
            rows[(x, p)] = max(s_p, s_x)
    return rows
