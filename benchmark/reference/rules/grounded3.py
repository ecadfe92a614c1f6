"""And(Member(g,$3), Member($2,$3), Interacts(g,$2)): the genes that
interact with g and share a process with it, each with that process.
A row's stamp is the commit that completed it."""

COLUMNS = (("$2", "Gene"), ("$3", "BiologicalProcess"))
KEY = "gene"


def rows(kb, g: int) -> dict:
    mine = kb.procs_of(g)
    rows = {}
    for x, s_int in kb.out_of(g).items():
        for p, s_x in kb.procs_of(x).items():
            if p in mine:
                rows[(x, p)] = max(s_int, s_x, mine[p])
    return rows
