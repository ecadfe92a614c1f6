"""The check that decides `correct` for answers that raced a commit:
an answer is right only if it is the exact set of ONE committed state
between the last commit acknowledged before it was sent and the last one
issued before it was received (so it grows monotonically with commits
and never mixes two states)."""

import pytest

from benchmark.harness.cell import verify_records
from benchmark.harness.spec import Cell
from benchmark.reference import generator, plain

#: shape -> its loaded rule file, as a cell holds them
RULES = Cell("mem-uniform-closed").rules
COLUMNS = RULES["grounded3"].COLUMNS


@pytest.fixture()
def kb():
    return plain.PlainKB(generator.Store(0.002, 11))


def grounded3(kb, g):
    return RULES["grounded3"].rows(kb, g)


def gene_with_rows(kb):
    return next(g for g in range(kb.store.n_genes) if grounded3(kb, g))


def commit(kb, g, v, n=2):
    """n new (Interacts g x) + (Member x p), stamped v."""
    mine = sorted(kb.procs_of(g))
    added, x = 0, 0
    while added < n:
        x += 1
        if x == g or x in kb.out_of(g):
            continue
        p = next((q for q in mine if q not in kb.procs_of(x)), None)
        if p is None:
            continue
        assert kb.add_interacts(g, x, v) and kb.add_member(x, p, v)
        added += 1


def record(kb, shape, g, v, sent, recv, drop=0, extra=None):
    rule = RULES[shape]
    rows = kb.canonical_rows(rule.rows(kb, g), v, rule.COLUMNS)
    rows = rows[drop:] + ([extra] if extra else [])
    return {"c": 0, "i": 0, "shape": shape, "key": g, "ok": True,
            "sent": sent, "recv": recv, "n": len(rows),
            "d": plain.digest(sorted(rows))}


def test_reference_states_grow_with_commits(kb):
    g = gene_with_rows(kb)
    base = kb.canonical_rows(grounded3(kb, g), 0, COLUMNS)
    commit(kb, g, 1)
    commit(kb, g, 2)
    at = [kb.canonical_rows(grounded3(kb, g), v, COLUMNS)
          for v in (0, 1, 2, None)]
    assert at[0] == base and at[3] == at[2]
    assert set(at[0]) < set(at[1]) < set(at[2])
    assert len(at[1]) >= len(base) + 2 and len(at[2]) >= len(at[1]) + 2
    assert kb.counts()[1] == kb.store.counts()[1] + 8


def test_answers_between_send_and_receive_are_accepted(kb):
    g = gene_with_rows(kb)
    commit(kb, g, 1)
    commit(kb, g, 2)
    # commit 1: issued 10.0 acked 10.1; commit 2: issued 20.0 acked 20.1
    acked, issued = [10.1, 20.1], [10.0, 20.0]
    cases = [
        record(kb, "grounded3", g, 0, 1.0, 2.0),      # before any commit
        record(kb, "grounded3", g, 0, 9.0, 10.05),    # raced commit 1: old state
        record(kb, "grounded3", g, 1, 9.0, 10.05),    # raced commit 1: new state
        record(kb, "grounded3", g, 1, 15.0, 16.0),    # after 1, before 2
        record(kb, "grounded3", g, 2, 19.0, 30.0),    # saw commit 2
        record(kb, "shared2", g, 2, 25.0, 26.0),
    ]
    out = verify_records(cases, kb, RULES, acked, issued)
    assert out["wrong"] == [] and out["raced_a_commit"] == 3


@pytest.mark.parametrize("case", [
    "stale_after_ack", "from_the_future", "mixed_states", "row_missing",
    "row_invented"])
def test_wrong_answers_are_caught(kb, case):
    g = gene_with_rows(kb)
    commit(kb, g, 1)
    commit(kb, g, 2)
    acked, issued = [10.1, 20.1], [10.0, 20.0]
    if case == "stale_after_ack":      # commit 1 acknowledged, not seen
        rec = record(kb, "grounded3", g, 0, 11.0, 12.0)
    elif case == "from_the_future":    # commit 2 not yet issued, seen
        rec = record(kb, "grounded3", g, 2, 11.0, 12.0)
    elif case == "mixed_states":       # half of commit 1's rows
        full = kb.canonical_rows(grounded3(kb, g), 1, COLUMNS)
        base = kb.canonical_rows(grounded3(kb, g), 0, COLUMNS)
        new = [r for r in full if r not in base]
        rows = sorted(base + new[:1])
        rec = {"c": 0, "i": 0, "shape": "grounded3", "key": g, "ok": True,
               "sent": 9.0, "recv": 10.2, "n": len(rows),
               "d": plain.digest(rows)}
    elif case == "row_missing":
        rec = record(kb, "shared2", g, 2, 25.0, 26.0, drop=1)
    else:
        rec = record(kb, "shared2", g, 2, 25.0, 26.0,
                     extra="$2=" + "f" * 32 + ",$3=" + "e" * 32)
    out = verify_records([rec], kb, RULES, acked, issued)
    assert len(out["wrong"]) == 1


def test_a_failed_request_is_failed_not_wrong(kb):
    rec = {"c": 0, "i": 0, "shape": "grounded3", "key": 1, "ok": False,
           "sent": 1.0, "recv": 2.0, "err": "DAS-RETRY kind=saturated"}
    out = verify_records([rec], kb, RULES, [], [])
    assert out["wrong"] == [] and len(out["failed"]) == 1


def test_canonical_answer_reads_the_wire_format():
    h2, h3 = "a" * 32, "b" * 32
    msg = "{{'$3': '%s', '$2': '%s'}, {'$2': '%s', '$3': '%s'}}" % (h3, h2, h3, h2)
    assert plain.canonical_answer(msg) == sorted(
        [f"$2={h2},$3={h3}", f"$2={h3},$3={h2}"])
    assert plain.canonical_answer("") == []
    assert plain.canonical_answer("NOT {}") is None
