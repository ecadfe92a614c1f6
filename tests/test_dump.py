"""Store export/import interop (VERDICT r04 item 5).

The dump files must be byte-identical to what the reference's `mongodump`
script would export for the same store: one `Expression.to_dict()` JSON
document per line (expression.py:25-53), C-locale sorted per collection
(mongodump:1-8 pipes mongoexport through sort(1)).  The differential
oracle below builds every expected line with the REFERENCE'S OWN
`das.expression.Expression.to_dict` (imported from /root/reference, pure
module) and compares whole files.

The loader proves the reverse direction: a dump — including a
reference-produced one, which lacks the typedef designator names —
reconstructs a store whose re-dump is byte-identical (every hash
re-derived through the parser, so corruption cannot pass).
"""

import importlib.util
import json
import os
import sys

import pytest

from das_tpu.convert import dump as dump_mod
from das_tpu.ingest.pipeline import load_knowledge_base
from das_tpu.query.ast import Link, Node, PatternMatchingAnswer, Variable
from das_tpu.storage.atom_table import AtomSpaceData
from das_tpu.storage.memory_db import MemoryDB
from tests.conftest import reference_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ANIMALS = f"{REPO}/data/samples/animals.metta"


@pytest.fixture(scope="module")
def animals_data():
    return load_knowledge_base(AtomSpaceData(), ANIMALS)


def _reference_expression_cls():
    """Import the reference's pure das/expression.py WITHOUT putting
    /root/reference on sys.path (which would shadow the compat shim)."""
    spec = importlib.util.spec_from_file_location(
        "_ref_expression", reference_path("das", "expression.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Expression


def test_dump_matches_reference_to_dict_byte_for_byte(animals_data, tmp_path):
    """Every dump line equals json of the REFERENCE Expression.to_dict for
    the same atom — field names, field ORDER, and bool rendering included."""
    RefExpression = _reference_expression_cls()
    prefix = str(tmp_path / "animals")
    written = dump_mod.dump_store(animals_data, prefix)
    assert sorted(written) == [f"{prefix}.atom_types", f"{prefix}.links_2",
                               f"{prefix}.nodes"]

    expected = {"nodes": [], "atom_types": [], "links_2": []}
    for handle, rec in animals_data.nodes.items():
        e = RefExpression(
            terminal_name=rec.name, named_type=rec.named_type,
            composite_type_hash=rec.named_type_hash, hash_code=handle,
        )
        expected["nodes"].append(json.dumps(e.to_dict(), separators=(",", ":")))
    for handle, rec in animals_data.typedefs.items():
        e = RefExpression(
            typedef_name=rec.name, typedef_name_hash=rec.name_hash,
            composite_type_hash=rec.composite_type_hash, hash_code=handle,
        )
        expected["atom_types"].append(
            json.dumps(e.to_dict(), separators=(",", ":"))
        )
    for handle, rec in animals_data.links.items():
        e = RefExpression(
            toplevel=rec.is_toplevel, named_type=rec.named_type,
            named_type_hash=rec.named_type_hash,
            composite_type=rec.composite_type,
            composite_type_hash=rec.composite_type_hash,
            elements=list(rec.elements), hash_code=handle,
        )
        expected["links_2"].append(
            json.dumps(e.to_dict(), separators=(",", ":"))
        )

    for name, lines in expected.items():
        with open(f"{prefix}.{name}") as f:
            got = f.read()
        assert got == "\n".join(sorted(lines)) + "\n", f"{name} differs"


def test_dump_load_round_trip_byte_identical(animals_data, tmp_path):
    prefix = str(tmp_path / "animals")
    dump_mod.dump_store(animals_data, prefix)
    reloaded = dump_mod.load_dump(prefix)
    assert reloaded.count_atoms() == animals_data.count_atoms() == (14, 26)
    prefix2 = str(tmp_path / "reloaded")
    dump_mod.dump_store(reloaded, prefix2)
    for name in ("nodes", "atom_types", "links_2"):
        with open(f"{prefix}.{name}") as a, open(f"{prefix2}.{name}") as b:
            assert a.read() == b.read(), f"{name} changed across round trip"


def test_reference_style_dump_loads_without_designators(animals_data, tmp_path):
    """A reference-produced dump carries no typedef designator names; the
    loader recovers them by exact hash check against _id."""
    prefix = str(tmp_path / "animals")
    dump_mod.dump_store(animals_data, prefix)
    text = dump_mod.dump_to_metta(prefix)
    # the recovered typedefs land as (: Name Type) lines
    assert "(: Concept Type)" in text
    assert "(: Similarity Type)" in text
    assert "(: Inheritance Type)" in text
    assert '(: "human" Concept)' in text


def test_loaded_dump_answers_queries(animals_data, tmp_path):
    prefix = str(tmp_path / "animals")
    dump_mod.dump_store(animals_data, prefix)
    db = MemoryDB(dump_mod.load_dump(prefix))
    q = Link(
        "Inheritance",
        [Variable("V1"), Node("Concept", "mammal")],
        True,
    )
    answer = PatternMatchingAnswer()
    assert q.matched(db, answer)
    assert len(answer.assignments) == 4  # human, monkey, chimp, rhino


def test_nested_and_high_arity_links_round_trip(tmp_path):
    """keys split (arity > 2) and non-toplevel sub-link rendering."""
    from das_tpu.storage.atom_table import load_metta_text

    text = (
        "(: List Type)\n"
        "(: Concept Type)\n"
        '(: "a" Concept)\n'
        '(: "b" Concept)\n'
        '(: "c" Concept)\n'
        '(List "a" "b" "c")\n'
        '(List (List "a" "b" "c") "c")\n'
    )
    data = load_metta_text(text)
    prefix = str(tmp_path / "nested")
    written = dump_mod.dump_store(data, prefix)
    assert f"{prefix}.links_n" in written and f"{prefix}.links_2" in written
    with open(f"{prefix}.links_n") as f:
        (line,) = [ln for ln in f.read().splitlines() if ln]
    doc = json.loads(line)
    assert len(doc["keys"]) == 3 and "key_0" not in doc
    reloaded = dump_mod.load_dump(prefix)
    assert reloaded.count_atoms() == data.count_atoms()
    prefix2 = str(tmp_path / "nested2")
    dump_mod.dump_store(reloaded, prefix2)
    for name in ("nodes", "atom_types", "links_2", "links_n"):
        with open(f"{prefix}.{name}") as a, open(f"{prefix2}.{name}") as b:
            assert a.read() == b.read()


def test_symbol_element_links_round_trip(tmp_path):
    """A link whose element is a bare SYMBOL (typedef hash) renders
    unquoted and round-trips (code-review r5 finding 1)."""
    from das_tpu.storage.atom_table import load_metta_text

    text = (
        "(: Concept Type)\n"
        "(: Eval Type)\n"
        '(: "x" Concept)\n'
        '(Eval Concept "x")\n'
    )
    data = load_metta_text(text)
    prefix = str(tmp_path / "sym")
    dump_mod.dump_store(data, prefix)
    reconstructed = dump_mod.dump_to_metta(prefix)
    assert '(Eval Concept "x")' in reconstructed
    reloaded = dump_mod.load_dump(prefix)
    assert set(reloaded.links) == set(data.links)
    prefix2 = str(tmp_path / "sym2")
    dump_mod.dump_store(reloaded, prefix2)
    for name in ("nodes", "atom_types", "links_2"):
        with open(f"{prefix}.{name}") as a, open(f"{prefix2}.{name}") as b:
            assert a.read() == b.read()


def test_same_name_two_types_fails_loudly(tmp_path):
    """Canonical MeTTa text cannot express one terminal name under two
    types; the loader must refuse rather than silently collapse
    (code-review r5 finding 2)."""
    from das_tpu.storage.atom_table import load_metta_text

    data = load_metta_text(
        "(: Concept Type)\n(: Number Type)\n(: Rel Type)\n"
        '(: "x" Concept)\n(Rel "x" "x")\n'
    )
    # second store contributes the same name under ANOTHER type
    load_metta_text('(: Number Type)\n(: Rel Type)\n(: "x" Number)\n(Rel "x" "x")\n', data)
    assert len(data.nodes) == 2
    prefix = str(tmp_path / "dup")
    dump_mod.dump_store(data, prefix)
    with pytest.raises(ValueError, match="does not reconstruct faithfully"):
        dump_mod.load_dump(prefix)


def test_missing_prefix_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no dump files"):
        dump_mod.load_dump(str(tmp_path / "no_such_prefix"))


def test_non_ascii_and_html_chars_escape_like_mongoexport(tmp_path):
    """Go's encoding/json (mongoexport) writes raw UTF-8 but HTML-escapes
    < > & — our lines must match byte-for-byte (code-review r5)."""
    from das_tpu.storage.atom_table import load_metta_text

    data = load_metta_text(
        '(: Concept Type)\n(: Rel Type)\n'
        '(: "café" Concept)\n(: "a<b&c" Concept)\n'
        '(Rel "café" "a<b&c")\n'
    )
    prefix = str(tmp_path / "uni")
    dump_mod.dump_store(data, prefix)
    raw = open(f"{prefix}.nodes", "rb").read().decode("utf-8")
    assert "café" in raw            # raw UTF-8, not é
    assert "\\u00e9" not in raw
    assert "a\\u003cb\\u0026c" in raw  # HTML chars escaped Go-style
    reloaded = dump_mod.load_dump(prefix)
    assert set(reloaded.nodes) == set(data.nodes)
    assert set(reloaded.links) == set(data.links)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_randomized_store_round_trip(seed, tmp_path):
    """Property: any store built from generated MeTTa (random types,
    names with spaces/unicode, arities 1-4, nested links, duplicate
    expressions) dumps and reloads byte-identically."""
    import random

    rng = random.Random(seed)
    types = [f"T{i}" for i in range(rng.randint(2, 5))]
    names = [
        rng.choice(["n", "x y", "café", "a.b", "N0"]) + str(i)
        for i in range(rng.randint(3, 10))
    ]
    lines = [f"(: {t} Type)" for t in types]
    decls = [(n, rng.choice(types)) for n in names]
    lines += [f'(: "{n}" {t})' for n, t in decls]
    def term():
        return f'"{rng.choice(names)}"'
    exprs = []
    for _ in range(rng.randint(4, 15)):
        arity = rng.randint(1, 4)
        elems = [term() for _ in range(arity)]
        if exprs and rng.random() < 0.4:
            elems[rng.randrange(arity)] = rng.choice(exprs)
        expr = f"({rng.choice(types)} {' '.join(elems)})"
        exprs.append(expr)
        lines.append(expr)
    if exprs:
        lines.append(rng.choice(exprs))  # duplicate toplevel dedups

    from das_tpu.storage.atom_table import load_metta_text

    data = load_metta_text("\n".join(lines) + "\n")
    p1 = str(tmp_path / "a")
    dump_mod.dump_store(data, p1)
    reloaded = dump_mod.load_dump(p1)
    assert reloaded.count_atoms() == data.count_atoms()
    p2 = str(tmp_path / "b")
    written2 = dump_mod.dump_store(reloaded, p2)
    for path2 in written2:
        path1 = p1 + path2[len(p2):]
        with open(path1) as a, open(path2) as b:
            assert a.read() == b.read(), f"{path2} diverged (seed {seed})"
