"""The open BASELINE.json north-star requirement: the reference's OWN
artifacts run UNCHANGED against the TPU backends through the compat/das
shim (VERDICT r02 item 1).

* /root/reference/scripts/regression.py executes verbatim (subprocess,
  PYTHONPATH at the shim) on BOTH the memory and tensor backends, and the
  two printed outputs are identical after canonical normalization (set
  iteration order and the uncommitted symbol↔value zip inside
  UnorderedAssignment reprs are nondeterministic in the reference too, so
  blocks are compared as canonical multisets).  The host algebra itself is
  proven identical to the actual reference engine by test_differential.py,
  which closes the chain: reference engine == shim/memory == shim/tensor.

* /root/reference/scripts/benchmark.py executes verbatim against a
  persisted bio-ontology checkpoint (DAS_TPU_CHECKPOINT standing in for
  the reference's Mongo/Redis env endpoints), completing all three query
  layouts with matches.
"""

import ast as pyast
import os
import re
import subprocess
import sys

import pytest

from tests.conftest import reference_path

pytestmark = pytest.mark.full  # heavy block: excluded from `pytest -m quick`

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shim_env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/compat:{REPO}"
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _run_reference_script(script, env, timeout=900):
    proc = subprocess.run(
        [sys.executable, reference_path("scripts", script)],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


# -- output normalization ----------------------------------------------------

def _canon_unord(d):
    # UnorderedAssignment has no committed pairing: equal symbol and value
    # multisets mean the SAME assignment, so canonical form drops the zip
    return tuple(sorted(d.keys())), tuple(sorted(d.values()))


def _canon_line(line):
    line = line.strip()
    if not line:
        return None
    m = re.match(r"Ordered = (.*) \| Unordered = \[(.*)\]$", line)
    if m:
        o = m.group(1)
        od = None if o == "None" else tuple(sorted(pyast.literal_eval(o).items()))
        parts = re.findall(r"\*(\{[^}]*\})", m.group(2))
        uns = tuple(sorted(_canon_unord(pyast.literal_eval(p)) for p in parts))
        return ("comp", od, uns)
    if line.startswith("*{"):
        return ("unord", _canon_unord(pyast.literal_eval(line[1:])))
    if line.startswith("{"):
        return ("ord", tuple(sorted(pyast.literal_eval(line).items())))
    if line.startswith("["):  # get_all_nodes handle list — order-free
        return ("list", tuple(sorted(pyast.literal_eval(line))))
    return ("raw", line)


def normalize_regression_output(text):
    blocks, cur = [], []
    for line in text.splitlines():
        if line.startswith("-----") or line.startswith("====="):
            if cur:
                blocks.append(cur)
                cur = []
            continue
        if line.startswith("Matching"):
            if cur:
                blocks.append(cur)
            cur = [("hdr", line.strip())]
            continue
        c = _canon_line(line)
        if c:
            cur.append(c)
    if cur:
        blocks.append(cur)
    return [
        (
            tuple(x for x in b if x[0] == "hdr"),
            tuple(sorted(repr(x) for x in b if x[0] != "hdr")),
        )
        for b in blocks
    ]


# -- tests -------------------------------------------------------------------

@pytest.fixture(scope="module")
def regression_outputs():
    mem = _run_reference_script(
        "regression.py", _shim_env(DAS_TPU_BACKEND="memory")
    )
    tensor = _run_reference_script(
        "regression.py", _shim_env(DAS_TPU_BACKEND="tensor")
    )
    return mem, tensor


def test_reference_regression_runs_unchanged(regression_outputs):
    mem, tensor = regression_outputs
    for out in (mem, tensor):
        assert "Integration tests" in out
        # Concept:human exists and matches (known md5 from the reference)
        assert "af12f10f9ae2002a1607ba0b47ba8407" in out
    n_mem = normalize_regression_output(mem)
    n_tensor = normalize_regression_output(tensor)
    assert len(n_mem) == len(n_tensor) == 56
    for i, (a, b) in enumerate(zip(n_mem, n_tensor)):
        assert a == b, f"block {i} ({a[0]}) differs between memory and tensor"


def test_reference_regression_known_answers(regression_outputs):
    mem, _ = regression_outputs
    blocks = normalize_regression_output(mem)
    by_hdr = {b[0][0][1] if b[0] else "": b[1] for b in blocks}
    # grounded probes
    assert "('raw', 'True')" in by_hdr["Matching <Concept: human>"]
    assert (
        "('raw', 'False')"
        in by_hdr["Matching <Similarity: [<Concept: human>, <Concept: mammal>]>"]
    )
    # all-variable Inheritance scan yields the full 12-row answer set
    inh = by_hdr["Matching <Inheritance: [V1, V2]>"]
    assert sum(1 for x in inh if x.startswith("('ord'")) == 12


@pytest.fixture(scope="module")
def bio_checkpoint(tmp_path_factory):
    from das_tpu.models.bio import build_bio_ontology_atomspace
    from das_tpu.storage import checkpoint

    data, _, _ = build_bio_ontology_atomspace(
        n_genes=60, n_processes=20, members_per_gene=3, n_interactions=50,
        n_reactomes=20, n_uniprots=40,
    )
    path = str(tmp_path_factory.mktemp("bio_ckpt"))
    checkpoint.save(data, path, with_indexes=True)
    return path


def test_reference_benchmark_runs_unchanged(bio_checkpoint):
    out = _run_reference_script(
        "benchmark.py",
        _shim_env(DAS_TPU_BACKEND="tensor", DAS_TPU_CHECKPOINT=bio_checkpoint),
        timeout=1800,
    )
    # three layouts, each printing a BenchmarkResults block
    assert out.count("Average time per query") == 3
    assert out.count("DB backend architecture: COUCHBASE_AND_MONGODB") == 3
    for layout in ("QUERY_1", "QUERY_2", "QUERY_3"):
        assert f"Test layout: {layout}" in out
    # the conjunctive layouts find matches on this KB
    m1 = re.search(r"100 runs \((\d+) matched\)", out)
    assert m1 and int(m1.group(1)) > 0


def test_reference_pattern_matcher_unit_tests_pass(tmp_path):
    """The reference's OWN engine unit-test file (625 LoC of assignment
    and matching assertions, readable-handle fixture) passes byte-for-byte
    against this framework's engine + storage through the shim's
    translation StubDB (compat/das/database/stub_db.py).

    The file is COPIED into tmp_path before running: pytest's prepend
    import mode puts the test file's ancestor (/root/reference) at
    sys.path[0], AHEAD of PYTHONPATH — running it in place would import
    the reference's own das package and verify nothing about this repo.
    The copy's directory contains no das package, so every `das.*` import
    resolves to the shim.  A probe asserts that resolution explicitly."""
    import shutil

    src = reference_path("das", "pattern_matcher", "pattern_matcher_test.py")
    copied = tmp_path / "pattern_matcher_test.py"
    shutil.copyfile(src, copied)
    # probe: the das package under test must be the SHIM, not the reference
    (tmp_path / "conftest.py").write_text(
        "import das, sys\n"
        "assert '/compat/' in das.__file__, f'wrong das: {das.__file__}'\n"
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
            str(copied),
        ],
        capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path),
        env=_shim_env(),
    )
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "7 passed" in proc.stdout


# -- the reference's DB-integration test files (VERDICT r04 item 3) ----------

@pytest.fixture(scope="module")
def animals_checkpoint(tmp_path_factory):
    """The animals KB persisted as a checkpoint: DAS_TPU_CHECKPOINT stands
    in for the pre-populated Mongo/Redis servers the reference's bare
    `DistributedAtomSpace()` construction expects."""
    from das_tpu.ingest.pipeline import load_knowledge_base
    from das_tpu.storage import checkpoint
    from das_tpu.storage.atom_table import AtomSpaceData

    data = AtomSpaceData()
    load_knowledge_base(data, f"{REPO}/data/samples/animals.metta")
    path = str(tmp_path_factory.mktemp("animals_ckpt"))
    checkpoint.save(data, path, with_indexes=True)
    return path


_REFERENCE_DAS_TESTS = {
    # file -> number of test functions upstream (asserted exactly)
    "distributed_atom_space_test.py": 11,   # das/distributed_atom_space_test.py:8-66
    "das_update_test.py": 4,                # das/das_update_test.py:8-192
}


@pytest.mark.parametrize("backend", ["memory", "tensor"])
@pytest.mark.parametrize("fname", sorted(_REFERENCE_DAS_TESTS))
def test_reference_das_integration_tests_pass(
    tmp_path, animals_checkpoint, fname, backend
):
    """The reference's own public-API integration test files run VERBATIM
    (subprocess copy, same sys.path rationale as the pattern_matcher proof
    above) against the animals checkpoint on both in-process backends.
    das_update_test.py additionally commits 10 expressions through an open
    transaction before its checks — the incremental-commit path on the
    tensor backend."""
    import shutil

    src = reference_path("das", fname)
    copied = tmp_path / fname
    shutil.copyfile(src, copied)
    (tmp_path / "conftest.py").write_text(
        "import das, sys\n"
        "assert '/compat/' in das.__file__, f'wrong das: {das.__file__}'\n"
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
            str(copied),
        ],
        capture_output=True, text=True, timeout=900,
        cwd=str(tmp_path),
        env=_shim_env(
            DAS_TPU_BACKEND=backend, DAS_TPU_CHECKPOINT=animals_checkpoint
        ),
    )
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert f"{_REFERENCE_DAS_TESTS[fname]} passed" in proc.stdout
