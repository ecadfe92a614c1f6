"""chip_smoke.py's phases on the CPU at --scale 0.002, and the
compile-cache placement it reports.

The smoke itself decides `ok` only on a TPU; here its control flow, its
plain-set reference and its route proof run on the CPU backend, and the
device gate must refuse."""

import contextlib
import io
import json
import os
import types

import jax
import pytest

import chip_smoke
import das_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def rehearsal():
    """One run of `chip_smoke.main` at the rehearsal scale: (exit code,
    {phase: record}, raw stdout)."""
    from das_tpu.query import compiler

    # the smoke reports the PROCESS's route counts (its own process is
    # fresh); under xdist this one has run other files' queries already
    compiler.reset_route_counts()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = chip_smoke.main(["--scale", "0.002", "--seed", "0"])
    lines = [json.loads(line) for line in out.getvalue().splitlines()]
    return rc, {rec.get("phase"): rec for rec in lines}, out.getvalue()


def test_gate_refuses_ok_without_a_tpu(rehearsal):
    rc, phases, raw = rehearsal
    assert rc != 0
    assert '"ok"' not in raw
    assert phases["gate"]["device"]["platform"] == "cpu"
    # every phase ran before the gate refused
    assert {"serve", "store", "queries", "counts", "commit",
            "counters"} <= set(phases)


def test_answers_equal_the_plain_reference(rehearsal):
    _rc, phases, _raw = rehearsal
    results = phases["queries"]["results"]
    # 8 grounded + 8 repeats + all-variable join + DSL tree + nested tree
    assert len(results) == 2 * chip_smoke.N_GROUNDED + 3
    assert all(r["equal"] for r in results)
    assert any(r["rows"] > 0 for r in results)
    store = phases["store"]
    assert store["count_rpc"] == str((store["nodes"], store["links"]))
    assert phases["commit"]["rows_after"] >= phases["commit"]["rows_before"] + 5


def test_route_proof(rehearsal):
    _rc, phases, _raw = rehearsal
    q = phases["queries"]
    assert q["route_delta"].get("host", 0) == 0
    assert q["route_delta"].get("staged", 0) == 0
    assert q["route_delta"]["fused"] == 2 * chip_smoke.N_GROUNDED + 1
    assert q["route_delta"]["fused_tree"] == 2
    assert q["dispatch_delta"]["fused"] >= chip_smoke.N_GROUNDED + 1
    assert q["result_cache_hits"] >= chip_smoke.N_GROUNDED
    # only the Or tree reaches the per-query dispatcher, by design
    assert q["per_query_dispatcher"] == ["Or"]
    assert phases["counters"]["route_counts"]["host"] == 0


def test_no_accelerator_refuses_before_building_at_real_scale(capsys):
    assert chip_smoke.main(["--scale", "0.1"]) == chip_smoke.EXIT_NO_ACCELERATOR
    captured = capsys.readouterr()
    assert captured.out == ""            # no line that could be read as a result
    assert "no accelerator" in captured.err


def test_a_failed_phase_fails_the_run(monkeypatch, capsys):
    def broken(_smoke):
        raise chip_smoke.SmokeFailure("phase made to fail")

    monkeypatch.setattr(chip_smoke, "phase_store", broken)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.main(["--scale", "0.002"])
    assert '"ok"' not in capsys.readouterr().out


def test_parse_answer_and_plain_handles():
    neg, rows = chip_smoke.parse_answer(
        "NOT {{'$1': '" + "a" * 32 + "', '$2': '" + "b" * 32 + "'}}"
    )
    assert neg and rows == {frozenset({("$1", "a" * 32), ("$2", "b" * 32)})}
    assert chip_smoke.parse_answer("") == (False, set())
    # the plain reference's md5 handle is the store's own
    from das_tpu.core.hashing import ExpressionHasher

    assert chip_smoke.handle("Gene", "GENE:0000001") == (
        ExpressionHasher.terminal_hash("Gene", "GENE:0000001")
    )


# -- compile-cache placement ------------------------------------------------


@pytest.fixture
def cache_updates(monkeypatch):
    """enable_compile_cache() as a TPU process would run it: returns the
    jax.config.update calls it made."""
    calls = []
    monkeypatch.setattr(das_tpu, "_compile_cache_checked", False)
    monkeypatch.setattr(
        das_tpu.jax, "devices",
        lambda *a, **k: [types.SimpleNamespace(platform="tpu")],
    )
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_compile_cache_honours_jax_compilation_cache_dir(
    cache_updates, monkeypatch, tmp_path
):
    monkeypatch.delenv("DAS_TPU_XLA_CACHE", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    das_tpu.enable_compile_cache()
    assert cache_updates == []          # JAX's own handling stands
    assert das_tpu.cache_root() == str(tmp_path)


def test_compile_cache_lands_inside_the_checkout(cache_updates, monkeypatch):
    from das_tpu.query.fused import CapStore

    monkeypatch.delenv("DAS_TPU_XLA_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    das_tpu.enable_compile_cache()
    root = os.path.join(REPO, ".jax_cache")
    assert cache_updates == [
        ("jax_compilation_cache_dir", os.path.join(root, "xla"))
    ]
    assert das_tpu.cache_root() == root
    assert CapStore("greedy").path == os.path.join(root, "caps_greedy.json")
    home = os.path.expanduser("~")
    assert not root.startswith(os.path.join(home, ".cache"))


def test_compile_cache_off_switch(cache_updates, monkeypatch):
    from das_tpu.query.fused import CapStore

    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
    das_tpu.enable_compile_cache()
    assert cache_updates == [] and das_tpu.cache_root() is None
    assert CapStore("greedy").path is None
