"""daslint suite (marker `lint`, standalone: ops/pytests.sh lint).

Pins, in order of load-bearing-ness:
  * the analyzer runs CLEAN over das_tpu/ (baseline-grandfathered
    findings allowed; the baseline is currently empty) — the invariant
    contracts of ARCHITECTURE §11 hold on the committed tree;
  * each rule still FIRES on its known-bad fixture and stays quiet on
    the known-good one (tests/lint_fixtures/) — a refactor of the
    analyzer cannot silently lobotomize a rule;
  * re-introducing the two historical bug classes — deleting a
    plan-signature field that the builder reads (the PR-4 class)
    and counting into an undeclared counter key — is caught on REAL
    source, by mutating copies of query/fused.py / query/compiler.py;
  * the CLI contract (`python -m das_tpu.analysis`): exit 0 clean,
    1 on findings and on stale baseline entries, plus suppression and
    baseline mechanics;
  * the counter registries and generated env table stay in sync (the
    registry pin below is also DL004's "referenced by at least one
    test" witness for the cold-path keys the behavior suites don't
    exercise: staged, tree).
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from das_tpu.analysis import run_analysis
from das_tpu.analysis.core import apply_baseline, iter_rules, load_baseline

pytestmark = pytest.mark.lint

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"
RULES = (
    "DL001", "DL002", "DL003", "DL004", "DL006", "DL007", "DL008",
    "DL009", "DL010", "DL012", "DL013", "DL014", "DL015", "DL016",
    "DL017",
)


# -- the tentpole pin: the committed tree honors every contract ----------


def test_tree_is_clean():
    findings = run_analysis(
        [REPO / "das_tpu"], tests_dir=REPO / "tests"
    )
    baseline = load_baseline(REPO / "daslint.baseline.json")
    new, _kept, stale = apply_baseline(findings, baseline)
    assert not new, "new daslint findings:\n" + "\n".join(
        f.render() for f in new
    )
    assert not stale, "stale baseline entries: " + str(
        [(b.rule, b.path) for b in stale]
    )


def test_all_rules_registered():
    assert [rid for rid, _ in iter_rules()] == list(RULES)


# -- per-rule fixture corpus ---------------------------------------------


@pytest.mark.parametrize("rule", RULES)
def test_bad_fixture_trips(rule):
    path = FIXTURES / f"{rule.lower()}_bad.py"
    findings = run_analysis([path], rules=[rule])
    assert findings, f"{path.name} tripped nothing for {rule}"
    assert all(f.rule == rule for f in findings)


@pytest.mark.parametrize("rule", RULES)
def test_good_fixture_clean(rule):
    path = FIXTURES / f"{rule.lower()}_good.py"
    findings = run_analysis([path], rules=[rule])
    assert not findings, "\n".join(f.render() for f in findings)


def test_fixture_messages_name_the_contract():
    """Spot-pin that the findings explain the hazard, not just point."""
    f1 = run_analysis([FIXTURES / "dl001_bad.py"], rules=["DL001"])
    assert any("transfer-free" in f.message for f in f1)
    f2 = run_analysis([FIXTURES / "dl002_bad.py"], rules=["DL002"])
    assert any("index_joins" in f.message for f in f2)


# -- regression: re-introduce the historical bug classes on REAL code ----


def test_dl002_catches_removed_plan_sig_field(tmp_path):
    """Delete FusedPlanSig.index_joins (the PR-4 class of omission):
    _trace_conj still reads sig.index_joins, so DL002 must fire on the
    mutated copy of the real module."""
    src = (REPO / "das_tpu/query/fused.py").read_text()
    field_line = "    index_joins: Tuple[int, ...] = ()\n"
    assert src.count(field_line) == 1, "fused.py layout changed"
    mutated = tmp_path / "fused_mutated.py"
    mutated.write_text(src.replace(field_line, ""))
    findings = run_analysis([mutated], rules=["DL002"])
    hits = [f for f in findings if "index_joins" in f.message]
    assert hits, "DL002 missed the removed plan-sig field:\n" + "\n".join(
        f.render() for f in findings
    )


def test_dl004_catches_undeclared_counter_key(tmp_path):
    """Typo a ROUTE_COUNTS key in a copy of the real compiler module:
    the literal no longer matches ops/counters.py's registry."""
    src = (REPO / "das_tpu/query/compiler.py").read_text()
    needle = 'ROUTE_COUNTS["staged"]'
    assert needle in src, "compiler.py layout changed"
    mutated = tmp_path / "compiler_mutated.py"
    mutated.write_text(src.replace(needle, 'ROUTE_COUNTS["stagedd"]', 1))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/ops/counters.py"], rules=["DL004"]
    )
    assert any("'stagedd'" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )


def test_dl007_catches_unguarded_cache_insert(tmp_path):
    """Mutate the REAL streaming-settle insert site (query/fused.py
    settle_pending_iter) to re-read the version at insert time — the
    exact bug shape the delta_version guard exists to prevent, now that
    speculative dispatch widens the dispatch→insert window."""
    src = (REPO / "das_tpu/query/fused.py").read_text()
    needle = "results_cache.put(key, job.result, pending.version)"
    assert src.count(needle) == 1, "fused.py layout changed"
    mutated = tmp_path / "fused_mutated.py"
    mutated.write_text(src.replace(
        needle,
        "results_cache.put(key, job.result, results_cache.version())",
        1,
    ))
    findings = run_analysis([mutated], rules=["DL007"])
    assert any(
        "AT INSERT TIME" in f.message for f in findings
    ), "\n".join(f.render() for f in findings)
    # ... and dropping the argument entirely is the other bug shape
    unversioned = tmp_path / "fused_unversioned.py"
    unversioned.write_text(src.replace(
        needle, "results_cache.put(key, job.result)", 1
    ))
    findings = run_analysis([unversioned], rules=["DL007"])
    assert any(
        "without a dispatch-time version" in f.message for f in findings
    ), "\n".join(f.render() for f in findings)


def test_dl008_catches_undeclared_planner_route(tmp_path):
    """Mutate the REAL planner search module to emit a route ROUTE_KEYS
    never declared (the ISSUE-8 named candidate rule): the costed plan
    would then claim a route no counter tracks and no pin could verify."""
    src = (REPO / "das_tpu/planner/search.py").read_text()
    needle = 'route="sharded" if n_shards > 1 else "fused",'
    assert src.count(needle) == 1, "search.py layout changed"
    mutated = tmp_path / "search_mutated.py"
    mutated.write_text(src.replace(
        needle, 'route="sharded" if n_shards > 1 else "warp_fused",', 1
    ))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/ops/counters.py"], rules=["DL008"]
    )
    assert any("'warp_fused'" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )
    # ... and an undeclared planner counter key is the other bug shape
    csrc = (REPO / "das_tpu/planner/__init__.py").read_text()
    cneedle = 'PLANNER_COUNTS["planned"] += 1'
    assert csrc.count(cneedle) == 1, "planner/__init__.py layout changed"
    typo = tmp_path / "planner_typo.py"
    typo.write_text(csrc.replace(
        cneedle, 'PLANNER_COUNTS["planed"] += 1', 1
    ))
    findings = run_analysis(
        [typo, REPO / "das_tpu/ops/counters.py"], rules=["DL008"]
    )
    assert any("'planed'" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )


def test_dl009_catches_undeclared_collective_scope(tmp_path):
    """Mutate a COPY of the real sharded executor: a psum added to a
    scope COLLECTIVE_SITES never declared must fail — otherwise
    cross-shard bytes leave the one reviewable list."""
    src = (REPO / "das_tpu/parallel/fused_sharded.py").read_text()
    needle = "def _repartition("
    assert src.count(needle) == 1, "fused_sharded.py layout changed"
    mutated = tmp_path / "fused_sharded_mutated.py"
    mutated.write_text(src.replace(
        needle,
        'def _rogue_reduce(x):\n'
        '    return lax.psum(x, SHARD_AXIS)\n\n\n'
        + needle,
        1,
    ))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/parallel/mesh.py"], rules=["DL009"]
    )
    assert any("_rogue_reduce" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )
    # ... and a clean SAME-STEM copy stays quiet next to the real
    # registry (only the registry's stale-entry leg may fire, for the
    # sharded_db/sharded_tree scopes absent from this partial set)
    clean = tmp_path / "fused_sharded.py"
    clean.write_text(src)
    findings = run_analysis(
        [clean, REPO / "das_tpu/parallel/mesh.py"], rules=["DL009"]
    )
    assert not [
        f for f in findings
        if "undeclared scope" in f.message
    ], "\n".join(f.render() for f in findings)


def test_dl010_catches_sync_through_helper(tmp_path):
    """Route the REAL dispatch half through a syncing helper: the body
    stays DL001-clean (the banned call moved one hop away) but the
    call-graph scan must still reach it and render the path."""
    src = (REPO / "das_tpu/query/fused.py").read_text()
    needle = '        record_dispatch("fused")\n'
    assert src.count(needle) == 1, "fused.py layout changed"
    mutated = tmp_path / "fused_mutated.py"
    mutated.write_text(
        src.replace(
            needle,
            '        record_dispatch("fused")\n'
            "        _flush_telemetry(self.arrays)\n",
            1,
        )
        + "\n\ndef _flush_telemetry(arrays):\n"
        "    return np.asarray(arrays)\n"
    )
    findings = run_analysis([mutated], rules=["DL010"])
    hits = [f for f in findings if "_flush_telemetry" in f.message]
    assert hits, "DL010 missed the helper-hop sync:\n" + "\n".join(
        f.render() for f in findings
    )
    assert any("_ExecJob.dispatch" in f.message for f in hits)
    # ... and DL001 alone stays quiet on it: the hop defeats the
    # syntactic rule, which is exactly why DL010 exists
    direct = [
        f for f in run_analysis([mutated], rules=["DL001"])
        if "_flush_telemetry" in f.message
    ]
    assert not direct


def test_dl012_catches_per_request_dict_keying_jit(tmp_path):
    """Key the REAL fused builder's trace on a per-request dict (the
    DL002 lesson, dynamic edition): the annotation flip makes the
    closure's count_only a mutable per-request value."""
    src = (REPO / "das_tpu/query/fused.py").read_text()
    needle = "def build_fused(sig: FusedPlanSig, count_only: bool = False):"
    assert src.count(needle) == 1, "fused.py layout changed"
    mutated = tmp_path / "fused_mutated.py"
    mutated.write_text(src.replace(
        needle,
        "def build_fused(sig: FusedPlanSig, count_only: dict = False):",
        1,
    ))
    findings = run_analysis([mutated], rules=["DL012"])
    assert any(
        "count_only" in f.message for f in findings
    ), "\n".join(f.render() for f in findings)
    # the committed module is clean
    assert not run_analysis(
        [REPO / "das_tpu/query/fused.py"], rules=["DL012"]
    )


def test_dl013_catches_undeclared_device_get(tmp_path):
    """Add an undeclared jax.device_get to a same-stem copy of the real
    tree module (run against the real FETCH_SITES registry): the new
    transfer site must fail, the declared ones must not."""
    src = (REPO / "das_tpu/query/tree.py").read_text()
    needle = "def materialize_tables("
    assert src.count(needle) == 1, "tree.py layout changed"
    mutated = tmp_path / "tree.py"  # stem must stay `tree` for the scopes
    mutated.write_text(src.replace(
        needle,
        "def _rogue_fetch(t):\n"
        "    return jax.device_get(t.vals)\n\n\n" + needle,
        1,
    ))
    findings = run_analysis(
        [mutated, REPO / "das_tpu/query/fused.py"], rules=["DL013"],
        partial=True,
    )
    assert any("_rogue_fetch" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )
    # ... and the clean same-stem copy passes next to the registry
    # (partial=True: the other declared scopes' modules aren't in set)
    clean = tmp_path / "clean" / "tree.py"
    clean.parent.mkdir()
    clean.write_text(src)
    findings = run_analysis(
        [clean, REPO / "das_tpu/query/fused.py"], rules=["DL013"],
        partial=True,
    )
    assert not [
        f for f in findings if "undeclared scope" in f.message
    ], "\n".join(f.render() for f in findings)


def test_dl013_partial_suppresses_stale_only():
    """A partial set must still report presence violations but skip the
    stale-entry leg (the --changed-only contract); the full-set run
    keeps it."""
    fused = REPO / "das_tpu/query/fused.py"
    partial = run_analysis([fused], rules=["DL013"], partial=True)
    assert not partial, "\n".join(f.render() for f in partial)
    full_subset = run_analysis([fused], rules=["DL013"])
    assert any("stale entry" in f.message for f in full_subset), (
        "fused.py alone declares scopes for other modules — the "
        "non-partial run must flag them stale"
    )


# -- suppression + baseline mechanics ------------------------------------


def test_per_file_suppression(tmp_path):
    bad = (FIXTURES / "dl003_bad.py").read_text()
    suppressed = tmp_path / "suppressed.py"
    suppressed.write_text("# daslint: disable=DL003\n" + bad)
    assert run_analysis([suppressed], rules=["DL003"]) == []


def test_suppression_requires_a_comment_line(tmp_path):
    """Quoting the syntax in a docstring or string literal must NOT
    disable anything — only a real comment token counts, including when
    the quote sits on its own line inside a multi-line docstring."""
    bad = (FIXTURES / "dl003_bad.py").read_text()
    documented = tmp_path / "documented.py"
    documented.write_text(
        '"""Docs may mention `# daslint: disable=DL003` harmlessly."""\n'
        'EXAMPLE = "# daslint: disable=DL003"\n' + bad
    )
    assert run_analysis([documented], rules=["DL003"])
    multiline = tmp_path / "multiline.py"
    multiline.write_text(
        '"""Docs.\n# daslint: disable=DL003\n"""\n' + bad
    )
    assert run_analysis([multiline], rules=["DL003"])


def test_dl006_sees_mutations_inside_with_blocks():
    """Regression: a mutation that is a DIRECT statement of a `with`
    block must be checked (holding some lock does not satisfy worker
    confinement, and the wrong lock does not satisfy lock ownership)."""
    findings = run_analysis([FIXTURES / "dl006_bad.py"], rules=["DL006"])
    msgs = "\n".join(f.message for f in findings)
    assert "Pipeline.rescale" in msgs
    assert "`self._worker` mutated outside `with self._lock:` in " \
           "Pipeline.rescale" in msgs


def test_dl006_covers_undeclared_classes_in_declaring_module():
    """Regression: a second class in a module that declares a
    LOCK_DISCIPLINE is covered even though no map entry names it."""
    findings = run_analysis([FIXTURES / "dl006_bad.py"], rules=["DL006"])
    msgs = "\n".join(f.message for f in findings)
    assert "`self.entries` mutated in SideCar.put" in msgs


def test_dl006_sees_mutations_inside_match_cases():
    """Regression: a mutation inside a `match` arm must be checked like
    any other compound statement — `classify` is not a worker method."""
    findings = run_analysis([FIXTURES / "dl006_bad.py"], rules=["DL006"])
    msgs = "\n".join(f.message for f in findings)
    assert "`self.stats` is worker-thread-confined but Pipeline.classify" \
        in msgs


def test_dl004_nested_def_counts_once(tmp_path):
    """Regression: a counting site inside a nested function is reported
    exactly once, and the nested scope's dynamic-key names do not pick
    up same-named locals from the enclosing function."""
    mod = tmp_path / "nested.py"
    mod.write_text(
        "DISPATCH_KEYS = ()\n"
        "DISPATCH_COUNTS = {}\n"
        "def outer():\n"
        "    k = 'outer_key'\n"
        "    def inner():\n"
        "        k = 'inner_key'\n"
        "        DISPATCH_COUNTS[k] += 1\n"
        "    inner()\n"
    )
    findings = run_analysis([mod], rules=["DL004"])
    inner = [f for f in findings if "'inner_key'" in f.message]
    assert len(inner) == 1, "\n".join(f.render() for f in findings)
    assert not any("'outer_key'" in f.message for f in findings), \
        "\n".join(f.render() for f in findings)


def test_dl002_checks_qualified_constructor():
    """Regression: `mod.LeakyPlanSig(...)` gets the same keyword check
    as a bare-name construction."""
    findings = run_analysis([FIXTURES / "dl002_bad.py"], rules=["DL002"])
    assert any("`exch`" in f.message for f in findings), "\n".join(
        f.render() for f in findings
    )


def test_dl002_sees_optional_annotated_consumers():
    """Regression: Optional[Sig]-annotated params keep the read check."""
    findings = run_analysis([FIXTURES / "dl002_bad.py"], rules=["DL002"])
    assert any(
        "exch_caps" in f.message and f.line > 30 for f in findings
    ), "\n".join(f.render() for f in findings)


def test_cli_rules_subset_skips_other_rules_baseline(tmp_path):
    """Regression: a --rules subset run must not report other rules'
    grandfathered entries as stale."""
    import shutil

    from das_tpu.analysis.__main__ import main

    work = tmp_path / "fx"
    work.mkdir()
    shutil.copy(FIXTURES / "dl006_good.py", work / "dl006_good.py")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [{
        "rule": "DL001", "path": "somewhere.py", "message": "kept",
        "justification": "belongs to an unselected rule",
    }]}))
    assert main([
        str(work), "--rules", "DL006", "--baseline", str(bl),
    ]) == 0


def test_baseline_grandfathers_and_goes_stale(tmp_path):
    findings = run_analysis([FIXTURES / "dl003_bad.py"], rules=["DL003"])
    assert findings
    entries = [
        {
            "rule": f.rule, "path": f.path, "message": f.message,
            "justification": "fixture keep",
        }
        for f in findings
    ]
    # one extra entry that matches nothing -> stale
    entries.append({
        "rule": "DL003", "path": "nowhere.py", "message": "gone",
        "justification": "stale on purpose",
    })
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": entries}))
    new, kept, stale = apply_baseline(findings, load_baseline(bl))
    assert not new and len(kept) == len(findings) and len(stale) == 1


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [
        {"rule": "DL001", "path": "x.py", "message": "m"}
    ]}))
    with pytest.raises(ValueError):
        load_baseline(bl)


# -- CLI contract --------------------------------------------------------


def test_cli_exit_codes_inprocess(capsys):
    from das_tpu.analysis.__main__ import main

    assert main([str(FIXTURES / "dl006_good.py"), "--rules", "DL006"]) == 0
    assert main([str(FIXTURES / "dl006_bad.py"), "--rules", "DL006"]) == 1
    assert main(["--list-rules"]) == 0
    assert main([str(REPO / "does_not_exist.py")]) == 2
    # an EXPLICIT --baseline that does not exist must not silently skip
    # the stale-entry check (the default path may be absent)
    assert main([
        str(FIXTURES / "dl006_good.py"), "--rules", "DL006",
        "--baseline", str(REPO / "no_such_baseline.json"),
    ]) == 2
    out = capsys.readouterr().out
    assert "DL006" in out


def test_cli_json_output(capsys):
    from das_tpu.analysis.__main__ import main

    rc = main([str(FIXTURES / "dl001_bad.py"), "--rules", "DL001", "--json"])
    assert rc == 1
    record = json.loads(capsys.readouterr().out)
    assert record["findings"] and not record["stale_baseline"]
    assert {"rule", "path", "line", "message"} <= set(
        record["findings"][0]
    )


def test_cli_subprocess_whole_tree():
    """The acceptance command, end to end: exits 0 on the final tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "das_tpu.analysis", "das_tpu"],
        cwd=REPO, capture_output=True, text=True,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(Path.home())},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_cli_select_ignore_and_unknown_ids(tmp_path, capsys):
    from das_tpu.analysis.__main__ import main

    bad = str(FIXTURES / "dl013_bad.py")
    # --select is the --rules alias with the same semantics
    assert main([bad, "--select", "DL013"]) == 1
    # --ignore carves the selected rule back out -> nothing runs -> clean
    assert main([bad, "--select", "DL013", "--ignore", "DL013"]) == 0
    # an unknown id in either flag is a usage error, not a silent no-op
    assert main([bad, "--select", "DL999"]) == 2
    assert main([bad, "--ignore", "DL0XX"]) == 2
    capsys.readouterr()


def test_cli_allow_partial_skips_stale_baseline(tmp_path, capsys):
    """--changed-only's analyzer contract: a baseline entry whose file
    is outside the partial path set must NOT fail the run as stale —
    staleness is the full run's verdict (which must still flag it)."""
    from das_tpu.analysis.__main__ import main

    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [{
        "rule": "DL006", "path": "somewhere/else.py", "message": "kept",
        "justification": "its module is not in the partial set",
    }]}))
    args = [
        str(FIXTURES / "dl006_good.py"), "--select", "DL006",
        "--baseline", str(bl),
    ]
    assert main(args + ["--allow-partial"]) == 0
    assert main(args) == 1  # the full-set semantics keep the teeth
    capsys.readouterr()


def test_dl013_flags_module_level_fetch(tmp_path):
    """An import-time device_get has no declarable scope and must fire
    even though it sits in no function body."""
    mod = tmp_path / "import_fetch.py"
    mod.write_text(
        "import jax\n"
        "FETCH_SITES = ()\n"
        "FETCH_COUNTS = {'n': 0}\n"
        "_SNAP = jax.device_get(42)\n"
    )
    findings = run_analysis([mod], rules=["DL013"])
    assert any(
        "outside any function" in f.message for f in findings
    ), "\n".join(f.render() for f in findings)


def test_cli_sarif_reports_stale_baseline(tmp_path, capsys):
    """A stale entry fails the run, so the SARIF consumer must see it —
    an empty results array on a red build explains nothing."""
    from das_tpu.analysis.__main__ import main

    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"findings": [{
        "rule": "DL006", "path": "gone.py", "message": "vanished",
        "justification": "stale on purpose",
    }]}))
    rc = main([
        str(FIXTURES / "dl006_good.py"), "--select", "DL006",
        "--baseline", str(bl), "--format", "sarif",
    ])
    assert rc == 1
    record = json.loads(capsys.readouterr().out)
    results = record["runs"][0]["results"]
    assert any("stale baseline entry" in r["message"]["text"]
               for r in results)


def test_cli_sarif_output(capsys):
    from das_tpu.analysis.__main__ import main

    rc = main([
        str(FIXTURES / "dl001_bad.py"), "--select", "DL001",
        "--format", "sarif",
    ])
    assert rc == 1
    record = json.loads(capsys.readouterr().out)
    assert record["version"] == "2.1.0"
    run = record["runs"][0]
    assert run["tool"]["driver"]["name"] == "daslint"
    assert run["results"], "no SARIF results for a bad fixture"
    r0 = run["results"][0]
    assert r0["ruleId"] == "DL001"
    loc = r0["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("dl001_bad.py")
    assert loc["region"]["startLine"] > 0
    assert any(
        rule["id"] == "DL001" for rule in run["tool"]["driver"]["rules"]
    )


def test_file_cache_reuses_and_invalidates(tmp_path):
    """The (path, mtime, size) parse cache returns the SAME SourceFile
    for an unchanged file and re-parses after an edit."""
    from das_tpu.analysis.core import collect_files

    mod = tmp_path / "cached.py"
    mod.write_text("X = 1\n")
    first = collect_files([mod])[0]
    again = collect_files([mod])[0]
    assert again is first, "unchanged file was re-parsed"
    import os

    mod.write_text("X = 2  # changed\n")
    # belt and braces on coarse filesystem clocks: bump mtime explicitly
    st = mod.stat()
    os.utime(mod, ns=(st.st_atime_ns, st.st_mtime_ns + 1))
    fresh = collect_files([mod])[0]
    assert fresh is not first, "edited file served from cache"
    assert "changed" in fresh.text


# -- registries + generated docs stay pinned -----------------------------


def test_counter_registry_pins():
    """THE test reference for every counter key (DL004's third leg):
    a key rename/add/remove must consciously edit this pin, and the
    dicts must be built from the registry."""
    from das_tpu import kernels
    from das_tpu.ops import counters
    from das_tpu.query import compiler

    assert counters.DISPATCH_KEYS == (
        "lowered", "fused", "fused_tree",
        "sharded", "sharded_tree_fused", "count",
    )
    assert counters.ROUTE_KEYS == (
        "fused", "fused_tree", "sharded_tree_fused",
        "staged", "tree", "sharded", "host", "star",
    )
    assert tuple(counters.DISPATCH_COUNTS) == counters.DISPATCH_KEYS
    # the counters' old address, which the benchmark's harness still
    # imports (benchmark/harness/cell.py): the same objects, not copies
    assert kernels.DISPATCH_COUNTS is counters.DISPATCH_COUNTS
    assert kernels.record_dispatch is counters.record_dispatch
    assert kernels.reset_dispatch_counts is counters.reset_dispatch_counts
    assert tuple(compiler.ROUTE_COUNTS) == counters.ROUTE_KEYS
    from das_tpu import planner

    assert counters.PLANNER_KEYS == (
        "planned", "greedy", "dp", "greedy_tail", "ref_order",
        "programs", "round0", "retries", "est_rows", "actual_rows",
        "explain",
    )
    assert tuple(planner.PLANNER_COUNTS) == counters.PLANNER_KEYS


def test_coalescer_declares_lock_discipline():
    from das_tpu.service import coalesce

    assert "QueryCoalescer.stats" in coalesce.LOCK_DISCIPLINE
    assert "_run" in coalesce.WORKER_METHODS["QueryCoalescer"]


def test_env_table_in_sync():
    """ARCHITECTURE.md's operator table is generated from ENV_REGISTRY;
    editing either side alone must fail (the gen script's --check)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen_env_table", REPO / "scripts/gen_env_table.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    doc = (REPO / "ARCHITECTURE.md").read_text()
    assert mod.splice(doc, mod.render_table()) == doc
