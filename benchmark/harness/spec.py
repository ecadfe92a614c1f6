"""Where the harness finds what a cell names.

Everything that belongs to one configuration, one traffic mix, one
query shape, one shape's plain reference or one per-layer metric is a
file of its own, found by the name `BENCHMARK.json` (or the file it
names) gives it; a name with no file behind it is an error, never a
default.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(Exception):
    """BENCHMARK.json or a file it names does not hold what it must."""


def _read_json(path: str):
    if not os.path.isfile(path):
        raise SpecError(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _load_module(path: str, what: str, needs: tuple):
    """The Python file `path` as a module of its own; `what` names it in
    the error when the file, or one of the names it must state, is not
    there."""
    rel = os.path.relpath(path, ROOT)
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no such file {rel}")
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", "bench_" + rel), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for name in needs:
        if not hasattr(module, name):
            raise SpecError(f"{rel} states no {name}")
    return module


def load_rule(name: str, rules_dir: str = None):
    """A query shape's plain reference: `<rules_dir>/<name>.py`, which
    states COLUMNS ((variable, node type), .. in answer order), KEY
    ("gene": the shape's dsl carries {key}; None: it is asked of the
    whole store) and rows(kb, key) -> {tuple of ids: stamp} over
    `reference.plain.PlainKB`'s accessors."""
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"reference rule {name!r} is not a name")
    rules_dir = rules_dir or os.path.join(BENCH_DIR, "reference", "rules")
    rule = _load_module(os.path.join(rules_dir, name + ".py"),
                        f"reference rule {name!r}", ("COLUMNS", "KEY", "rows"))
    if not rule.COLUMNS or rule.KEY not in ("gene", None):
        raise SpecError(f"reference rule {name!r}: COLUMNS {rule.COLUMNS!r}, "
                        f"KEY {rule.KEY!r}")
    return rule


def problems(bench: dict) -> list:
    """What the contract's naming rules would refuse (the driver checks
    the rest): names, units, sources, cross-references."""
    out = []

    def name_ok(kind, value):
        if not isinstance(value, str) or not NAME.match(value):
            out.append(f"{kind} {value!r} is not a name")

    configs = {c.get("name") for c in bench.get("configs", [])}
    cells = {w.get("name") for w in bench.get("workloads", [])}
    for c in bench.get("configs", []):
        name_ok("config", c.get("name"))
        for key in c.get("reduced", []):
            name_ok("reduced key", key)
    for w in bench.get("workloads", []):
        name_ok("workload", w.get("name"))
        name_ok("traffic", w.get("traffic"))
        if w.get("config") not in configs:
            out.append(f"workload {w.get('name')}: no config {w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            out.append(f"workload {w.get('name')}: chips {w.get('chips')!r}")
        if not 1 <= len(w.get("why", "")) <= 200:
            out.append(f"workload {w.get('name')}: why must have 1..200 characters")
    used = {w.get("config") for w in bench.get("workloads", [])}
    for c in configs - used:
        out.append(f"config {c!r} is used by no workload")
    e2e = {m.get("name") for m in bench.get("end_to_end", [])}
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    seen = set()
    for group in ("end_to_end", "per_layer"):
        for m in bench.get(group, []):
            name_ok(f"{group} metric", m.get("name"))
            if m.get("name") in seen:
                out.append(f"metric {m.get('name')!r} appears twice")
            seen.add(m.get("name"))
            if not UNIT.match(str(m.get("unit", ""))):
                out.append(f"metric {m.get('name')}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"metric {m.get('name')}: better {m.get('better')!r}")
            if m.get("source") not in SOURCES:
                out.append(f"metric {m.get('name')}: source {m.get('source')!r}")
            for w in m.get("workloads", []):
                if w not in cells:
                    out.append(f"metric {m.get('name')}: no workload {w!r}")
    for m in bench.get("end_to_end", []):
        if m.get("source") not in ("host_clock", "device_trace"):
            out.append(f"end_to_end {m.get('name')}: source {m.get('source')!r}")
        if not isinstance(m.get("bound"), (int, float)) or not 0.01 <= m["bound"] <= 0.25:
            out.append(f"end_to_end {m.get('name')}: bound {m.get('bound')!r}")
    for m in bench.get("per_layer", []):
        if m.get("moves") not in e2e:
            out.append(f"per_layer {m.get('name')}: moves {m.get('moves')!r}")
    return out


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The metrics of `group` that `workload` reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


class Cell:
    """One entry of `workloads` with everything it names, loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = load_benchmark(root)
        bad = problems(self.bench)
        if bad:
            raise SpecError("BENCHMARK.json: " + "; ".join(bad))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(there are: {sorted(cells)})")
        self.name = name
        self.workload = cells[name]
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.workload["config"])
        self.config = _read_json(os.path.join(root, cfg_entry["file"]))
        bench_dir = os.path.join(root, self.bench["paths"][0])
        self.bench_dir = bench_dir
        self.traffic = _read_json(os.path.join(
            bench_dir, "traffic", self.workload["traffic"] + ".json"))
        self.queries = {
            q["shape"]: _read_json(os.path.join(
                bench_dir, "queries", q["shape"] + ".json"))
            for q in self.traffic["queries"]
        }
        #: shape -> its plain reference, found by the name the shape's
        #: file gives (a `reference_rule` with no file is refused here)
        self.rules = {}
        for shape, q in self.queries.items():
            rule = load_rule(q.get("reference_rule"), os.path.join(
                bench_dir, "reference", "rules"))
            if (rule.KEY is not None) != ("{key}" in q["dsl"]):
                raise SpecError(
                    f"query shape {shape!r}: rule KEY {rule.KEY!r} against "
                    f"dsl {q['dsl']!r}")
            self.rules[shape] = rule
        self.end_to_end = metrics_of(self.bench, "end_to_end", name)
        self.per_layer = metrics_of(self.bench, "per_layer", name)

    def layer_reader(self, metric_name: str):
        """`read(spans, counters, trace, window)` of one per-layer
        metric, from the file that carries its name."""
        return _load_module(
            os.path.join(self.bench_dir, "layer_metrics", metric_name + ".py"),
            f"per-layer metric {metric_name!r} has no reader", ("read",)).read
