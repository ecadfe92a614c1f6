#!/usr/bin/env python
"""Generate the runnable-walkthrough notebooks with STORED outputs.

Role of /root/reference/notebooks/ (QueryDAS.ipynb, SimplePatternMiner.ipynb
ship with executed outputs — the de-facto baseline docs).  jupyter_client is
not in this image, so instead of a kernel each code cell is exec()'d in one
shared namespace with stdout captured and the trailing expression repr'd,
then written through nbformat as a v4 notebook whose outputs are the REAL
results of this run.

Usage:  JAX_PLATFORMS=cpu python ops/make_notebooks.py   (from the repo root)
"""

import ast
import contextlib
import io
import os
import sys

import nbformat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "compat"))
sys.path.insert(0, REPO)


def run_cell(source: str, ns: dict):
    """Execute one cell REPL-style: exec the body, eval a trailing
    expression; returns (stdout_text, result_repr_or_None)."""
    tree = ast.parse(source)
    trailing = None
    if tree.body and isinstance(tree.body[-1], ast.Expr):
        trailing = ast.Expression(tree.body.pop(-1).value)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        if tree.body:
            exec(compile(tree, "<cell>", "exec"), ns)
        result = (
            eval(compile(trailing, "<cell>", "eval"), ns)
            if trailing is not None
            else None
        )
    return buf.getvalue(), (repr(result) if result is not None else None)


def build_notebook(cells, path):
    nb = nbformat.v4.new_notebook()
    ns: dict = {}
    count = 0
    for kind, source in cells:
        if kind == "md":
            nb.cells.append(nbformat.v4.new_markdown_cell(source))
            continue
        count += 1
        stdout, result = run_cell(source, ns)
        outputs = []
        if stdout:
            outputs.append(
                nbformat.v4.new_output("stream", name="stdout", text=stdout)
            )
        if result is not None:
            outputs.append(
                nbformat.v4.new_output(
                    "execute_result",
                    data={"text/plain": result},
                    execution_count=count,
                )
            )
        cell = nbformat.v4.new_code_cell(source, execution_count=count)
        cell.outputs = outputs
        nb.cells.append(cell)
    nbformat.write(nb, path)
    print(f"wrote {path} ({len(nb.cells)} cells)")


QUERY_DAS = [
    ("md", "# Query DAS after loading a knowledge base"),
    ("md",
     "This notebook mirrors the reference `notebooks/QueryDAS.ipynb` on the "
     "TPU-native backend: instantiate a `DistributedAtomSpace`, load the "
     "animals knowledge base, and run the four example queries.\n\n"
     "The imports come from the `das` compatibility package (`compat/das`), "
     "i.e. the exact module paths the reference uses — backed by das_tpu, "
     "with `matched()` routed through the device compiler."),
    ("code",
     "import sys\n"
     "sys.path.insert(0, '../compat'); sys.path.insert(0, '..')\n"
     "from das.distributed_atom_space import DistributedAtomSpace, QueryOutputFormat\n"
     "from das.pattern_matcher.pattern_matcher import PatternMatchingAnswer, "
     "OrderedAssignment, UnorderedAssignment, CompositeAssignment, "
     "Node, Link, Variable, Not, And, Or\n"
     "import warnings\n"
     "warnings.filterwarnings('ignore')\n"
     "das = DistributedAtomSpace(backend='tensor')\n"
     "das.load_knowledge_base('../data/samples/animals.metta')\n"
     "db = das.db\n"
     "db.prefetch()"),
    ("md",
     "Two utility functions showing how to iterate a query answer.  Answers "
     "mix `Ordered` assignments (one value per variable) and `Unordered` "
     "assignments (a multiset of values matching a multiset of variables)."),
    ("code",
     "def print_ordered_assignment(assignment):\n"
     "    if assignment is not None:\n"
     "        for key, value in assignment.mapping.items():\n"
     "            print(f\"{key}: {db.get_node_name(value)}\")\n"
     "\n"
     "def print_unordered_assignment(assignment):\n"
     "    if assignment is not None:\n"
     "        symbols = [s for s, c in assignment.symbols.items() for _ in range(c)]\n"
     "        values = [db.get_node_name(v) for v, c in assignment.values.items() for _ in range(c)]\n"
     "        print(f\"{', '.join(symbols)} = {', '.join(values)}\")"),
    ("md", "Print the atom count to make sure the knowledge base is correct."),
    ("code", "das.count_atoms()"),
    ("md",
     "The handle of `Concept:human` is reference-identical "
     "(md5 content addressing):"),
    ("code", "das.get_node('Concept', 'human')"),
    ("md",
     "Four example queries (`And` / `Or` / `Not` over `Link` patterns with "
     "`Variable`s — same constructors and keyword conventions as the "
     "reference)."),
    ("code",
     "V1 = Variable(\"V1\")\nV2 = Variable(\"V2\")\nV3 = Variable(\"V3\")\n"
     "my_query_1 = And([\n"
     "    Link(\"Inheritance\", ordered=True, targets=[V1, V2]),\n"
     "    Link(\"Inheritance\", ordered=True, targets=[V2, V3])\n"
     "])"),
    ("code",
     "N1 = Node(\"Concept\", \"human\")\n"
     "my_query_2 = And([\n"
     "    Link(\"Inheritance\", ordered=True, targets=[V1, V2]),\n"
     "    Link(\"Inheritance\", ordered=True, targets=[V2, V3]),\n"
     "    Not(Link(\"Inheritance\", ordered=True, targets=[N1, V2]))\n"
     "])"),
    ("code",
     "N2 = Node(\"Concept\", \"snake\")\n"
     "my_query_3 = And([\n"
     "    Link(\"Inheritance\", ordered=True, targets=[V1, V2]),\n"
     "    Link(\"Inheritance\", ordered=True, targets=[V2, V3]),\n"
     "    Not(Or([\n"
     "        Link(\"Inheritance\", ordered=True, targets=[N1, V2]),\n"
     "        Link(\"Inheritance\", ordered=True, targets=[N2, V2])\n"
     "    ]))\n"
     "])"),
    ("code",
     "NM = Node(\"Concept\", \"mammal\")\n"
     "my_query_4 = And([\n"
     "    Link(\"Similarity\", ordered=False, targets=[V1, V2]),\n"
     "    Not(Or([\n"
     "        Link(\"Inheritance\", ordered=True, targets=[V1, NM]),\n"
     "        Link(\"Inheritance\", ordered=True, targets=[V2, NM]),\n"
     "    ]))\n"
     "])"),
    ("md",
     "Execute each query.  `matched()` routes through the compiled device "
     "path (fused / tree executor) and falls back to the host algebra only "
     "outside the compilable language; either way the answer sets are "
     "reference-identical."),
    ("code",
     "for name, q in [(\"my_query_1\", my_query_1), (\"my_query_2\", my_query_2),\n"
     "                (\"my_query_3\", my_query_3), (\"my_query_4\", my_query_4)]:\n"
     "    query_answer = PatternMatchingAnswer()\n"
     "    matched = q.matched(db, query_answer)\n"
     "    print(f\"{name}: matched={matched}, \"\n"
     "          f\"{len(query_answer.assignments)} assignments\")"),
    ("md", "Inspect one answer set in full (query 4: similar non-mammals)."),
    ("code",
     "query_answer = PatternMatchingAnswer()\n"
     "matched = my_query_4.matched(db, query_answer)\n"
     "for assignment in sorted(query_answer.assignments):\n"
     "    if type(assignment) is OrderedAssignment:\n"
     "        print_ordered_assignment(assignment)\n"
     "    elif type(assignment) is UnorderedAssignment:\n"
     "        print_unordered_assignment(assignment)\n"
     "    elif type(assignment) is CompositeAssignment:\n"
     "        print_ordered_assignment(assignment.ordered_mapping)\n"
     "        for unordered_assignment in assignment.unordered_mappings:\n"
     "            print_unordered_assignment(unordered_assignment)\n"
     "    print(\"\")"),
    ("md",
     "The same queries are also available through the API facade with "
     "formatted output:"),
    ("code",
     "print(das.query(my_query_1, QueryOutputFormat.HANDLE)[:300] + ' ...')"),
]


SIMPLE_PATTERN_MINER = [
    ("md", "# Simple Pattern Miner"),
    ("md",
     "TPU-native edition of the reference `SimplePatternMiner.ipynb`: mine "
     "surprising conjunctive patterns from a bio atomspace.  The reference "
     "notebook's stored baseline is **74-104 ms per halo link** for its "
     "template-build + count loop against a live Redis cluster (cell 9); "
     "here candidate counting funnels through batched device count "
     "programs (`query/fused.py count_batch`)."),
    ("code",
     "import sys, time\n"
     "sys.path.insert(0, '..')\n"
     "import warnings; warnings.filterwarnings('ignore')\n"
     "from das_tpu.models.bio import build_bio_ontology_atomspace\n"
     "from das_tpu.storage.tensor_db import TensorDB\n"
     "from das_tpu.core.config import DasConfig\n"
     "from das_tpu.mining.miner import PatternMiner\n"
     "data, _, _ = build_bio_ontology_atomspace(\n"
     "    n_genes=20000, n_processes=2000, members_per_gene=5,\n"
     "    n_interactions=40000, n_reactomes=2000, n_uniprots=6000)\n"
     "db = TensorDB(data, DasConfig())\n"
     "db.prefetch()"),
    ("md", "Atom counts for this run (the reference's cell 0 prints its "
     "FlyBase store: `(2584508, 27871440)`; chip_smoke.py loads that "
     "shape times `--scale` on the chip):"),
    ("code", "db.count_atoms()"),
    ("md",
     "**Halo expansion** — all links within 2 hops of three seed genes.  "
     "The reference probes 5 wildcard templates per node per level "
     "(~0.1 ms per warm Redis probe); here the incoming-set CSR lives on "
     "device, so the halo is an offsets gather per frontier."),
    ("code",
     "miner = PatternMiner(db, halo_length=2, link_rate=0.01, seed=7)\n"
     "genes = db.get_all_nodes('Gene', names=True)[:3]\n"
     "gene_handles = [db.get_node_handle('Gene', g) for g in genes]\n"
     "t0 = time.perf_counter()\n"
     "universe = miner.expand_halo(gene_handles)\n"
     "halo_s = time.perf_counter() - t0\n"
     "print(f'{universe} halo links in {halo_s*1e3:.0f} ms')"),
    ("md",
     "**Candidate patterns** — every wildcard variant of every halo link, "
     "counted in batched device programs (the reference runs one Redis "
     "round trip per candidate)."),
    ("code",
     "t0 = time.perf_counter()\n"
     "n_candidates = miner.build_patterns()\n"
     "count_s = time.perf_counter() - t0\n"
     "print(f'{n_candidates} candidate patterns counted in {count_s:.1f} s')"),
    ("md",
     "**Mining loop** — sample 3-term composite patterns, count their "
     "joint matches, score by I-Surprisingness (observed probability vs "
     "the best independence estimate over every binary partition)."),
    ("code",
     "t0 = time.perf_counter()\n"
     "best = miner.mine(ngram=3, epochs=50)\n"
     "mine_s = time.perf_counter() - t0\n"
     "print(f'joint mining {mine_s:.1f} s')\n"
     "print(f'best pattern count={best.count} "
     "isurprisingness={best.isurprisingness:.4f}')\n"
     "for term in best.term_handles:\n"
     "    print('  ', term)"),
    ("md", "Throughput summary vs the reference baseline:"),
    ("code",
     "total_s = halo_s + count_s + mine_s\n"
     "print(f'counting phase: {(halo_s+count_s)/universe*1e3:.2f} ms/link '\n"
     "      f'(reference loop: 74-104 ms/link)')\n"
     "print(f'total incl. whole-KB ngram joint mining: '\n"
     "      f'{total_s/universe*1e3:.2f} ms/link')"),
]


LOAD_KNOWLEDGE_BASE = [
    ("md", "# Load a knowledge base"),
    ("md",
     "TPU-native edition of the reference `LoadKnowledgeBase.ipynb`: the "
     "three load paths — the general MeTTa parser, the canonical fast "
     "path (C++ scanner when built), and incremental transaction "
     "commits."),
    ("code",
     "import sys\n"
     "sys.path.insert(0, '../compat'); sys.path.insert(0, '..')\n"
     "import warnings; warnings.filterwarnings('ignore')\n"
     "from das.distributed_atom_space import DistributedAtomSpace\n"
     "das = DistributedAtomSpace(backend='tensor')"),
    ("md", "**General parser path** — any .metta/.scm file or directory:"),
    ("code",
     "das.load_knowledge_base('../data/samples/animals.metta')\n"
     "das.count_atoms()"),
    ("md",
     "**Canonical fast path** — normalized one-expression-per-line files "
     "(converter output).  The native C++ scanner parses GIL-free with "
     "inline md5; identical records to the Python scanner:"),
    ("code",
     "from das_tpu.ingest import native\n"
     "from das_tpu.models.bio import write_bio_canonical\n"
     "import tempfile, os, time\n"
     "d = tempfile.mkdtemp()\n"
     "path = os.path.join(d, 'bio.metta')\n"
     "lines = write_bio_canonical(path, n_genes=5000, n_processes=500,\n"
     "                            members_per_gene=5, n_interactions=4000)\n"
     "das2 = DistributedAtomSpace(backend='tensor')\n"
     "t0 = time.perf_counter()\n"
     "das2.load_canonical_knowledge_base(path)\n"
     "dt = time.perf_counter() - t0\n"
     "print(f'native scanner: {native.native_available()}')\n"
     "print(f'{lines} expressions in {dt:.2f}s '\n"
     "      f'({os.path.getsize(path)/1e6/dt:.1f} MB/s)')\n"
     "das2.count_atoms()"),
    ("md",
     "**Incremental commits** — O(delta) device-side merge, no "
     "re-finalize (the reference's das_update_test.py path):"),
    ("code",
     "tx = das.open_transaction()\n"
     "tx.add('(: \"dog\" Concept)')\n"
     "tx.add('(Inheritance \"dog\" \"mammal\")')\n"
     "das.commit_transaction(tx)\n"
     "das.count_atoms()"),
    ("code",
     "das.get_node('Concept', 'dog')"),
]


QUERY_FLYBASE = [
    ("md", "# Query a FlyBase-style knowledge base"),
    ("md",
     "TPU-native edition of the reference `QueryFlyBase.ipynb`: convert a "
     "PostgreSQL dump with the FlyBase converter, load the emitted MeTTa, "
     "and run Execution-link queries with wall-clock timing."),
    ("code",
     "import sys, glob, time\n"
     "sys.path.insert(0, '../compat'); sys.path.insert(0, '..')\n"
     "import warnings; warnings.filterwarnings('ignore')\n"
     "import tempfile, os\n"
     "from das_tpu.convert.flybase import FlybaseConverter\n"
     "d = tempfile.mkdtemp()\n"
     "sql = os.path.join(d, 'dump.sql')\n"
     "with open(sql, 'w') as f:\n"
     "    f.write('CREATE TABLE public.gene (\\n'\n"
     "            '    gene_id integer NOT NULL,\\n'\n"
     "            '    name text,\\n'\n"
     "            '    organism_id integer\\n'\n"
     "            ');\\n'\n"
     "            'CREATE TABLE public.organism (\\n'\n"
     "            '    organism_id integer NOT NULL,\\n'\n"
     "            '    genus text\\n'\n"
     "            ');\\n'\n"
     "            'COPY public.gene (gene_id, name, organism_id) FROM stdin;\\n'\n"
     "            + ''.join(f'{i}\\tFBgn{i:07d}\\t{1 + i % 3}\\n' for i in range(200))\n"
     "            + '\\\\.\\n'\n"
     "            'COPY public.organism (organism_id, genus) FROM stdin;\\n'\n"
     "            '1\\tDrosophila\\n2\\tMusca\\n3\\tAedes\\n'\n"
     "            '\\\\.\\n'\n"
     "            'ALTER TABLE ONLY public.gene ADD CONSTRAINT g_pk PRIMARY KEY (gene_id);\\n'\n"
     "            'ALTER TABLE ONLY public.organism ADD CONSTRAINT o_pk PRIMARY KEY (organism_id);\\n'\n"
     "            'ALTER TABLE ONLY public.gene ADD CONSTRAINT g_fk FOREIGN KEY (organism_id) '\n"
     "            'REFERENCES public.organism(organism_id);\\n')\n"
     "out = os.path.join(d, 'metta')\n"
     "FlybaseConverter(sql, out).run()"),
    ("md", "Load the converted files (reference loads its file_NNN.metta "
     "chunks the same way):"),
    ("code",
     "from das.distributed_atom_space import DistributedAtomSpace\n"
     "das = DistributedAtomSpace(backend='tensor')\n"
     "for p in sorted(glob.glob(out + '/*.metta')):\n"
     "    das.load_knowledge_base(p)\n"
     "das.count_atoms()"),
    ("md",
     "Execution-link query with wall-clock timing (the reference's "
     "WallClock cells): which genes belong to organism 1?"),
    ("code",
     "from das.pattern_matcher.pattern_matcher import (\n"
     "    And, Link, Node, PatternMatchingAnswer, Variable)\n"
     "q = Link('Execution', ordered=True, targets=[\n"
     "    Link('Schema', ordered=True, targets=[Node('Schema', 'gene.organism_id')]),\n"
     "    Variable('V_gene'),\n"
     "    Node('Concept', 'organism:1'),\n"
     "])\n"
     "answer = PatternMatchingAnswer()\n"
     "t0 = time.perf_counter()\n"
     "matched = q.matched(das.db, answer)\n"
     "dt = (time.perf_counter() - t0) * 1000\n"
     "print(f'{len(answer.assignments)} genes in {dt:.1f} ms')"),
    ("md", "Resolve a few of the answers to node names:"),
    ("code",
     "names = sorted(das.db.get_node_name(list(a.mapping.values())[0])\n"
     "               for a in answer.assignments)\n"
     "print(names[:10])"),
]


if __name__ == "__main__":
    out_dir = os.path.join(REPO, "notebooks")
    os.makedirs(out_dir, exist_ok=True)
    os.chdir(out_dir)  # notebooks use ../ relative paths
    only = sys.argv[1:] or ["QueryDAS", "SimplePatternMiner",
                            "LoadKnowledgeBase", "QueryFlyBase"]
    specs = {
        "QueryDAS": QUERY_DAS,
        "SimplePatternMiner": SIMPLE_PATTERN_MINER,
        "LoadKnowledgeBase": LOAD_KNOWLEDGE_BASE,
        "QueryFlyBase": QUERY_FLYBASE,
    }
    for name in only:
        build_notebook(specs[name], os.path.join(out_dir, f"{name}.ipynb"))
