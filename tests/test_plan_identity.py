"""The chip's plans, pinned (ISSUE 31 satellite 1).

Every `PlannedProgram` (order, estimates, capacity seeds, route, cost)
and every executor signature (term_caps, join_caps, index_joins, the
mesh's exch_caps) the TPU branch of the planner gave at PR 30 for the
query shapes the benchmark's cells send and the bio suite's stars, Or
trees and negations, recorded as literals — plus `join_step_cost`
itself on a grid that crosses every step of its model.  The literals
were taken on the parent commit with the platform forced to "tpu"
(`kernels.interpret_mode` patched False); the planner has read no
platform since PR 31, so the same numbers must come out unpatched.

Regenerate (only when a PR means to change a plan, and says so):
`python tests/test_plan_identity.py` prints both literal blocks.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from das_tpu.api.atomspace import DistributedAtomSpace  # noqa: E402
from das_tpu.core.config import DasConfig  # noqa: E402
from das_tpu.models.bio import build_bio_atomspace  # noqa: E402
from das_tpu.planner import cost as pcost  # noqa: E402
from das_tpu.planner import plan_conjunction, plan_tree  # noqa: E402
from das_tpu.query import compiler  # noqa: E402
from das_tpu.query.ast import And, Link, Node, Not, Or, Variable  # noqa: E402

#: the benchmark's generator at the rehearsal scale: link types, widths
#: and members_per_gene untouched (benchmark/reference/generator.py)
CELL_SCALE, CELL_SEED = 0.002, 11
CELL_GENE = 17


def _clean_env(monkeypatch):
    # learned capacities from an earlier process must not merge into
    # the seeds under test, and no exported knob may pick another plan
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")
    for name in ("DAS_TPU_PLANNER", "DAS_TPU_PLANNER_DP_MAX",
                 "DAS_TPU_TREE_FUSION"):
        monkeypatch.delenv(name, raising=False)


def _write_cell_kb(directory) -> str:
    from benchmark.reference import generator

    store = generator.Store(CELL_SCALE, CELL_SEED)
    path = os.path.join(str(directory), "kb.metta")
    generator.write_canonical(store, path)
    return path


def _store_getter(cell_kb):
    """name -> db, built on first use (a module holds each once)."""
    built = {}

    def cell(backend, **fields):
        das = DistributedAtomSpace(
            database_name=f"pi_{backend}", backend=backend,
            config=DasConfig(**fields))
        das.load_canonical_knowledge_base(cell_kb)
        return das.db

    def bio(backend, **kw):
        data, _g, _p = build_bio_atomspace(**kw)
        if backend == "tensor":
            from das_tpu.storage.tensor_db import TensorDB

            return TensorDB(data, DasConfig())
        from das_tpu.parallel.sharded_db import ShardedDB

        return ShardedDB(data, DasConfig())

    makers = {
        "cell": lambda: cell("tensor"),
        "cell_mesh4": lambda: cell("sharded", mesh_shape=(4,)),
        "bio": lambda: bio(
            "tensor", n_genes=60, n_processes=15, members_per_gene=4,
            n_interactions=80, seed=7),
        "bio_mesh8": lambda: bio(
            "sharded", n_genes=60, n_processes=15, members_per_gene=4,
            n_interactions=80, seed=7),
        "skew": lambda: bio(
            "tensor", n_genes=120, n_processes=40, members_per_gene=3,
            n_interactions=0, seed=17, skew=1.1),
    }

    def get(name):
        if name not in built:
            os.environ["DAS_TPU_XLA_CACHE"] = "0"
            built[name] = makers[name]()
        return built[name]

    return get


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    return _store_getter(
        _write_cell_kb(tmp_path_factory.mktemp("plan_identity")))


# -- the query shapes ----------------------------------------------------


def _v(name):
    return Variable(name)


def _member(a, b):
    return Link("Member", [a, b], True)


def _interacts(a, b):
    return Link("Interacts", [a, b], True)


def _cell_gene():
    from benchmark.reference import generator

    return Node("Gene", generator.gene_name(CELL_GENE))


def _bio_genes(db, n):
    return [Node("Gene", g) for g in db.get_all_nodes("Gene", names=True)[:n]]


def q_grounded3(db):
    g = _cell_gene()
    return And([_member(g, _v("V3")), _member(_v("V2"), _v("V3")),
                _interacts(g, _v("V2"))])


def q_shared2(db):
    g = _cell_gene()
    return And([_member(g, _v("V3")), _member(_v("V2"), _v("V3"))])


def q_allvar3(db):
    return And([_member(_v("V1"), _v("V3")), _member(_v("V2"), _v("V3")),
                _interacts(_v("V1"), _v("V2"))])


def q_star3(db):
    return And([_member(_v("V1"), _v("V3")), _member(_v("V2"), _v("V3")),
                _member(_v("V4"), _v("V3"))])


def q_star4(db):
    return And([_member(_v("V1"), _v("V3")), _member(_v("V2"), _v("V3")),
                _member(_v("V4"), _v("V3")), _member(_v("V5"), _v("V3"))])


def q_negated(db):
    g = _bio_genes(db, 2)[1]
    return And([_member(_v("V2"), _v("V3")), _member(g, _v("V3")),
                Not(_interacts(g, _v("V2")))])


def _branch(g):
    return And([_member(g, _v("V3")), _member(_v("V2"), _v("V3"))])


def q_or3(db):
    return Or([_branch(g) for g in _bio_genes(db, 3)])


def q_or_not(db):
    a, b, c = _bio_genes(db, 3)
    return Or([_branch(a), _branch(c), Not(_branch(b))])


#: case -> (store, query builder); a "tree" query plans whole
CASES = {
    "cell.grounded3": ("cell", q_grounded3),
    "cell.shared2": ("cell", q_shared2),
    "cell.allvar3": ("cell", q_allvar3),
    "cell_mesh4.grounded3": ("cell_mesh4", q_grounded3),
    "cell_mesh4.shared2": ("cell_mesh4", q_shared2),
    "bio.allvar3": ("bio", q_allvar3),
    "bio.star3": ("bio", q_star3),
    "bio.star4": ("bio", q_star4),
    "bio.negated": ("bio", q_negated),
    "bio.or3": ("bio", q_or3),
    "bio.or_not": ("bio", q_or_not),
    "bio_mesh8.star3": ("bio_mesh8", q_star3),
    "bio_mesh8.or_not": ("bio_mesh8", q_or_not),
    "skew.star3": ("skew", q_star3),
}


# -- what a case observes -------------------------------------------------


def _executor(db):
    if hasattr(db, "query_sharded"):
        from das_tpu.parallel.fused_sharded import get_sharded_executor

        return get_sharded_executor(db), int(db.mesh.devices.size)
    from das_tpu.query.fused import get_executor

    return get_executor(db), 1


def _planned_view(planned):
    if planned is None:
        return None
    assert getattr(planned, "multiway", 0) == 0  # the chip runs chains
    return {
        "order": tuple(planned.order),
        "est_term_rows": tuple(int(r) for r in planned.est_term_rows),
        "est_join_rows": tuple(int(r) for r in planned.est_join_rows),
        "join_cap_seeds": tuple(int(c) for c in planned.join_cap_seeds),
        "route": planned.route,
        "method": planned.method,
        "cost": float(planned.cost),
    }


def _job_view(job):
    if job is None:
        return None
    assert getattr(job, "multiway", 0) == 0
    out = {
        "term_caps": tuple(int(c) for c in job.term_caps),
        "join_caps": tuple(int(c) for c in job.join_caps),
        "index_joins": tuple(int(p) for p in job.index_joins),
    }
    if hasattr(job, "exch_caps"):
        out["exch_caps"] = tuple(int(c) for c in job.exch_caps)
    return out


def observe(db, query) -> dict:
    ex, n_shards = _executor(db)
    plans = compiler.plan_query(db, query)
    if plans is not None:
        assert plans is not compiler.EMPTY_PLAN
        return {
            "planned": _planned_view(
                plan_conjunction(db, list(plans), n_shards=n_shards)),
            "job": _job_view(ex._exec_job(list(plans), False)),
        }
    from das_tpu.query.plan import build_plan
    from das_tpu.query.tree import tree_fusion_sites

    pos_sites, neg_plans, _const = tree_fusion_sites(build_plan(db, query))
    pt = plan_tree(db, pos_sites, neg_plans, n_shards=n_shards)
    tj = ex.tree_exec_job(pos_sites, neg_plans)
    return {
        "tree": {
            "sites": tuple(_planned_view(p) for p in pt.site_plans),
            "neg": _planned_view(pt.neg_plan),
            "est_site_rows": tuple(int(r) for r in pt.est_site_rows),
            "est_union_rows": int(pt.est_union_rows),
            "route": pt.route,
            "cost": float(pt.cost),
        },
        "site_jobs": tuple(_job_view(j) for j in tj.site_jobs),
        "neg_job": _job_view(tj.neg_job),
    }


# -- join_step_cost on a grid that crosses every step of the model -------

#: (left_rows, left_width, right_rows, right_width, n_pairs, cap_rows,
#:  out_width, max_capacity).  Cell 1's row counts (774,001 nodes /
#: 8,361,000 links: 7.2 M Member, 900 k Interacts) are among them.
MAX_CAP = 1 << 24
COST_GRID = (
    # one block: everything under 8 MiB
    (10, 1, 10, 2, 1, 14, 2, MAX_CAP),
    (10.0, 2, 7_200_000, 2, 1, 135.0, 3, MAX_CAP),
    (1_300, 2, 1_300, 2, 1, 1_300, 3, MAX_CAP),
    (64, 1, 4_096, 2, 1, 50_000.5, 2, MAX_CAP),
    # chunked: the resident set fits, the window does not
    (10_000, 2, 10_000, 2, 1, 400_000, 3, MAX_CAP),
    (100_000, 3, 50_000, 2, 2, 1_000_000, 3, MAX_CAP),
    (1_300, 2, 135_000, 2, 1, 3_000_000, 3, MAX_CAP),
    # the resident set alone is over 8 MiB: lowered, x4
    (900_000, 2, 7_200_000, 2, 1, 900_000, 3, MAX_CAP),
    (7_200_000, 2, 7_200_000, 2, 1, 30_000_000, 3, MAX_CAP),
    (774_001, 1, 8_361_000, 2, 1, 8_361_000, 2, MAX_CAP),
    (240_000, 2, 10, 2, 1, 10, 3, MAX_CAP),
    # more than 256 grid steps of the smallest chunk headroom leaves
    (200_000, 2, 10_000, 2, 1, 16_000_000, 3, MAX_CAP),
    (230_000, 2, 8_000, 4, 1, 12_000_000, 6, MAX_CAP),
    # the resident set leaves headroom for the smallest chunk, or not
    (231_700, 2, 10, 2, 1, 600, 3, MAX_CAP),
    (232_700, 2, 10, 2, 1, 600, 3, MAX_CAP),
    # capacity clamped by a small ceiling; widths of zero floor at one
    (5_000, 2, 5_000, 2, 1, 900_000, 3, 1 << 16),
    (0, 0, 0, 0, 0, 0, 0, MAX_CAP),
    # row counts past int32 are clamped before the byte model
    (float(2 ** 33), 2, 100, 2, 1, 1_000, 3, MAX_CAP),
)


# -- the recorded literals (parent commit f7a022d, TPU branch forced) ----
# PR 44 re-pinned the two all-variable cases (bio.allvar3, cell.allvar3):
# their second join shares two variables with the left and verifies a
# pair before it counts it, so its estimate and capacity seed are the
# rows of the join (155, 1666), no longer the candidates of its first
# variable (2336, 600000), and the step is priced on those rows with
# both tables whole (cost 587832 -> 193028.8, 21323712 -> 13228256);
# the order is what it was.

EXPECTED_PLANS = {'bio.allvar3': {'job': {'index_joins': (0, 0),
                         'join_caps': (1024, 512),
                         'term_caps': (256, 16, 16)},
                 'planned': {'cost': 193028.8,
                             'est_join_rows': (584, 155),
                             'est_term_rows': (146, 240, 240),
                             'join_cap_seeds': (1024, 512),
                             'method': 'dp',
                             'order': (2, 0, 1),
                             'route': 'fused'}},
 'bio.negated': {'job': {'index_joins': (-1,),
                         'join_caps': (128,),
                         'term_caps': (256, 16, 16)},
                 'planned': {'cost': 32736.0,
                             'est_join_rows': (68,),
                             'est_term_rows': (240, 4, 4),
                             'join_cap_seeds': (128,),
                             'method': 'ref_order',
                             'order': (0, 1, 2),
                             'route': 'fused'}},
 'bio.or3': {'neg_job': None,
             'site_jobs': ({'index_joins': (1,),
                            'join_caps': (128,),
                            'term_caps': (16, 16)},
                           {'index_joins': (1,),
                            'join_caps': (128,),
                            'term_caps': (16, 16)},
                           {'index_joins': (1,),
                            'join_caps': (128,),
                            'term_caps': (16, 16)}),
             'tree': {'cost': 97136.0,
                      'est_site_rows': (71, 68, 73),
                      'est_union_rows': 212,
                      'neg': None,
                      'route': 'fused_tree',
                      'sites': ({'cost': 31816.0,
                                 'est_join_rows': (71,),
                                 'est_term_rows': (4, 240),
                                 'join_cap_seeds': (128,),
                                 'method': 'ref_order',
                                 'order': (0, 1),
                                 'route': 'fused'},
                                {'cost': 31792.0,
                                 'est_join_rows': (68,),
                                 'est_term_rows': (4, 240),
                                 'join_cap_seeds': (128,),
                                 'method': 'ref_order',
                                 'order': (0, 1),
                                 'route': 'fused'},
                                {'cost': 31832.0,
                                 'est_join_rows': (73,),
                                 'est_term_rows': (4, 240),
                                 'join_cap_seeds': (128,),
                                 'method': 'ref_order',
                                 'order': (0, 1),
                                 'route': 'fused'})}},
 'bio.or_not': {'neg_job': {'index_joins': (1,),
                            'join_caps': (128,),
                            'term_caps': (16, 16)},
                'site_jobs': ({'index_joins': (1,),
                               'join_caps': (128,),
                               'term_caps': (16, 16)},
                              {'index_joins': (1,),
                               'join_caps': (128,),
                               'term_caps': (16, 16)}),
                'tree': {'cost': 96592.0,
                         'est_site_rows': (71, 73),
                         'est_union_rows': 144,
                         'neg': {'cost': 31792.0,
                                 'est_join_rows': (68,),
                                 'est_term_rows': (4, 240),
                                 'join_cap_seeds': (128,),
                                 'method': 'ref_order',
                                 'order': (0, 1),
                                 'route': 'fused'},
                         'route': 'fused_tree',
                         'sites': ({'cost': 31816.0,
                                    'est_join_rows': (71,),
                                    'est_term_rows': (4, 240),
                                    'join_cap_seeds': (128,),
                                    'method': 'ref_order',
                                    'order': (0, 1),
                                    'route': 'fused'},
                                   {'cost': 31832.0,
                                    'est_join_rows': (73,),
                                    'est_term_rows': (4, 240),
                                    'join_cap_seeds': (128,),
                                    'method': 'ref_order',
                                    'order': (0, 1),
                                    'route': 'fused'})}},
 'bio.star3': {'job': {'index_joins': (1, 1),
                       'join_caps': (8192, 131072),
                       'term_caps': (256, 16, 16)},
               'planned': {'cost': 4085832.0,
                           'est_join_rows': (4106, 73782),
                           'est_term_rows': (240, 240, 240),
                           'join_cap_seeds': (8192, 131072),
                           'method': 'dp',
                           'order': (0, 1, 2),
                           'route': 'fused'}},
 'bio.star4': {'job': {'index_joins': (1, 1, 1),
                       'join_caps': (8192, 131072, 2097152),
                       'term_caps': (256, 16, 16, 16)},
               'planned': {'cost': 29384008.0,
                           'est_join_rows': (4106, 73782, 1376918),
                           'est_term_rows': (240, 240, 240, 240),
                           'join_cap_seeds': (8192, 131072, 2097152),
                           'method': 'dp',
                           'order': (0, 1, 2, 3),
                           'route': 'fused'}},
 'bio_mesh8.or_not': {'neg_job': {'exch_caps': (0,),
                                  'index_joins': (1,),
                                  'join_caps': (64,),
                                  'term_caps': (16, 16)},
                      'site_jobs': ({'exch_caps': (0,),
                                     'index_joins': (1,),
                                     'join_caps': (64,),
                                     'term_caps': (16, 16)},
                                    {'exch_caps': (0,),
                                     'index_joins': (1,),
                                     'join_caps': (64,),
                                     'term_caps': (16, 16)}),
                      'tree': {'cost': 96592.0,
                               'est_site_rows': (71, 73),
                               'est_union_rows': 144,
                               'neg': {'cost': 31792.0,
                                       'est_join_rows': (68,),
                                       'est_term_rows': (4, 240),
                                       'join_cap_seeds': (64,),
                                       'method': 'ref_order',
                                       'order': (0, 1),
                                       'route': 'sharded'},
                               'route': 'sharded_tree_fused',
                               'sites': ({'cost': 31816.0,
                                          'est_join_rows': (71,),
                                          'est_term_rows': (4, 240),
                                          'join_cap_seeds': (64,),
                                          'method': 'ref_order',
                                          'order': (0, 1),
                                          'route': 'sharded'},
                                         {'cost': 31832.0,
                                          'est_join_rows': (73,),
                                          'est_term_rows': (4, 240),
                                          'join_cap_seeds': (64,),
                                          'method': 'ref_order',
                                          'order': (0, 1),
                                          'route': 'sharded'})}},
 'bio_mesh8.star3': {'job': {'exch_caps': (0, 0),
                             'index_joins': (1, 1),
                             'join_caps': (2048, 32768),
                             'term_caps': (64, 16, 16)},
                     'planned': {'cost': 4085832.0,
                                 'est_join_rows': (4106, 73782),
                                 'est_term_rows': (240, 240, 240),
                                 'join_cap_seeds': (2048, 32768),
                                 'method': 'dp',
                                 'order': (0, 1, 2),
                                 'route': 'sharded'}},
 'cell.allvar3': {'job': {'index_joins': (0, 0),
                          'join_caps': (65536, 4096),
                          'term_caps': (8192, 16, 16)},
                  'planned': {'cost': 13228256.0,
                              'est_join_rows': (60000, 1666),
                              'est_term_rows': (6000, 48000, 48000),
                              'join_cap_seeds': (65536, 4096),
                              'method': 'dp',
                              'order': (2, 0, 1),
                              'route': 'fused'}},
 'cell.grounded3': {'job': {'index_joins': (1, -1),
                            'join_caps': (2048, 64),
                            'term_caps': (16, 16, 16)},
                    'planned': {'cost': 2148760.0,
                                'est_join_rows': (1320, 2),
                                'est_term_rows': (10, 48000, 2),
                                'join_cap_seeds': (2048, 64),
                                'method': 'ref_order',
                                'order': (0, 1, 2),
                                'route': 'fused'}},
 'cell.shared2': {'job': {'index_joins': (1,),
                          'join_caps': (2048,),
                          'term_caps': (16, 16)},
                  'planned': {'cost': 2090664.0,
                              'est_join_rows': (1320,),
                              'est_term_rows': (10, 48000),
                              'join_cap_seeds': (2048,),
                              'method': 'ref_order',
                              'order': (0, 1),
                              'route': 'fused'}},
 'cell_mesh4.grounded3': {'job': {'exch_caps': (0, 0),
                                  'index_joins': (1, -1),
                                  'join_caps': (1024, 64),
                                  'term_caps': (16, 16, 16)},
                          'planned': {'cost': 2148760.0,
                                      'est_join_rows': (1320, 2),
                                      'est_term_rows': (10, 48000, 2),
                                      'join_cap_seeds': (1024, 64),
                                      'method': 'ref_order',
                                      'order': (0, 1, 2),
                                      'route': 'sharded'}},
 'cell_mesh4.shared2': {'job': {'exch_caps': (0,),
                                'index_joins': (1,),
                                'join_caps': (1024,),
                                'term_caps': (16, 16)},
                        'planned': {'cost': 2090664.0,
                                    'est_join_rows': (1320,),
                                    'est_term_rows': (10, 48000),
                                    'join_cap_seeds': (1024,),
                                    'method': 'ref_order',
                                    'order': (0, 1),
                                    'route': 'sharded'}},
 'skew.star3': {'job': {'index_joins': (1, 1),
                        'join_caps': (8192, 262144),
                        'term_caps': (512, 16, 16)},
                'planned': {'cost': 8819312.0,
                            'est_join_rows': (6212, 189090),
                            'est_term_rows': (360, 360, 360),
                            'join_cap_seeds': (8192, 262144),
                            'method': 'dp',
                            'order': (0, 1, 2),
                            'route': 'fused'}}}

EXPECTED_COSTS = (7152.0,
 921697268.0,
 288320.0,
 5255812.0,
 7410240.0,
 18298368.0,
 57481280.0,
 1431102848.0,
 5271194112.0,
 3652091328.0,
 34576760.0,
 222940160.0,
 4617371392.0,
 8397872.0,
 33701600.0,
 14027680.0,
 5888.0,
 309238034512.0)


# -- the tests -----------------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
def test_plan_identity(case, stores, monkeypatch):
    _clean_env(monkeypatch)
    store, build = CASES[case]
    db = stores(store)
    assert observe(db, build(db)) == EXPECTED_PLANS[case]


@pytest.mark.parametrize("at", range(len(COST_GRID)))
def test_join_step_cost_identity(at, monkeypatch):
    _clean_env(monkeypatch)
    assert pcost.join_step_cost(*COST_GRID[at]) == EXPECTED_COSTS[at]


if __name__ == "__main__":  # regenerate the literal blocks
    import pprint
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["DAS_TPU_XLA_CACHE"] = "0"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    get = _store_getter(_write_cell_kb(tempfile.mkdtemp(prefix="plan_id")))
    plans = {}
    for case, (store, build) in sorted(CASES.items()):
        db = get(store)
        plans[case] = observe(db, build(db))
    print("EXPECTED_PLANS = " + pprint.pformat(plans, width=76))
    print()
    print("EXPECTED_COSTS = " + pprint.pformat(
        tuple(pcost.join_step_cost(*a) for a in COST_GRID), width=76))
