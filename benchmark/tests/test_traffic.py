"""The general traffic generator: the same seed gives the same requests,
every seed the same set of sizes, keys as the mix file says."""

import json
import os

import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.harness.spec import BENCH_DIR


def mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as fh:
        return json.load(fh)


def take(plan, n):
    return [plan.next() for _ in range(n)]


@pytest.mark.parametrize("name", ["uniform-closed", "mixed95-closed"])
def test_same_seed_same_requests_other_seed_other(name):
    tr = mix(name)
    a = take(traffic.ClientPlan(tr, 5000, 2**31 + 5, 3, 64), 200)
    b = take(traffic.ClientPlan(tr, 5000, 2**31 + 5, 3, 64), 200)
    c = take(traffic.ClientPlan(tr, 5000, 2**31 + 6, 3, 64), 200)
    assert a == b and a != c


def test_uniform_without_repeats_across_clients_and_warm_keys_apart():
    tr = mix("uniform-closed")
    n_keys, n_clients = 64 * 40, 64
    seen = []
    for c in range(n_clients):
        seen += [k for _, k in take(
            traffic.ClientPlan(tr, n_keys, 9, c, n_clients), 30)]
    assert len(seen) == len(set(seen))
    warm = [k for c in range(n_clients) for _, k in take(
        traffic.ClientPlan(tr, n_keys, 9, c, n_clients, "warm"), 5)]
    assert not set(warm) & set(seen)


def test_every_block_holds_the_same_set_of_shapes():
    tr = mix("uniform-closed")
    block = sum(q["per_block"] for q in tr["queries"])
    for seed in (1, 2, 3):
        shapes = [s for s, _ in take(
            traffic.ClientPlan(tr, 10000, seed, 0, 64), 10 * block)]
        for i in range(0, len(shapes), block):
            assert sorted(shapes[i:i + block]) == sorted(
                q["shape"] for q in tr["queries"]
                for _ in range(q["per_block"]))


def test_wrapping_a_small_store_is_counted():
    tr = mix("uniform-closed")
    plan = traffic.ClientPlan(tr, 64, 1, 0, 64)     # one key per client
    keys = [k for _, k in take(plan, 3)]
    assert len(set(keys)) == 1 and plan.repeats >= 2   # drawn in chunks


def test_zipf_ranks_follow_the_law():
    z = traffic.ZipfRanks(100_000, 0.99)
    r = z.draw(np.random.default_rng(0), 200_000)
    top = (r == 0).mean()
    want = 1.0 / (np.arange(1, 100_001) ** -0.99).sum()
    assert abs(top - want) < 0.005
    assert r.max() < 100_000


def test_open_schedule_is_poisson_at_the_rate():
    due = traffic.open_schedule(500.0, 20.0, 7, 0)
    assert abs(len(due) - 10_000) < 400
    assert (np.diff(due) > 0).all() and due[-1] < 20.0
    half = traffic.open_schedule(500.0, 20.0, 7, 1, share=0.5)
    assert abs(len(half) - 5_000) < 300
    assert np.array_equal(due, traffic.open_schedule(500.0, 20.0, 7, 0))


@pytest.mark.parametrize("bad", [
    {"loop": "spiral"}, {"loop": "open", "clients": 4},
    {"loop": "closed", "clients": 0},
])
def test_a_bad_mix_is_refused(bad):
    tr = dict(mix("uniform-closed"), **bad)
    with pytest.raises(ValueError):
        traffic.validate(tr)
