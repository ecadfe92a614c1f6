"""The one general traffic generator.  A mix is a JSON file of
parameters under `benchmark/traffic/`; nothing here knows a mix by name.

    loop        "closed" (each client sends its next request when the
                last returns) or "open" (Poisson arrivals at `rate`/s,
                at most `clients` in flight, latency from the due time)
    clients     closed: concurrent clients; open: requests in flight
    generator_processes   load-generator child processes
    keys        {"distribution": "uniform", "repeat": false}: one seeded
                permutation of all keys dealt round-robin to the clients,
                so no key repeats inside a run;
                {"distribution": "zipf", "theta": t}: rank r drawn with
                p ~ r^-t, rank -> key through a seeded permutation
    queries     [{"shape": name, "per_block": n}, ..]: every block of
                sum(per_block) requests of a client holds exactly
                per_block of each shape, in seeded order — every seed
                sends the same set of sizes, in another order
    writes      null, or the commit side of the mix (cell.py Writer):
                {"writers": 1, "reads_per_write": r, "reader_lead": l,
                 "transaction": {"interacts": n, "members": n},
                 "readback": [shape, ..]}: the writer commits whenever
                answered reads >= r x commits issued, readers hold while
                answered reads > r x (acknowledged commits + l), so the
                share of commits is 1 / (1 + r) whatever the speeds;
                after each acknowledgement the writer reads `readback`
                on the gene it changed and must see every new row
    warmup      {"requests_per_client": n, "min_rounds": a,
                 "max_rounds": b}: a seeded prefix of the same traffic,
                on keys of its own, round after round until a round
                builds no XLA program.  With writes also
                "commits_per_round" (commits until one builds nothing)
                and "climb_commits": that many commits in the first
                round on the hottest key, each read back.  The program
                sizes a query's buffers from its key's degree, in
                powers of two, and keeps the largest size it has met for
                every later query of that shape: the climb takes the
                hottest key past the sizes the window can reach, so
                that the step up (a new XLA program) is paid in set-up

Everything is drawn from (seed, purpose, client): the same seed gives
the same requests.  numpy only — no jax, no das_tpu.
"""

from __future__ import annotations

import numpy as np

_PURPOSE = {"permutation": 1, "zipf": 2, "mix": 3, "arrivals": 4,
            "warm": 5, "writer": 6}


def rng_for(seed: int, purpose: str, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), _PURPOSE[purpose], int(stream)])


def key_permutation(seed: int, n_keys: int) -> np.ndarray:
    return rng_for(seed, "permutation").permutation(n_keys)


class ZipfRanks:
    """Ranks 0..n-1 with p(r) ~ (r+1)^-theta, by inverse CDF."""

    def __init__(self, n: int, theta: float):
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(theta)
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return np.minimum(np.searchsorted(self.cdf, rng.random(size)),
                          len(self.cdf) - 1)


def validate(traffic: dict) -> None:
    if traffic.get("loop") not in ("closed", "open"):
        raise ValueError(f"traffic loop {traffic.get('loop')!r}")
    if traffic["loop"] == "open" and not traffic.get("rate"):
        raise ValueError("an open loop needs a rate")
    if int(traffic.get("clients", 0)) < 1:
        raise ValueError("traffic needs clients >= 1")
    dist = traffic.get("keys", {}).get("distribution")
    if dist not in ("uniform", "zipf"):
        raise ValueError(f"key distribution {dist!r}")
    if not traffic.get("queries"):
        raise ValueError("traffic needs queries")
    for q in traffic["queries"]:
        if int(q.get("per_block", 0)) < 1 or not q.get("shape"):
            raise ValueError(f"query share {q!r}")


class ClientPlan:
    """The (shape, key) sequence of one client, in chunks.

    `phase` "warm" draws from keys (uniform: the far end of the
    permutation; zipf: a stream of its own) that differ from the
    window's, so the warm-up fills no cache entry the window would hit
    by design of the mix."""

    CHUNK = 512

    def __init__(self, traffic: dict, n_keys: int, seed: int, client: int,
                 n_clients: int, phase: str = "window", permutation=None):
        validate(traffic)
        self.shapes = [q["shape"] for q in traffic["queries"]
                       for _ in range(int(q["per_block"]))]
        self.keys_spec = traffic["keys"]
        self.n_keys, self.client, self.n_clients = n_keys, client, n_clients
        warm = phase == "warm"
        self.mix_rng = rng_for(seed, "warm" if warm else "mix", client)
        perm = (permutation if permutation is not None
                else key_permutation(seed, n_keys))
        self.repeats = 0
        if self.keys_spec["distribution"] == "uniform":
            mine = (perm[::-1] if warm else perm)[client::n_clients]
            self._mine, self._at = mine, 0
        else:
            self.perm = perm
            self.zipf = ZipfRanks(n_keys, self.keys_spec["theta"])
            self.key_rng = rng_for(seed, "zipf",
                                   client + (1 << 20 if warm else 0))
        self._buf = []

    def _keys(self, n: int) -> np.ndarray:
        if self.keys_spec["distribution"] == "zipf":
            return self.perm[self.zipf.draw(self.key_rng, n)]
        out = np.empty(n, dtype=np.int64)
        for i in range(n):
            if self._at >= len(self._mine):
                # more requests than keys (a rehearsal store): wrap, and
                # say so — the caller prints `repeats`
                self._at = 0
                self.repeats += 1
            out[i] = self._mine[self._at]
            self._at += 1
        return out

    def _refill(self) -> None:
        blocks = max(1, self.CHUNK // len(self.shapes))
        shapes = []
        for _ in range(blocks):
            order = self.mix_rng.permutation(len(self.shapes))
            shapes.extend(self.shapes[i] for i in order)
        keys = self._keys(len(shapes))
        self._buf = list(zip(shapes, keys.tolist()))[::-1]

    def next(self):
        if not self._buf:
            self._refill()
        return self._buf.pop()


def open_schedule(rate: float, seconds: float, seed: int, stream: int,
                  share: float = 1.0) -> np.ndarray:
    """Due times (seconds from the window's start) of one generator
    process's share of a Poisson stream of `rate`/s."""
    lam = float(rate) * share
    rng = rng_for(seed, "arrivals", stream)
    n = int(lam * seconds * 1.5) + 16
    due = np.cumsum(rng.exponential(1.0 / lam, size=n))
    while due[-1] < seconds:
        more = np.cumsum(rng.exponential(1.0 / lam, size=n)) + due[-1]
        due = np.concatenate([due, more])
    return due[due < seconds]
