"""Closed- and open-loop arithmetic, driven without a server."""

import threading
import time

from benchmark.harness import loadgen, stats


class Plan:
    def __init__(self, client=0):
        self.client, self.n = client, 0

    def next(self):
        self.n += 1
        return "q", self.n


def test_closed_loop_sends_the_next_when_the_last_returned():
    def call(shape, key):
        time.sleep(0.02)
        return True, ""

    t0 = time.monotonic() + 0.05
    recs = loadgen.closed_loop([Plan(0), Plan(1)], call, t0, 0.5)
    for client in (0, 1):
        mine = [r for r in recs if r[0] == client]
        assert 15 <= len(mine) <= 26          # ~ 0.5 s / 20 ms
        for a, b in zip(mine, mine[1:]):
            assert b[5] >= a[6]               # sent after the last returned
        assert mine[0][5] >= t0
        assert all(r[5] < t0 + 0.5 for r in mine)


def test_open_loop_counts_latency_from_the_due_time():
    """One sender, 30 ms a call, a request due every 10 ms: the k-th
    waits for the k-1 before it, and its latency says so."""
    def call(shape, key):
        time.sleep(0.03)
        return True, ""

    due = [0.01 * k for k in range(10)]
    t0 = time.monotonic() + 0.05
    recs = loadgen.open_loop(Plan(), due, call, t0, in_flight=1)
    assert [r[1] for r in recs] == list(range(10))
    latency = [r[6] - r[4] for r in recs]
    lateness = [r[5] - r[4] for r in recs]
    assert latency[0] < 0.06
    assert latency[-1] > 0.18                 # 10 x 30 ms - 90 ms
    assert lateness[-1] > 0.15                # and the generator says it ran late
    assert stats.percentile(latency, 0.5) > 0.08
    assert all(abs(r[4] - (t0 + d)) < 1e-9 for r, d in zip(recs, due))


def test_open_loop_with_room_keeps_to_the_schedule():
    def call(shape, key):
        time.sleep(0.005)
        return True, ""

    due = [0.02 * k for k in range(10)]
    t0 = time.monotonic() + 0.05
    recs = loadgen.open_loop(Plan(), due, call, t0, in_flight=4)
    assert max(r[5] - r[4] for r in recs) < 0.02


def test_pace_holds_readers_at_the_mix(tmp_path):
    path = str(tmp_path / "pace.bin")
    m = loadgen.Pace.create(path)
    pace = loadgen.Pace(path, 0, 1, reads_per_write=19, lead=2)
    for _ in range(38):
        assert pace.may_send()
        pace.answered()
    assert pace.may_send()                    # 38 <= 19 x (0 + 2)
    pace.answered()
    assert not pace.may_send()                # 39 > 38: hold for a commit
    assert not pace.wait(time.monotonic() + 0.01)
    threading.Timer(0.02, lambda: m.__setitem__(0, 1)).start()
    assert pace.wait(time.monotonic() + 1.0)  # released by the commit


def test_finish_record_digests_and_keeps_few_rows():
    h = "0" * 32
    msg = "{{'$2': '%s', '$3': '%s'}}" % (h, h.replace("0", "1"))
    rec = loadgen.finish_record([0, 0, "q", 5, 1.0, 1.0, 2.0, True, msg])
    assert rec["ok"] and rec["n"] == 1 and len(rec["d"]) == 32
    assert rec["rows"] == [f"$2={h},$3={'1' * 32}"]
    empty = loadgen.finish_record([0, 1, "q", 5, 1.0, 1.0, 2.0, True, ""])
    assert empty["n"] == 0
    bad = loadgen.finish_record([0, 2, "q", 5, 1.0, 1.0, 2.0, True, "NOT {}"])
    assert not bad["ok"]
    err = loadgen.finish_record([0, 3, "q", 5, 1.0, 1.0, 2.0, False, "boom"])
    assert not err["ok"] and err["err"] == "boom"
