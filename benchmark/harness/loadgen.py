"""Load-generator child process.

Started by `cell.py` with `JAX_PLATFORMS=cpu`: it never asks for a
device, so the one process that holds the chip is the server's.  It
speaks to the server only through `das_tpu.service.client.DasClient`
over localhost, and to its parent through JSON lines: the spec on the
first line of stdin, then commands

    {"cmd": "warm", "requests": n}      -> {"ev": "warm_done", ...}
    {"cmd": "run", "t0": mono, "seconds": s} -> {"ev": "run_done", ...}
    {"cmd": "quit"}

`t0` is on `time.monotonic()`, which all processes of a machine share.
Answers are kept raw while the window is open and brought to canonical
rows + digest after it has closed; the records go to a file.

The loops are written against a `call(shape, key) -> (ok, msg)`
function so that the tests drive them without a server.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import numpy as np  # noqa: E402

from benchmark.harness import traffic as traffic_mod  # noqa: E402
from benchmark.reference.plain import canonical_answer, digest  # noqa: E402

#: rows of an answer kept in full in the record (beyond it, the digest)
KEEP_ROWS = 16


class Pace:
    """The read side of a mix with writes: shared counters in a small
    memory-mapped file.  slot 0 = commits acknowledged; slot 8+i = reads
    answered by generator process i.  Readers hold while
    answered > reads_per_write * (acknowledged + lead)."""

    SLOTS = 64

    def __init__(self, path: str, proc_index: int, n_procs: int,
                 reads_per_write: int, lead: int):
        self.m = np.memmap(path, dtype=np.int64, mode="r+",
                           shape=(self.SLOTS,))
        self.proc_index, self.n_procs = proc_index, n_procs
        self.reads_per_write, self.lead = reads_per_write, lead
        self._lock = threading.Lock()
        self._mine = 0

    @staticmethod
    def create(path: str) -> np.memmap:
        m = np.memmap(path, dtype=np.int64, mode="w+", shape=(Pace.SLOTS,))
        m[:] = 0
        m.flush()
        return m

    def answered(self) -> None:
        with self._lock:
            self._mine += 1
            self.m[8 + self.proc_index] = self._mine

    def may_send(self) -> bool:
        total = int(self.m[8:8 + self.n_procs].sum())
        return total <= self.reads_per_write * (int(self.m[0]) + self.lead)

    def wait(self, deadline: float) -> bool:
        """False when the window closed while holding."""
        while not self.may_send():
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.0005)
        return True


def closed_loop(plans, call, t0: float, seconds: float, pace=None,
                clock=time.monotonic, sleep=time.sleep):
    """One thread per plan; each sends its next request when the last
    returned, until the window closes.  Returns the records
    [client, seq, shape, key, t_due, t_sent, t_recv, ok, msg]."""
    t_end = t0 + seconds
    out = [[] for _ in plans]

    def client(i, plan):
        while clock() < t0:
            sleep(min(0.01, max(0.0, t0 - clock())))
        seq = 0
        while clock() < t_end:
            if pace is not None and not pace.wait(t_end):
                break
            shape, key = plan.next()
            sent = clock()
            ok, msg = call(shape, key)
            recv = clock()
            out[i].append([plan.client, seq, shape, key, sent, sent, recv,
                           ok, msg])
            if pace is not None and ok:
                pace.answered()
            seq += 1

    threads = [threading.Thread(target=client, args=(i, p), daemon=True)
               for i, p in enumerate(plans)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return [r for per in out for r in per]


def open_loop(plan, due, call, t0: float, in_flight: int,
              clock=time.monotonic, sleep=time.sleep):
    """Requests fall due on the schedule `due` (seconds from t0) whatever
    the server does.  A dispatcher hands each to one of `in_flight`
    senders when it is due; with all of them busy the request waits, and
    its latency still counts from the due time.  Records as closed_loop:
    t_due is the schedule's, t_sent when it really left."""
    work: queue.Queue = queue.Queue()
    out = []
    lock = threading.Lock()

    def sender():
        while True:
            item = work.get()
            if item is None:
                return
            seq, shape, key, t_due = item
            sent = clock()
            ok, msg = call(shape, key)
            recv = clock()
            with lock:
                out.append([plan.client, seq, shape, key, t_due, sent, recv,
                            ok, msg])

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(in_flight)]
    for th in threads:
        th.start()
    for seq, offset in enumerate(due):
        t_due = t0 + float(offset)
        while True:
            wait = t_due - clock()
            if wait <= 0:
                break
            sleep(min(wait, 0.01))
        shape, key = plan.next()
        work.put((seq, shape, key, t_due))
    for _ in threads:
        work.put(None)
    for th in threads:
        th.join()
    out.sort(key=lambda r: r[1])
    return out


def finish_record(rec) -> dict:
    """Raw record -> what the parent verifies: canonical rows' digest,
    the row count, the rows themselves when few."""
    client, seq, shape, key, due, sent, recv, ok, msg = rec
    out = {"c": client, "i": seq, "shape": shape, "key": key,
           "due": due, "sent": sent, "recv": recv, "ok": bool(ok)}
    if not ok:
        out["err"] = str(msg)[:300]
        return out
    rows = canonical_answer(msg)
    if rows is None:
        out["ok"] = False
        out["err"] = "unparsable answer: " + str(msg)[:200]
        return out
    out["n"] = len(rows)
    out["d"] = digest(rows)
    if len(rows) <= KEEP_ROWS:
        out["rows"] = rows
    return out


def main() -> int:
    # imported before the spec arrives: the parent starts its children
    # early, so this import overlaps the parent's own set-up
    from das_tpu.service.client import DasClient

    spec = json.loads(sys.stdin.readline())
    client = DasClient(port=spec["port"])
    templates = spec["queries"]
    key_format = spec["key_format"]
    token = spec["token"]

    def call(shape, key):
        try:
            reply = client.call(
                "query", key=token,
                query=templates[shape].format(key=key_format.format(key)),
                output_format="HANDLE")
        except Exception as exc:  # noqa: BLE001 — a failed RPC is a failed request
            return False, f"{type(exc).__name__}: {exc}"
        return reply["success"], reply["msg"]

    traffic = spec["traffic"]
    perm = traffic_mod.key_permutation(spec["seed"], spec["n_keys"])
    n_clients = spec["n_clients"]

    def plans(phase):
        return [traffic_mod.ClientPlan(traffic, spec["n_keys"], spec["seed"],
                                       c, n_clients, phase, permutation=perm)
                for c in spec["clients"]]

    pace = None
    if spec.get("pace"):
        p = spec["pace"]
        pace = Pace(p["file"], spec["proc_index"], spec["n_procs"],
                    p["reads_per_write"], p["lead"])

    def say(**fields):
        sys.stdout.write(json.dumps(fields) + "\n")
        sys.stdout.flush()

    warm_plans = plans("warm")
    say(ev="ready", pid=os.getpid())
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "quit":
            break
        if cmd["cmd"] == "warm":
            n = int(cmd["requests"])
            errors, worst = [], [0.0]

            def warm_client(plan):
                for _ in range(n):
                    shape, key = plan.next()
                    t = time.monotonic()
                    ok, msg = call(shape, key)
                    worst[0] = max(worst[0], time.monotonic() - t)
                    if not ok:
                        errors.append(str(msg)[:300])

            # the plans carry on from round to round: a later round
            # sends keys the earlier ones did not
            ths = [threading.Thread(target=warm_client, args=(p,))
                   for p in warm_plans]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            say(ev="warm_done", sent=n * len(ths), failed=len(errors),
                errors=errors[:3], worst_ms=worst[0] * 1e3)
        elif cmd["cmd"] == "run":
            t0, seconds = float(cmd["t0"]), float(cmd["seconds"])
            cpu0 = time.process_time()
            if traffic["loop"] == "closed":
                records = closed_loop(plans("window"), call, t0, seconds,
                                      pace=pace)
            else:
                plan = plans("window")[0]
                due = traffic_mod.open_schedule(
                    traffic["rate"], seconds, spec["seed"],
                    spec["proc_index"], share=1.0 / spec["n_procs"])
                records = open_loop(plan, due, call, t0,
                                    max(1, len(spec["clients"])))
            cpu = time.process_time() - cpu0
            path = os.path.join(spec["workdir"],
                                f"loadgen_{spec['proc_index']}.jsonl")
            with open(path, "w") as fh:
                for rec in records:
                    fh.write(json.dumps(finish_record(rec)) + "\n")
            say(ev="run_done", file=path, records=len(records),
                cpu_share=cpu / seconds)
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
