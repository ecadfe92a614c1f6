"""coalescer (host, one interpreter): share of the wall time of spans
that never block (`serve.plan`, `exec.materialize`, `exec.format`: pure
Python and numpy on the coalescer worker) in which the thread was not
on a CPU: 1 - sum(cpu_ms) / sum(wall).  With nothing to wait for but the
interpreter lock, that is the wait for it."""

NAMES = ("serve.plan", "exec.materialize", "exec.format")


def read(spans, counters, trace, window):
    wall = cpu = 0.0
    for s in spans:
        if s["name"] in NAMES and s["phase"] == "X" \
                and "cpu_ms" in s["attrs"]:
            wall += s["dur"] * 1e3
            cpu += s["attrs"]["cpu_ms"]
    if wall <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - cpu / wall)
