"""executor: XLA programs built inside the window — each one a stall in
the served path.  Counts JAX's `backend_compile_duration` events, which
fire once per program whether the persistent cache or the compiler
supplied it.  The warm-up is sized so that this reads 0."""


def read(spans, counters, trace, window):
    return counters.get("built.builds", 0)
