"""DL004 good: every counting literal is declared, every key counted,
dicts built from the registry.  (The test passes no tests-dir for the
fixture runs, so the referenced-by-a-test leg is exercised on the real
tree instead.)"""

DISPATCH_KEYS = ("fixture_fused", "fixture_sharded")
ROUTE_KEYS = ("fixture_fused", "fixture_staged")

DISPATCH_COUNTS = {k: 0 for k in DISPATCH_KEYS}
ROUTE_COUNTS = {k: 0 for k in ROUTE_KEYS}


def record_dispatch(kind, n=1):
    DISPATCH_COUNTS[kind] = DISPATCH_COUNTS.get(kind, 0) + n


def run(tiled, fused):
    record_dispatch("fixture_fused")
    if tiled:
        record_dispatch("fixture_sharded")
    route = "fixture_fused" if fused else "fixture_staged"
    ROUTE_COUNTS[route] += 1
