"""DL004 bad: counting sites using keys the registry never declared,
a dead registry key, and a dict literal drifting from the registry."""

DISPATCH_KEYS = ("fixture_fused", "fixture_dead")
ROUTE_KEYS = ("fixture_fused",)

# drifted literal: missing fixture_dead, smuggles fixture_extra
DISPATCH_COUNTS = {"fixture_fused": 0, "fixture_extra": 0}
ROUTE_COUNTS = {k: 0 for k in ROUTE_KEYS}


def record_dispatch(kind, n=1):
    DISPATCH_COUNTS[kind] = DISPATCH_COUNTS.get(kind, 0) + n


def run(route_ok):
    record_dispatch("fixture_fused")
    record_dispatch("fixture_kernal")        # the canonical typo
    route = "fixture_fused" if route_ok else "fixture_mystery"
    ROUTE_COUNTS[route] += 1                 # resolves both literals
