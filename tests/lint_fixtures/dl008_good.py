"""DL008 good: every planner route literal is declared in ROUTE_KEYS,
every PLANNER_COUNTS key declared and counted, dict built from the
registry."""

ROUTE_KEYS = ("fixture_fused", "fixture_sharded")
PLANNER_KEYS = ("fixture_planned", "fixture_dp")

PLANNER_COUNTS = {k: 0 for k in PLANNER_KEYS}


class PlannedProgram:
    def __init__(self, route):
        self.route = route


def plan(single, exact):
    route = "fixture_fused" if single else "fixture_sharded"
    method = "fixture_dp" if exact else "fixture_planned"
    PLANNER_COUNTS[method] += 1
    PLANNER_COUNTS["fixture_planned"] += 0  # both keys have static sites
    return PlannedProgram(route=route)
