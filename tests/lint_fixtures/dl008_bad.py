"""DL008 bad: a planner emitting a route the registry never declared,
an undeclared planner counter key, a dead registry key, and a drifted
PLANNER_COUNTS literal."""

ROUTE_KEYS = ("fixture_fused", "fixture_sharded")
PLANNER_KEYS = ("fixture_planned", "fixture_dead")

# drifted literal: missing fixture_dead, smuggles fixture_extra
PLANNER_COUNTS = {"fixture_planned": 0, "fixture_extra": 0}


class PlannedProgram:
    def __init__(self, route):
        self.route = route


def plan(single):
    route = "fixture_fused" if single else "fixture_warp"  # undeclared
    PLANNER_COUNTS["fixture_planned"] += 1
    PLANNER_COUNTS["fixture_mystery"] += 1               # undeclared key
    return PlannedProgram(route="fixture_hyperspace")    # undeclared
