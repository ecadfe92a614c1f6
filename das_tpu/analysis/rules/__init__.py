"""Rule modules register themselves with core.register at import time."""

from das_tpu.analysis.rules import (  # noqa: F401
    dl001_host_sync,
    dl002_plan_sig,
    dl003_env_registry,
    dl004_counters,
    dl006_locks,
    dl007_cache_guard,
    dl008_planner_routes,
    dl009_collectives,
    dl010_transitive_sync,
    dl012_retrace,
    dl013_fetch_sites,
    dl014_obs_registry,
    dl015_fault_sites,
    dl016_proflog_sites,
    dl017_durability,
)
