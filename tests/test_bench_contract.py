"""The driver-facing bench output contract (VERDICT r04 item 1): the
final stdout line must be ONE complete JSON line that fits a 2000-char
tail capture with margin.  compact_headline is the pure function behind
it — pinned here so a field addition cannot silently outgrow the tail."""

import json
import sys

sys.path.insert(0, ".")

import bench


def _full_extra():
    return {
        "platform": "tpu",
        "device_only_method": "host_visible_minus_rtt",
        "host_visible_p50_ms": 99999.999,
        "transport_rtt_ms": 99999.999,
        "batched_ms_per_query": 99999.999,
        "batched_wide_ms_per_query": 99999.999,
        "served_ms_per_query": 99999.999,
        "sharded_serving": {
            "n_shards": 999,
            "clients": 999,
            "distinct_queries": 999,
            "per_client": 999,
            "interpret": True,
            "serial_qps": 999999.9,
            "pipelined_qps": 999999.9,
            "pipeline_speedup": 99.999,
            "inflight_peak": 999,
            "served_ms_per_query": 99999.999,
            "time_to_first_row_ms": 99999.999,
            "effective_depth": 999,
            "speculative_dispatches": 9_999_999,
            "early_settles": 9_999_999,
            "queue_rejections": 9_999_999,
            "open_loop_p50_ms": 99999.999,
            "open_loop_p95_ms": 99999.999,
            "open_loop_p99_ms": 99999.999,
            "latency_buckets": [[99999.999, 999_999]] * 12,
        },
        "serving": {
            "clients": 999,
            "distinct_queries": 999,
            "per_client": 999,
            "interpret": True,
            "serial_qps": 999999.9,
            "pipelined_qps": 999999.9,
            "pipeline_depth": 99,
            "pipeline_speedup": 99.999,
            "inflight_peak": 999,
            "max_batch": 999,
            "served_ms_per_query": 99999.999,
            "time_to_first_row_ms": 99999.999,
            "effective_depth": 999,
            "pipeline_depth_max": 999,
            "rtt_ewma_ms": 99999.9999,
            "speculative_dispatches": 9_999_999,
            "early_settles": 9_999_999,
            "queue_rejections": 9_999_999,
            "open_loop_p50_ms": 99999.999,
            "open_loop_p95_ms": 99999.999,
            "open_loop_p99_ms": 99999.999,
            "latency_buckets": [[99999.999, 999_999]] * 12,
            "cached_qps": 999999.9,
            "cache_hit_rate": 1.0,
            "cache_hit_ms": 99999.9999,
            "device_path_ms": 99999.9999,
            "cache_speedup": 99999.9,
        },
        "chaos": {
            "clients": 999,
            "per_client": 999,
            "fault_spec": "seed=17;sites=settle_fetch;rate=0.05;max=999",
            "interpret": True,
            "clean_qps": 999999.9,
            "chaos_qps": 999999.9,
            "chaos_qps_ratio": 9.999,
            "typed_errors": 999_999,
            "answered": 999_999,
            "injected": {"settle_fetch": 999_999},
            "deadline_ms": 999,
            "deadline_miss_rate": 1.0,
            "breaker_trips": 999_999,
            "breaker_recoveries": 999_999,
            "breaker_recovery_ms": 99999.9,
        },
        "planner_ab": {
            "clauses": 999,
            "skew": 9.9,
            "planner_first_contact_ms": 99999.999,
            "greedy_first_contact_ms": 99999.999,
            "planner_programs": 999_999,
            "greedy_programs": 999_999,
            "planner_ms": 99999.999,
            "greedy_ms": 99999.999,
            "planner_route": "fused",
            "retry_rounds_avoided": 999_999,
            "parity": True,
            "planner_stats": {
                "planned": 9_999_999, "greedy": 9_999_999,
                "round0": 9_999_999, "retries": 9_999_999,
                "est_rows": 9_999_999_999, "actual_rows": 9_999_999_999,
                "actual_vs_est_ratio": 9999.9999,
            },
        },
        "tree_fused_ab": {
            "branches": [9, 9, 9],
            "queries": 9,
            "interpret": True,
            "fused_first_contact_ms": 99999.999,
            "tree_first_contact_ms": 99999.999,
            "fused_programs": 999_999,
            "tree_programs": 999_999,
            "fused_ms": 99999.999,
            "tree_ms": 99999.999,
            "tree_fused_route": "fused_tree",
            "tree_programs_avoided": 999_999,
            "parity": True,
        },
        "durability": {
            "interpret": True,
            "commits": 999,
            "snapshot_s": 99999.999,
            "rebuild_s": 99999.999,
            "restore_s": 99999.999,
            "restore_vs_rebuild": 99999.99,
            "wal_records_replayed": 999_999,
            "wal_replay_commits_per_s": 999999.9,
            "chaos_crash_typed": True,
            "chaos_recovery_ms": 99999.9,
        },
        "programs": {
            "enabled": True,
            "compiles": 999_999,
            "compile_s": 99999.999,
            "calls": 9_999_999,
            "ledger_hits": 9_999_999,
            "hit_rate": 1.0,
            "cold_start_s": 99999.999,
            "persistent_cache_hits": 999_999,
            "errors": 999_999,
            "launches": 9_999_999,
            "entries": 9_999,
            "budget_vs_actual": {"fused": 9999.9999, "sharded": 9999.9999},
        },
        "kb_nodes": 999_999_999,
        "kb_links": 99_999_999_999,
        "matches": 999_999_999,
        "flybase_scale": {
            "kb_links": 99_999_999_999,
            "flybase_scale_factor": 1.0,
            "ingest_expressions_per_s": 999_999_999,
            "sequential_p50_ms": 99999.999,
            "sequential_device_only_ms": 99999.999,
            "batched_ms_per_query": 99999.999,
            "batched_fresh_ms_per_query": 99999.999,
            "miner_ms_per_link": 99999.99,
            "commit_10_expressions_steady_s": 99999.9999,
            "error": "x" * 500,  # must be truncated to 16
        },
    }


def test_compact_headline_fits_tail_with_margin():
    result = {
        "metric": "bio_atomspace 3-var conjunctive query latency (device-only)",
        "value": 99999.999,
        "unit": "ms",
        "vs_baseline": 9_999_999.9,
        "extra": _full_extra(),
    }
    line = json.dumps(bench.compact_headline(result))
    assert len(line) < 1500, f"compact line {len(line)} bytes"
    parsed = json.loads(line)
    assert parsed["metric"] == result["metric"]
    assert len(parsed["extra"]["flybase"]["error"]) == 16
    # the serving pipeline + result-cache record must survive compaction
    # (ISSUE 2: pipelined-vs-serial qps, depth, hit rate, hit-vs-device ms)
    assert parsed["extra"]["serving_qps"] == [999999.9, 999999.9]
    assert parsed["extra"]["pipeline_depth"] == 99
    assert parsed["extra"]["cache_hit_rate"] == 1.0
    assert parsed["extra"]["cache_vs_device_ms"] == [99999.9999, 99999.9999]
    # the sharded serving parity record must survive compaction (ISSUE 3:
    # mesh pipelined-vs-serial qps)
    assert parsed["extra"]["sharded_qps"] == [999999.9, 999999.9]
    # the 256-client open-loop record must survive compaction (ISSUE 6:
    # ms/query, time-to-first-row, the adaptive window's reached depth)
    assert parsed["extra"]["open_loop_ms_per_query"] == 99999.999
    assert parsed["extra"]["time_to_first_row_ms"] == 99999.999
    assert parsed["extra"]["effective_depth"] == 999
    # the histogram-derived open-loop tail must survive compaction
    # (ISSUE 12: p99 from the obs log-bucket histogram layer; p50/p95
    # and the bucket vectors stay in the full record)
    assert parsed["extra"]["open_loop_p99_ms"] == 99999.999
    # the cost-based planner A/B must survive compaction (ISSUE 8: the
    # planner's chosen route, warm [planner, greedy] ms, and the
    # capacity-retry compiles the costed seeds eliminated)
    assert parsed["extra"]["planner_route"] == "fused"
    assert parsed["extra"]["planner_vs_greedy_ms"] == [99999.999, 99999.999]
    assert parsed["extra"]["retry_rounds_avoided"] == 999_999
    # the whole-tree fused A/B must survive compaction (ISSUE 10: the
    # whole-tree route, warm [fused, tree] ms, and the per-site
    # dispatch/settle round trips the one-program route eliminated)
    assert parsed["extra"]["tree_fused_route"] == "fused_tree"
    assert parsed["extra"]["tree_fused_vs_tree_ms"] == [99999.999, 99999.999]
    assert parsed["extra"]["tree_programs_avoided"] == 999_999
    # the chaos serving record must survive compaction (ISSUE 13:
    # degraded-qps ratio at a fixed injected fault rate + the breaker
    # recoveries the half-open probes achieved)
    assert parsed["extra"]["chaos_qps_ratio"] == 9.999
    assert parsed["extra"]["breaker_recoveries"] == 999_999
    # the program-ledger headline must survive compaction (ISSUE 14:
    # total XLA compile seconds; the decomposition stays in the full
    # record's `programs` snapshot + per-section fields)
    assert parsed["extra"]["compile_s"] == 99999.999
    # the durability headline must survive compaction (ISSUE 15:
    # verified warm-restore wall seconds; the rebuild arm, WAL replay
    # throughput and chaos-recovery wall time stay in the full record)
    assert parsed["extra"]["restore_s"] == 99999.999


def test_compact_headline_minimal_and_null_record():
    minimal = {"metric": "m", "value": 1, "unit": "ms", "vs_baseline": 2}
    line = json.dumps(bench.compact_headline(minimal, None))
    parsed = json.loads(line)
    assert parsed["extra"]["full_record"] is None
    assert parsed["extra"]["flybase"] is None
    assert len(line) < 1500
