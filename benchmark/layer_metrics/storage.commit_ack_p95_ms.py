"""storage: 95th percentile of `commit_transaction` call -> return under
the tenant lock (10 expressions, WAL record fsynced before the swap),
over the commits of the window.  A window holds some twenty commits, so
this is close to their maximum; it stands here without a bound, and the
end-to-end commit metric is `commit_visible_p50_ms`."""

from benchmark.harness import stats


def read(spans, counters, trace, window):
    if not window["commit_ms"]:
        return None
    return stats.percentile(window["commit_ms"], 0.95)
