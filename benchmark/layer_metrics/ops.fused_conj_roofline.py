"""ops: how close the fused conjunction programs come to the HBM
roofline.  The least time the chip could take to move the bytes the
queries of the traced slice must move (`hbm_model`, from table sizes and
the answers' row counts) at the device's peak bandwidth (`peaks.json`),
as a share of the device time those programs took.  Bandwidth-bound by
construction: the programs are probes, gathers and sorts, no matmul."""

import json
import os

from benchmark.harness import devtrace, hbm_model


def read(spans, counters, trace, window):
    if trace is None or not devtrace.device_planes(trace):
        return None
    seconds = devtrace.module_seconds(trace)
    if seconds <= 0:
        return None
    with open(os.path.join(window["bench_dir"], "harness", "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]
    if window["device_kind"] not in peaks:
        raise KeyError(f"no peak for device {window['device_kind']!r}")
    peak = peaks[window["device_kind"]]["hbm_bytes_per_s"]
    moved = sum(
        hbm_model.query_bytes(shape, rows, window["store"])
        for shape, per_query in window["rows_by_shape_in_slice"].items()
        for rows in per_query)
    return 100.0 * (moved / peak) / seconds
