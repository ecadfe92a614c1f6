"""mesh: how close the fused mesh programs (`das_sharded*`) come to the
HBM roofline of the chips together.  As `ops.fused_conj_roofline`: the
bytes the queries of the traced slice must move (`hbm_model`, unchanged)
at one chip's peak bandwidth (`peaks.json`) are chip-seconds, and so is
the programs' device time summed over all planes.  A program that runs
on four chips for as long as it ran on one reads a quarter."""

from benchmark.harness import mesh_trace


def read(spans, counters, trace, window):
    seconds = mesh_trace.mesh_program_seconds(trace)
    if seconds <= 0:
        return None
    peak = mesh_trace.peak(window, "peaks.json", "hbm_bytes_per_s")
    return 100.0 * (mesh_trace.hbm_bytes_in_slice(window) / peak) / seconds
