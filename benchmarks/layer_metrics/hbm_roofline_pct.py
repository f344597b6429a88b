"""kernels: the least time the chip's HBM needs for the traced queries' logical bytes, as a share of
the time the device was busy. Bytes are rows times the logical width of the columns each query
references, from the configuration alone, so the number reads the same work whatever implements it.
HBM-bound by construction: these queries do a handful of operations a byte."""

from benchmarks.harness import datagen, peaks


def read(run):
    trace = run.trace
    if trace is None or not trace.queries or not trace.busy_s:
        return None
    # the trace covers whole rounds of the cell's queries
    round_bytes = sum(datagen.logical_bytes(run.config, q) for q in run.cell["queries"])
    traced_bytes = round_bytes * trace.queries / len(run.cell["queries"])
    least_s = traced_bytes / peaks.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least_s / trace.busy_s
