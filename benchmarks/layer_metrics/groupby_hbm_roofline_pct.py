"""kernels: the least time the chip's HBM needs for what the grouped aggregate must read (``datagen.
logical_bytes``: the fact table's rows times the logical width of the columns the query references, from the
configuration alone, whatever implements it), as a share of the device time the grouped aggregate's programs
took (``groupby_device_ms``). HBM-bound by construction: a handful of operations a byte; the one-hot's FLOPs
are the implementation's, not the query's. A share of those programs' own time, so under 100. Left out where
no such program ran."""

from benchmarks.harness import datagen, peaks
from benchmarks.layer_metrics.groupby_record import groupby_device_ms


def read(run):
    per_query_ms = groupby_device_ms(run)
    if not per_query_ms:
        return None
    # the trace covers whole rounds of the cell's queries
    round_bytes = sum(datagen.logical_bytes(run.config, q) for q in run.cell["queries"])
    least_ms = round_bytes / len(run.cell["queries"]) / peaks.peak(run.device_kind, "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / per_query_ms
