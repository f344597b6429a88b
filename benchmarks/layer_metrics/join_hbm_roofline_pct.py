"""kernels: the least time the chip's HBM needs for what the traced queries' join programs must read
(``join_record.join_bytes``: the fact columns, all of which pass through the joins, and the filtered
dimensions once a query, at their logical widths, from the configuration alone), as a share of the device
time those programs took (``join_device_ms``). HBM-bound by construction: a lookup is a subtraction and a
gather a row. Left out where no join program ran."""

from benchmarks.harness import peaks
from benchmarks.layer_metrics.join_record import JOIN_PROGRAMS, join_bytes, programs_device_ms


def read(run):
    per_query_ms = programs_device_ms(run, JOIN_PROGRAMS)
    if not per_query_ms or not all("dimension_filters" in run.config["queries"][q] for q in run.cell["queries"]):
        return None
    # the trace covers whole rounds of the cell's queries
    round_bytes = sum(join_bytes(run.config, q) for q in run.cell["queries"])
    least_ms = round_bytes / len(run.cell["queries"]) / peaks.peak(run.device_kind, "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / per_query_ms
