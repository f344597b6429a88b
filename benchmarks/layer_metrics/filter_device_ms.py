"""operators: milliseconds of device time a traced query spends in ``FilterExec``'s own programs
(``jit_FilterExec.*``: the un-fused filter's mask and compaction)."""

from benchmarks.layer_metrics.engine_record import operator_device_ms


def read(run):
    return operator_device_ms(run, "filter")
