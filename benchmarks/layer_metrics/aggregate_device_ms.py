"""operators: milliseconds of device time a traced query spends in ``HashAggregateExec``'s own programs
(``jit_HashAggregateExec.*``: update, merge, and both Pallas aggregate lanes)."""

from benchmarks.layer_metrics.engine_record import operator_device_ms


def read(run):
    return operator_device_ms(run, "aggregate")
