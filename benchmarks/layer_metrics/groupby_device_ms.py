"""operators: milliseconds of device time a traced query spends in the programs of the grouped partial
aggregate, fused or not (``groupby_record.GROUPBY_PROGRAMS``: ``jit__fused_program_builder*``,
``jit_HashAggregateExec._update*``): the filter in front, finding the groups, the one-hot kernel. Loops and
branches counted once. 0.0 where no such program ran."""

from benchmarks.layer_metrics.groupby_record import groupby_device_ms


def read(run):
    return groupby_device_ms(run)
