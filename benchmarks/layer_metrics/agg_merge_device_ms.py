"""operators: milliseconds of device time a traced query spends in the final merge of its grouped
aggregate (``join_record.AGG_MERGE_PROGRAMS``: ``jit__fused_merge_builder``, the partial states of every fact
batch concatenated, merged and finalized). ``aggregate_device_ms`` reads 0.0 in a cell whose aggregates are
fused with their neighbours; this is the part of them a trace can tell apart. 0.0 where no such program ran."""

from benchmarks.layer_metrics.join_record import AGG_MERGE_PROGRAMS, programs_device_ms


def read(run):
    return programs_device_ms(run, AGG_MERGE_PROGRAMS)
