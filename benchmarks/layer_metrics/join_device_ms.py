"""operators: milliseconds of device time a traced query spends in the programs the join execs register,
fused or not (``join_record.JOIN_PROGRAMS``: the per-pair join with whatever suffix is fused behind it, the
once-a-query lookup table or hash index, the first pair's count). 0.0 where no join ran."""

from benchmarks.layer_metrics.join_record import JOIN_PROGRAMS, programs_device_ms


def read(run):
    return programs_device_ms(run, JOIN_PROGRAMS)
