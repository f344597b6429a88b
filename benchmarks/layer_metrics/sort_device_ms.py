"""operators: milliseconds of device time a traced query spends in the sort / take-ordered programs
(``join_record.SORT_PROGRAMS``: ``jit_TopNExec.*``, ``jit_SortExec.*`` and the sort's builders)."""

from benchmarks.layer_metrics.join_record import SORT_PROGRAMS, programs_device_ms


def read(run):
    return programs_device_ms(run, SORT_PROGRAMS)
