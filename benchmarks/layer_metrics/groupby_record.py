"""What the readers of the grouped-aggregate cell's metrics share: which device programs hold the grouped
partial aggregate, and the per-query mean of a counter the engine keeps in ``phases``.

Program names are the engine's registry labels (``jit_<label>``, ``jit_registry.py``); ``harness/xplane.py``
puts a program's name in front of each of its operations, up to the first ``/``.
"""

from benchmarks.layer_metrics.engine_record import phase_ms
from benchmarks.layer_metrics.join_record import programs_device_ms

#: prefixes of the programs that hold a grouped partial aggregate: the fused chain that ends in it (the filter
#: in front, the group finding, the one-hot kernel), and the unfused update in both its lanes
GROUPBY_PROGRAMS = ("jit__fused_program_builder", "jit_HashAggregateExec._update")


def groupby_device_ms(run):
    """Milliseconds of device time a traced query spent in ``GROUPBY_PROGRAMS``, loops and branches counted
    once: 0.0 where the trace holds none of them, ``None`` without a trace."""
    return programs_device_ms(run, GROUPBY_PROGRAMS)


def phase_count(run, key: str):
    """Mean of the counter ``phases[key]`` over the window's queries; ``None`` where the engine keeps no such
    key (a parent commit). ``engine_record.phase_ms`` divides by 1e6: it was written for nanoseconds."""
    per_query = phase_ms(run, key)
    return None if per_query is None else per_query * 1e6
