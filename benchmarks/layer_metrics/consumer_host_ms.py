"""operators: milliseconds of a query's execution the pulling thread spent on its own work
(``phases.consumer_host_ns``: ``execute_ns`` less that thread's prefetch waits, device-gate waits, launches
and device reads, ``plan/session.py::CONSUMER_PHASES``; the five add up to ``execute_ns``), mean a query of the
window: the operators' Python, the plan's host work, the host part of ``result.fetch``. ``None`` where the
engine records no such phase (a parent commit)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "consumer_host_ns")
