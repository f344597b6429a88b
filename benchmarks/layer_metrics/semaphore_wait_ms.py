"""operators: milliseconds a query's threads stood blocked at the device semaphore
(``phases.semaphore_wait_ns``: ``exec/base.py::TpuSemaphore.acquire_if_necessary``, the ``semaphore.wait``
range, summed over the query's consumer and producer threads through its ``ExecContext``), mean a query of
the window. ``srt.sql.concurrentTpuTasks`` permits, sized from the executing session's conf; 0 while no
more threads than permits want the device at once. ``None`` where the engine records no such phase."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "semaphore_wait_ns")
