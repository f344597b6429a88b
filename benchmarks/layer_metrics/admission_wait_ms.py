"""serve: milliseconds a query stood queued for admission before it ran (``phases.admission_wait_ns``:
``robustness/admission.py::QuerySemaphore.acquire``, the ``admission.wait`` range), mean a query of the
window. ``srt.sql.concurrentQueryTasks`` queries run at once; 0 while no more are in flight than that.
``None`` where the engine records no such phase."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "admission_wait_ns")
