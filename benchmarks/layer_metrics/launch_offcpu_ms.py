"""operators: milliseconds a query's launches stood off a CPU: ``phases.dispatch_ns`` (the ``launch.<label>``
ranges' wall, every dispatching thread) less ``phases.dispatch_cpu_ns`` (those threads' CPU time inside the
same ranges, ``time.thread_time_ns``), mean a query of the window: time a launch waited for the interpreter's
lock, a lock of the runtime or the scheduler, and not computing. ``None`` where the engine records no such
phase (a parent commit)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    wall, cpu = phase_ms(run, "dispatch_ns"), phase_ms(run, "dispatch_cpu_ns")
    return None if wall is None or cpu is None else wall - cpu
