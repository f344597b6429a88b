"""operators: host milliseconds a query spends dispatching device programs: ``phases.dispatch_ns``, the
``launch.<label>`` ranges around each dispatch (``jit_registry.py``), summed over the query's threads.
Dispatch is asynchronous: this is the host's cost of launching, not the device's time."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "dispatch_ns")
