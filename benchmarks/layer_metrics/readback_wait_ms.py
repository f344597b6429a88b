"""operators: milliseconds a query's threads stood reading device values to the host
(``phases.readback_ns``: ``jit_registry.read_back``, the ``device.read`` range around one explicit read, summed over
the query's threads), mean a query of the window. A read waits for the programs that produce its values, so this is
how much of the device's time, and of each round trip, reaches the threads' walls. ``None`` where the engine records
no such phase (a parent commit)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "readback_ns")
