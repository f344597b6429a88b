"""scan: milliseconds the thread that pulls a query's plan stood waiting on an empty prefetch queue
(``phases.consumer_scan_wait_ns``: the ``prefetch.wait`` ranges of that thread alone, ``exec/pipeline.py``),
mean a query of the window. Beside ``scan_wait_ms``, which sums the waits of every producer thread too, this
is the part of the scan's wait that is on the query's wall. ``None`` where the engine records no such phase."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "consumer_scan_wait_ns")
