"""scan: milliseconds a query's pipeline stood waiting for the scan: ``phases.prefetch_wait_ns`` (the
consumer blocked on an empty prefetch queue, ``prefetch.wait`` range, ``exec/pipeline.py``) plus
``phases.scan_wait_ns`` (the thread pulling the scan blocked on the next decoded table, ``scan.wait``
range, ``io/scan.py``). The two run on different threads and may overlap."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "prefetch_wait_ns", "scan_wait_ns")
