"""scan: milliseconds of reader-thread time a query spends reading and decoding files:
``phases.scan_decode_ns``, the engine's ``scanDecodeTime`` summed over the plan (``io/scan.py``, timed
around read + decode + conform of each file on the thread that does it). Thread time: with a reader pool
it may exceed the query's wall. It has no range in the trace, on purpose (PERF.md, last section)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "scan_decode_ns")
