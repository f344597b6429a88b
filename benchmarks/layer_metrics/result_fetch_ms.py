"""entry + planner: milliseconds a query spends bringing its result to Python: ``phases.fetch_ns``, the
``result.fetch`` ranges (``batch_to_table`` of each output batch, then ``to_pydict`` and the row dicts in
``DataFrame.collect``; ``plan/session.py``)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "fetch_ns")
