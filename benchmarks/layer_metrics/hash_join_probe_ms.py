"""operators: milliseconds a query's hash-join pairs spent in their ``join.probe`` ranges (``phases.
hash_join_probe_ns``, the ``hashJoinProbeTime`` counter of exec/join.py: each HASH pair's launches, their
capacity read-backs and any relaunch), mean a query of the window. ``None`` where the engine keeps no such
phase (a parent commit)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "hash_join_probe_ns")
