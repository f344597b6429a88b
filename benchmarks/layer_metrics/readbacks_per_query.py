"""operators: device reads a query makes (``phases.readbacks``: calls of ``jit_registry.read_back`` that had a
value to fetch, each one round trip in which the reading thread dispatches nothing; the join execs'
``join_readbacks`` are among them), mean a query of the window. ``None`` where the engine counts none (a
parent commit)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    per_query = phase_ms(run, "readbacks")  # mean of the counter / 1e6: the reader was written for ns
    return None if per_query is None else per_query * 1e6
