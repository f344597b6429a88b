"""operators: host reads of a device scalar a query's join execs make (``phases.join_readbacks``, the
``joinReadbacks`` counter of exec/join.py: every ``int(num_rows)`` / ``int(total)`` / ``int(rows_in)`` that
had to wait for the device), mean a query of the window. Each is a full round trip in which the host
dispatches nothing. ``None`` where the engine counts none (a parent commit)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    per_query = phase_ms(run, "join_readbacks")  # mean of the counter / 1e6: the reader was written for ns
    return None if per_query is None else per_query * 1e6
