"""What the readers of the join cell's metrics share, beside ``engine_record.py``'s table: which device
programs are the join execs' and which the sort's, and the least bytes a query's joins must read.

Program names are the engine's registry labels (``jit_<label>``, ``jit_registry.py``); ``harness/xplane.py``
puts a program's name in front of each of its operations, up to the first ``/``.
"""

from benchmarks.harness import datagen

#: prefixes of the programs the join execs register (exec/join.py, exec/fused.py), fused or not: the per-pair
#: join, alone or with the suffix fused behind it; what is built once from the build side (lookup table,
#: key statistics, hash index); the first pair's count; bloom, sub-partition split and chunk programs
JOIN_PROGRAMS = ("jit__join_run_builder", "jit__fused_join_builder", "jit__lookup_", "jit__hash_index_builder",
                 "jit__bloom_", "jit__bucket_split_builder", "jit__chunk_slice_builder")
#: the sort / take-ordered programs (exec/sort.py)
SORT_PROGRAMS = ("jit_TopNExec.", "jit_SortExec.", "jit__concat_sort_builder", "jit__chunk_head_builder",
                 "jit__bound_prefix_builder", "jit__safe_prefix_builder")
#: the final merge of a grouped aggregate's partial states, with the projections the planner fused into it
#: (exec/fused.py::fused_final_merge_fn): the one program of a star query that is the aggregate's alone. The
#: partial aggregate runs inside ``jit__fused_join_builder``, behind the second join, and a trace's operations
#: carry their HLO text and no scope, so that part stays in ``join_device_ms``
AGG_MERGE_PROGRAMS = ("jit__fused_merge_builder",)
#: operations that only hold others: a trace lists a loop or a branch AND the operations of its body, so
#: summing a program's operations with these in would count the body twice
CONTAINERS = ("%while", "%conditional")


def programs_device_ms(run, prefixes: tuple):
    """Milliseconds of device time a traced query spent in the programs whose names start with one of
    ``prefixes``: 0.0 where the trace holds none of them, ``None`` without a trace."""
    trace = run.trace
    if trace is None or not trace.queries:
        return None
    seconds = 0.0
    for name, s in trace.op_seconds.items():
        program, _, operation = name.partition("/")
        if program.startswith(prefixes) and not operation.startswith(CONTAINERS):
            seconds += s
    return seconds * 1e3 / trace.queries


def _kept_share(column: dict, rows: int) -> float:
    """The share of a dimension's rows an equality on ``column`` keeps, from the column's own spec."""
    if column["dist"] == "uniform":
        return 1.0 / (int(column["hi"]) - int(column["lo"]) + 1)
    if column["dist"] == "calendar" and column["part"] == "month":
        return 1.0 / 12
    if column["dist"] == "calendar" and column["part"] == "year":
        return 365.25 / rows
    raise ValueError(f"no selectivity known for an equality on a {column['dist']} column ({column['name']})")


def join_bytes(config: dict, qid: str) -> float:
    """The least bytes query ``qid``'s join programs must read: every row of the fact table's scanned columns
    (all of them pass through the joins) and, once, the rows its WHERE keeps of each dimension, all at their
    logical widths. From the configuration alone (``scans``, ``dimension_filters``, the column specs),
    whatever implements the join."""
    query = config["queries"][qid]
    total = 0.0
    for table, columns in query["scans"].items():
        spec = config["tables"][table]
        by_name = {c["name"]: c for c in spec["columns"]}
        rows = float(spec["rows"])
        if table != query["fact"]:
            for name in query["dimension_filters"][table]:
                rows *= _kept_share(by_name[name], spec["rows"])
        for name in columns:
            c = by_name[name]
            total += rows * (c["avg_bytes"] if c["type"] == "STRING" else datagen.LOGICAL_WIDTH[c["type"]])
    return total
