"""What the readers of this PR's metrics share: the engine's own per-query record, and the table that
says which device programs belong to which operator.

The engine keeps a bounded record of the queries it completed (``spark_rapids_tpu.obs.registry``, 64
deep), each with a ``phases`` dict: nanoseconds measured inside the engine where the work happens
(docs/OBSERVABILITY.md, "Host ranges and query phases"). The window's queries are the newest records,
one record a query. An engine that keeps no ``phases`` (a parent commit) gives ``None``: the metric is
then left out of the line.

Device programs are named after the engine's registry labels (``jit_<label>``; ``jit_registry.py``), and
``harness/xplane.py`` puts a program's name in front of each of its operations, up to the first ``/``.
"""

#: operator -> prefixes of the programs (HLO modules) that are that operator's alone. A fused program
#: (``jit__fused_*``) runs several operators at once and belongs to none of them.
OPERATOR_PROGRAMS = {
    "filter": ("jit_FilterExec.",),
    # _update, _merge_finalize, the Pallas lanes (_update_pallas grouped, _pallas_stream global), and the
    # aggregate's sub-partition split
    "aggregate": ("jit_HashAggregateExec.", "jit__key_bucket_split_builder"),
}


def phase_ms(run, *keys: str):
    """Mean milliseconds a query of the window spent in the named ``phases``, summed; ``None`` where the
    engine records no such phase."""
    from spark_rapids_tpu.obs.registry import registry
    recorded = registry().queries()
    n = min(len(run.records), len(recorded))
    phases = [record.get("phases") for record in recorded[len(recorded) - n:]] if n else []
    if not phases or any(p is None or any(k not in p for k in keys) for p in phases):
        return None
    return sum(p[k] for p in phases for k in keys) / len(phases) / 1e6


def operator_device_ms(run, operator: str):
    """Milliseconds of device time a traced query spent in ``operator``'s own programs: 0.0 where the trace
    holds none of them (the operator was fused away, or never ran), ``None`` without a trace."""
    trace = run.trace
    if trace is None or not trace.queries:
        return None
    prefixes = OPERATOR_PROGRAMS[operator]
    seconds = sum(s for name, s in trace.op_seconds.items() if name.split("/", 1)[0].startswith(prefixes))
    return seconds * 1e3 / trace.queries
