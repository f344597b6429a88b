"""entry + planner: milliseconds a query spends before execution starts, timed inside the engine:
``phases.parse_ns`` (SQL text to a logical plan, ``plan.parse`` range, ``sql/parser.py``) plus
``phases.plan_ns`` (cache key, plan-cache lease, overrides, reset for rerun: ``plan.physical`` range,
``plan/session.py``)."""

from benchmarks.layer_metrics.engine_record import phase_ms


def read(run):
    return phase_ms(run, "parse_ns", "plan_ns")
