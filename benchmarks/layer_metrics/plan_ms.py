"""entry + planner: host milliseconds a query spends outside the engine's execution span (SQL parse,
plan and overrides, result rows to Python), mean over the window's queries. The benchmark's own span
around the call, minus the session's ``wall_ns``."""


def read(run):
    spans = [r.wall_ms - r.engine["wall_ns"] / 1e6 for r in run.records if "wall_ns" in r.engine]
    return sum(spans) / len(spans) if spans else None
