"""What the benchmark takes from the program: the session, its counters, and jax's compile events."""

from __future__ import annotations


class CompileCounter:
    """Executables jax asked its backend for, and how many of those the persistent cache answered
    (``jax.monitoring``; copied from ``chip_smoke.py``). ``requests - cache_hits`` were compiled."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def open_session(engine_conf: dict):
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.plan import TpuSession
    return TpuSession(SrtConf(dict(engine_conf)))


def open_tables(session, paths: dict, as_views: bool) -> dict:
    tables = {name: session.read.parquet(path) for name, path in paths.items()}
    if as_views:
        for name, frame in tables.items():
            session.create_or_replace_temp_view(name, frame)
    return tables


def last_query_counters(session) -> dict:
    """The engine's own numbers for the query it ran last: execution wall, and operator metrics summed
    over the plan. Empty where the session keeps no such record."""
    last = getattr(session, "_last_execution", None)
    if not last:
        return {}
    out = {}
    if "wall_ns" in last:
        out["wall_ns"] = int(last["wall_ns"])
    totals: dict = {}
    for metrics in last["ctx"].metrics.values():
        for name, metric in metrics.items():
            totals[name] = totals.get(name, 0) + int(metric.value)
    for name in ("scanTime", "pallasBatches", "scanHostDecodedFiles", "scanNativeDecodedFiles"):
        if name in totals:
            out[name] = totals[name]
    return out


def lane_precision(counters: dict) -> str:
    """The lane the program says answered the query those counters describe: float32 products where a
    Pallas aggregate lane ran (``pallasBatches`` > 0), else float64, emulated, on the XLA path. It never
    sets the limit (the configuration states each query's precision); a lower lane than stated is a fault."""
    return "float32" if counters.get("pallasBatches", 0) > 0 else "float64"


def mean_counters(per_query: list[dict]) -> dict:
    """The engine's numeric counters, averaged over the window's queries (a diagnostic beside the metrics)."""
    names = sorted({k for c in per_query for k, v in c.items() if isinstance(v, (int, float))})
    return {k: sum(c.get(k, 0) for c in per_query) / len(per_query) for k in names}
