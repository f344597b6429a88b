#!/usr/bin/env python
"""Sustained-QPS multi-tenant serving benchmark.

Replays a Zipf-weighted mix of NDS queries from N socket clients
against ONE SqlServer process for a fixed wall-clock window — the
serving analogue of the throughput benchmarks the reference publishes
for its Spark plugin under concurrent sessions. Each client is its own
tenant on its own TCP session, paced open-loop at the target aggregate
QPS; a load-shed (retryable SHED frame) is counted and the slot is
retried on the next tick rather than silently dropped.

Reported:

- ``serve_p50_ms`` / ``serve_p90_ms`` / ``serve_p99_ms`` — end-to-end
  submit->EOS latency over every completed request (time-like: lower
  is better);
- ``serve_tiers`` — the same quantiles split by admission tier
  (``cached`` / ``immediate`` / ``queued``), nested so the noisy
  per-tier tails inform without gating;
- ``serve_qps_sustained`` — completed requests / window (rate-like:
  higher is better);
- ``result_cache_hit_rate`` / ``plan_cache_hit_rate`` — cross-tenant
  reuse evidence; the Zipf mix repeats hot queries, so the result-
  cache rate must be > 0 when the cache is on;
- ``serve_load_shed`` / ``serve_cross_query_spills`` — pressure
  counters (informational).

Usage:
    python tools/serve_bench.py [--duration 30] [--clients 4]
        [--qps 8] [--scale-rows 8000] [--data-dir DIR] [--json]
"""

import argparse
import json
import os
import random
import sys
import threading
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: hot set replayed by the clients — the cheap head of the bench's
#: measured NDS order, so a 30s window completes hundreds of requests
#: even on the CPU fallback backend
DEFAULT_QUERIES = ["q68", "q16", "q96", "q93", "q89", "q25", "q84",
                   "q28", "q9", "q24"]

#: Zipf exponent for the replay mix: rank r is drawn with weight
#: 1/r^a, so the hottest query dominates and the result cache has a
#: real hit population to serve
ZIPF_A = 1.2


def _quantile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    vs = sorted(values)
    return vs[min(int(q * len(vs)), len(vs) - 1)]


class _CountingSink:
    """Event sink counting pressure events during the window (the
    bench runs standalone, so it owns the process sink)."""

    def __init__(self):
        self.cross_query_spills = 0
        self.load_sheds = 0
        self._lock = threading.Lock()

    def emit(self, event, **fields):
        if event == "CrossQuerySpill":
            with self._lock:
                self.cross_query_spills += 1
        elif event == "ServeLoadShed":
            with self._lock:
                self.load_sheds += 1

    def close(self):
        pass


def run_serve_bench(duration_s: float = 30.0, clients: int = 4,
                    qps: float = 8.0, scale_rows: int = 8000,
                    data_dir: Optional[str] = None,
                    queries: Optional[List[str]] = None,
                    conf_extra: Optional[Dict[str, str]] = None,
                    log=lambda msg: None) -> dict:
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.models.nds import NDS_QUERIES, register_nds
    from spark_rapids_tpu.obs import events
    from spark_rapids_tpu.plan import TpuSession
    from spark_rapids_tpu.serve import ServeError, ServeLoadShed, \
        SqlClient, SqlServer

    settings = {
        "srt.shuffle.partitions": 2,
        "srt.sql.resultCache.enabled": "true",
        "srt.sql.concurrentQueryTasks": "2",
        "srt.sql.admission.maxQueueDepth": "16",
    }
    settings.update(conf_extra or {})
    session = TpuSession(SrtConf(settings))
    if data_dir is None:
        data_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".bench_cache", f"nds_serve_{scale_rows}")
    register_nds(session, data_dir, scale_rows=scale_rows)
    names = [q for q in (queries or DEFAULT_QUERIES)
             if q in NDS_QUERIES]
    sql_texts = [NDS_QUERIES[q] for q in names]
    weights = [1.0 / (r + 1) ** ZIPF_A for r in range(len(sql_texts))]

    sink = _CountingSink()
    events.install(sink)
    latencies_ms: List[float] = []
    by_tier: Dict[str, List[float]] = {}
    counters = {"completed": 0, "shed": 0, "errors": 0,
                "cache_hits": 0}
    mu = threading.Lock()
    stop = threading.Event()

    server = SqlServer(session).start()
    log(f"server on {server.endpoint}: {clients} clients x "
        f"{qps / clients:.2f} qps for {duration_s:.0f}s over "
        f"{len(sql_texts)} NDS queries (zipf a={ZIPF_A})")

    # warm once so compile/trace cost lands before the window opens
    # (the serving numbers measure serving, not first-compile)
    with SqlClient(server.endpoint, tenant="warmup") as warm:
        for sql in sql_texts:
            try:
                warm.submit(sql)
            except (ServeError, OSError) as e:
                log(f"warmup failed: {e}")

    def client_loop(idx: int):
        rng = random.Random(1000 + idx)
        period = clients / qps if qps > 0 else 0.0
        try:
            c = SqlClient(server.endpoint, tenant=f"tenant-{idx}")
        except (ServeError, OSError) as e:
            with mu:
                counters["errors"] += 1
            log(f"client {idx} connect failed: {e}")
            return
        try:
            next_slot = time.monotonic() + rng.random() * period
            while not stop.is_set():
                now = time.monotonic()
                if now < next_slot:
                    if stop.wait(min(next_slot - now, 0.05)):
                        break
                    continue
                next_slot += period
                sql = rng.choices(sql_texts, weights=weights)[0]
                t0 = time.perf_counter()
                try:
                    r = c.submit(sql)
                except ServeLoadShed:
                    with mu:
                        counters["shed"] += 1
                    continue
                except (ServeError, OSError) as e:
                    with mu:
                        counters["errors"] += 1
                    log(f"client {idx} error: {e}")
                    continue
                ms = (time.perf_counter() - t0) * 1000.0
                tier = r.info.get("tier", "?")
                with mu:
                    counters["completed"] += 1
                    if r.info.get("cache") == "hit":
                        counters["cache_hits"] += 1
                    latencies_ms.append(ms)
                    by_tier.setdefault(tier, []).append(ms)
        finally:
            c.close()

    threads = [threading.Thread(target=client_loop, args=(i,),
                                name=f"serve-bench-client-{i}")
               for i in range(clients)]
    t_open = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    window = time.monotonic() - t_open
    server.stop()
    events.install(None)

    cache_stats = server.result_cache.stats() \
        if server.result_cache is not None else {}
    plan_stats = session._plan_cache.stats()
    plan_lookups = plan_stats["hits"] + plan_stats["misses"]
    out = {
        "serve_p50_ms": round(_quantile(latencies_ms, 0.50), 1),
        "serve_p90_ms": round(_quantile(latencies_ms, 0.90), 1),
        "serve_p99_ms": round(_quantile(latencies_ms, 0.99), 1),
        "serve_qps_sustained": round(
            counters["completed"] / window, 2) if window else 0.0,
        "serve_requests": counters["completed"],
        "serve_errors": counters["errors"],
        "serve_load_shed": max(counters["shed"], sink.load_sheds),
        "serve_cross_query_spills": sink.cross_query_spills,
        "serve_clients": clients,
        "serve_window_s": round(window, 1),
        "serve_tiers": {
            tier: {"p50_ms": round(_quantile(ms, 0.50), 1),
                   "p90_ms": round(_quantile(ms, 0.90), 1),
                   "p99_ms": round(_quantile(ms, 0.99), 1),
                   "n": len(ms)}
            for tier, ms in sorted(by_tier.items())},
        "result_cache_hit_rate": round(
            cache_stats.get("hit_rate", 0.0), 3),
        "plan_cache_hit_rate": round(
            plan_stats["hits"] / plan_lookups, 3) if plan_lookups
            else 0.0,
    }
    log(f"window {out['serve_window_s']}s: "
        f"{counters['completed']} ok ({out['serve_qps_sustained']} "
        f"qps), p99={out['serve_p99_ms']}ms, "
        f"shed={out['serve_load_shed']}, "
        f"result cache hit rate={out['result_cache_hit_rate']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--qps", type=float, default=8.0,
                    help="target aggregate submit rate")
    ap.add_argument("--scale-rows", type=int, default=8000)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--queries", default=None,
                    help="comma-separated NDS query ids")
    ap.add_argument("--json", action="store_true",
                    help="print only the final JSON record")
    args = ap.parse_args(argv)

    def log(msg):
        if not args.json:
            print(msg, file=sys.stderr, flush=True)

    out = run_serve_bench(
        duration_s=args.duration, clients=args.clients, qps=args.qps,
        scale_rows=args.scale_rows, data_dir=args.data_dir,
        queries=args.queries.split(",") if args.queries else None,
        log=log)
    print(json.dumps(out, indent=None if args.json else 2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
