#!/usr/bin/env python
"""Chaos smoke for the distributed runtime: run a real multi-process
cluster job under a sweep of seeded fault plans and verify every run
stays oracle-identical.

Each plan ships to the workers via ``srt.test.faultPlan`` (see
docs/ROBUSTNESS.md for the spec grammar and fault-site catalog). The
sweep covers the transient-transport paths (refused connects,
mid-frame resets, delays, dropped heartbeats), the stage-level
recovery path (a worker crash at a stage boundary), the data
integrity paths (seeded byte-flips of shuffle payloads on the wire and
at rest, corrupt input files, and a flipped disk-tier spill entry —
every one must be detected and recovered, never a silently wrong
answer), and the adaptive-execution paths (seeded skew and wrong
broadcast thresholds swept adaptive on/off with identical results,
plus a speculated straggler). A streaming-ingestion leg SIGKILLs the
Delta ingester child at seeded commit-protocol fault points
(stage/rename/commit/fsync), relaunches it, and asserts exactly-once
row counts with zero orphans, plus stale-epoch writer fencing. A
nonzero exit means a divergent result, a failed run, or a
blown wall-clock budget — any of which is a real robustness
regression.

Usage:
    python tools/chaos_check.py [--quick] [--workers N] [--budget SEC]

``--quick`` (2 workers, 2 plans) is wired into tier-1 as
tests/test_fault_injection.py::test_chaos_check_quick.
"""

import argparse
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# transient-transport sweep: safe to run back-to-back on one cluster
TRANSIENT_PLANS = [
    ("refused-connect + mid-frame reset",
     "seed=11|transport.connect:refuse@1|transport.serve_block:reset@1"),
    ("probabilistic block delays + dropped heartbeats",
     "seed=5|transport.block:delay%0.3*20+0.02"
     "|cluster.heartbeat:drop%1.0*3"),
]

# seeded data-corruption sweep: a byte-flip injected at each off-device
# byte path must be caught by the checksum envelope and healed by the
# corresponding recovery mechanism (same-endpoint refetch for wire
# corruption; quarantine -> fetch failure -> rerun for at-rest
# corruption; DataCorruption -> rerun for a corrupt input file)
CORRUPTION_PLANS = [
    ("shuffle payload corrupted on the wire",
     "seed=17|shuffle.block.wire:corrupt@1"),
    # pinned to attempt 0 via the map-id match (retry attempts offset
    # map ids by attempt<<20, so "map=0;" never re-fires): each worker
    # keeps its own fault counters across attempts, and an un-pinned
    # @1 would inject FRESH corruption from a worker whose store site
    # was first reached only during a retry — an unwinnable plan, not
    # a recovery bug
    ("shuffle payload corrupted at rest",
     "seed=19|shuffle.block.store:corrupt@1~map=0;"),
    ("input file read fails with DataCorruption",
     "seed=23|scan.file:corrupt@1"),
]

# kills logical worker 1 at the final (range-exchange) barrier of
# attempt 0 — after the hash exchange completed — forcing the driver's
# stage-level retry path; runs LAST because it costs a worker
CRASH_PLAN = ("worker crash at stage boundary",
              "seed=3|cluster.barrier:crash@1~attempt=0;workers=1;pos=0;")


def _spill_corruption_check() -> int:
    """Deterministic in-process disk-tier check: spill a batch to disk,
    flip one byte in the spill file, and require ``get()`` to raise
    ``DataCorruption`` with the entry dropped — a silent wrong batch or
    a reusable corrupt entry is a failure. Returns failure count."""
    import tempfile as _tf

    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.memory.budget import (MemoryBudget,
                                                reset_task_context)
    from spark_rapids_tpu.memory.spill import (SpillableBatch,
                                               reset_spill_catalog)
    from spark_rapids_tpu.robustness.integrity import DataCorruption

    with _tf.TemporaryDirectory(prefix="srt_chaos_spill_") as sdir:
        reset_task_context()
        cat = reset_spill_catalog(budget=MemoryBudget(1 << 30),
                                  host_limit=1 << 20, spill_dir=sdir)
        sb = SpillableBatch(batch_from_pydict(
            {"a": list(range(512)), "b": [float(i) for i in range(512)]}))
        sb.spill_to_host()
        sb.spill_to_disk()
        path = sb._path
        with open(path, "r+b") as f:
            f.seek(max(os.path.getsize(path) // 2, 0))
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        try:
            sb.get()
        except DataCorruption as e:
            dropped = sb.closed and not cat.leak_report()
            print(f"[chaos] {'PASS' if dropped else 'FAIL'} "
                  f"[disk spill entry corrupted]: {e}", flush=True)
            failures = 0 if dropped else 1
        else:
            print("[chaos] FAIL [disk spill entry corrupted]: get() "
                  "returned a batch from a corrupted spill file",
                  file=sys.stderr, flush=True)
            failures = 1
    reset_spill_catalog(budget=MemoryBudget(1 << 40))
    return failures


def _new_fault_events(events_dir, offsets):
    """FaultInjected events appended to the event log since the last
    call. ``offsets`` ({path: records_seen}) is updated in place so
    each plan only sees its own events — the same worker processes
    (and files) carry across the whole sweep."""
    from spark_rapids_tpu.obs import events as ev
    out = []
    if not os.path.isdir(events_dir):
        return out
    for path in ev.iter_log_files(events_dir):
        recs = ev.read_events(path)
        start = offsets.get(path, 0)
        out.extend(r for r in recs[start:]
                   if r.get("event") == "FaultInjected")
        offsets[path] = len(recs)
    return out


def _unfired_deterministic(spec, fired):
    """Deterministic clauses (@nth, or %prob >= 1.0) of ``spec`` with
    no matching (site, kind) FaultInjected event yet. Probabilistic
    clauses may legitimately never fire and are never reported."""
    from spark_rapids_tpu.robustness.faults import FaultPlan
    logged = {(e.get("site"), e.get("kind")) for e in fired}
    return [sp for sp in FaultPlan.parse(spec).specs
            if (sp.nth is not None or sp.prob >= 1.0)
            and (sp.site, sp.kind) not in logged]


def _check_fault_events(name, spec, fired, prev_armed=()):
    """Every injected fault must be visible in the event log: each
    DETERMINISTIC clause (@nth, or %prob >= 1.0 — probabilistic
    clauses may legitimately never fire) needs a matching (site, kind)
    FaultInjected event, and every logged event must come from one of
    the plan's clauses (``prev_armed`` tolerates late fires from the
    PREVIOUS plan's async sites — the worker heartbeat loop keeps
    hitting an armed plan after its job returns). Returns failure
    count."""
    from spark_rapids_tpu.robustness.faults import FaultPlan
    plan = FaultPlan.parse(spec)
    failures = 0
    logged = {(e.get("site"), e.get("kind")) for e in fired}
    for sp in _unfired_deterministic(spec, fired):
        print(f"[chaos] FAIL [{name}]: injected fault "
              f"{sp.site}:{sp.kind} produced no FaultInjected "
              f"event (logged: {sorted(logged)})",
              file=sys.stderr, flush=True)
        failures += 1
    armed = {(sp.site, sp.kind) for sp in plan.specs}
    stray = logged - armed - set(prev_armed)
    if stray:
        print(f"[chaos] FAIL [{name}]: FaultInjected events from "
              f"un-armed clauses: {sorted(stray)}",
              file=sys.stderr, flush=True)
        failures += 1
    return failures


def _telemetry_check(n_workers: int = 4) -> int:
    """Distributed-telemetry leg: run one clean query on a 4-worker
    cluster with event logs + tracing + resource sampling on, then
    require ``tools/history_report.py`` to merge the per-process logs
    into one coherent report — every worker contributed spans, all
    span parentage resolves across process boundaries (worker task
    spans under the driver's job span), and the clock-aligned
    timelines agree with the event log to < 50ms. Returns failure
    count."""
    import numpy as np

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    from spark_rapids_tpu.plan import TpuSession

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from history_report import build_report

    failures = 0
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="srt_telemetry_") as tmp:
        session = TpuSession(SrtConf({}))
        rng = np.random.default_rng(41)
        n = 6_000
        fact_dir = os.path.join(tmp, "fact")
        session.create_dataframe({
            "k": rng.integers(0, 30, n).tolist(),
            "v": rng.uniform(0, 10, n).tolist(),
        }).write.parquet(fact_dir)
        plan = session.read.parquet(fact_dir) \
            .group_by("k").agg(Alias(Sum(col("v")), "s"),
                               Alias(CountStar(), "c")) \
            .sort("k").plan
        events_dir = os.path.join(tmp, "events")
        driver = ClusterDriver(num_workers=n_workers,
                               barrier_timeout=60,
                               heartbeat_interval=0.5,
                               heartbeat_timeout=10)
        procs = launch_local_workers(driver, n_workers)
        try:
            driver.wait_for_workers(timeout=120)
            rows = driver.run(plan, {
                "srt.shuffle.partitions": 4,
                "srt.cluster.barrierTimeoutSec": 60,
                "srt.eventLog.enabled": "true",
                "srt.eventLog.dir": events_dir,
                "srt.eventLog.trace.enabled": "true",
                "srt.obs.resource.intervalMs": 50,
            })
            if len(rows) != 30:
                print(f"[chaos] FAIL [telemetry]: expected 30 groups, "
                      f"got {len(rows)}", file=sys.stderr, flush=True)
                failures += 1
        finally:
            driver.shutdown()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
        rep = build_report(events_dir)
        checks = []
        jobs = rep["jobs"]
        checks.append(("one cluster job recorded", len(jobs) == 1))
        if jobs:
            wids = {w["worker_id"] for w in jobs[0]["workers"]}
            checks.append((f"all {n_workers} workers reported TaskEnd",
                           wids == set(range(n_workers))))
        tr = rep.get("trace")
        checks.append(("trace files merged", tr is not None))
        if tr is not None:
            checks.append((f"driver + {n_workers} workers contributed "
                           "spans",
                           len(tr["processes"]) >= n_workers + 1))
            checks.append(("no unparented spans",
                           not tr["unparented"]))
            checks.append(("aligned clock skew < 50ms",
                           tr["max_skew_ms"] is not None
                           and tr["max_skew_ms"] < 50.0))
        res = rep.get("resources")
        checks.append(("resource samples recorded",
                       bool(res and res["samples"])))
        checks.append(("every advisor rule evaluated",
                       len(rep["advisor"]) >= 5))
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [telemetry]: {what}",
                      file=sys.stderr, flush=True)
                failures += 1
        print(f"[chaos] {'PASS' if not failures else 'FAIL'} "
              f"[telemetry: {n_workers}-worker history report] "
              f"{time.monotonic() - t0:.1f}s "
              f"({len(checks)} checks)", flush=True)
    return failures


def _concurrency_check(n_threads: int = 8, queries_per_thread: int = 4,
                       seed: int = 1337) -> int:
    """Concurrent-serving leg: N threads race mixed queries through a
    2-permit admission semaphore over a deliberately small device
    budget, with seeded delay faults widening the cancel windows and
    seeded cancels/deadlines fired mid-flight. Every query must end in
    exactly one of {bit-identical to the serial oracle, QueryCancelled,
    DeadlineExceeded, AdmissionRejected-then-retried-to-identical} —
    and afterwards the engine must be pristine: zero leaked threads,
    zero prefetch-thread leaks, empty budget slices, a drained
    semaphore, and no cross-budget violation (a spill stealing from a
    LIVE sibling's slice) in the event log. Returns failure count."""
    import random as _random

    import numpy as np

    from spark_rapids_tpu.conf import SrtConf, set_active_conf
    from spark_rapids_tpu.exec.pipeline import prefetch_thread_leaks
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.memory.budget import (device_budget,
                                                reset_device_budget)
    from spark_rapids_tpu.memory.spill import reset_spill_catalog
    from spark_rapids_tpu.obs import events as ev
    from spark_rapids_tpu.plan import TpuSession
    from spark_rapids_tpu.robustness.admission import (AdmissionRejected,
                                                       DeadlineExceeded,
                                                       QueryCancelled,
                                                       query_semaphore,
                                                       reset_query_semaphore)
    from spark_rapids_tpu.robustness.faults import (FaultPlan,
                                                    arm_fault_plan,
                                                    disarm_fault_plan)

    failures = 0
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="srt_conc_") as tmp:
        events_dir = os.path.join(tmp, "events")
        data_dir = os.path.join(tmp, "fact")
        rng = np.random.default_rng(seed)
        n = 40_000
        TpuSession(SrtConf({})).create_dataframe({
            "k": rng.integers(0, 64, n).tolist(),
            "v": rng.uniform(0, 10, n).tolist(),
        }).write.parquet(data_dir)

        def shapes(sess):
            scan = sess.read.parquet(data_dir)
            return [
                scan.filter(col("v") < 8.0).group_by("k")
                    .agg(Alias(Sum(col("v")), "s"),
                         Alias(CountStar(), "c")).sort("k"),
                scan.group_by("k")
                    .agg(Alias(CountStar(), "c")).sort("k"),
                scan.filter(col("v") >= 2.0).group_by("k")
                    .agg(Alias(Sum(col("v")), "s")).sort("k"),
            ]

        oracles = [d.collect() for d in shapes(TpuSession(SrtConf({})))]
        conf = SrtConf({
            "srt.sql.concurrentQueryTasks": "2",
            "srt.sql.admission.maxQueueDepth": "3",
            "srt.sql.admission.backoffBaseSec": "0.01",
            "srt.eventLog.enabled": "true",
            "srt.eventLog.dir": events_dir,
        })
        # contention: a small shared budget forces spill pressure
        # across the slices, and the delay faults stretch reserve and
        # scan long enough for cancels/deadlines to land mid-query
        reset_device_budget(24 << 20)
        reset_spill_catalog()
        reset_query_semaphore(conf)
        arm_fault_plan(FaultPlan.parse(
            f"seed={seed}|memory.reserve:delay%0.15*40+0.01"
            f"|scan.file:delay%0.2*30+0.01"))
        leaks_before = prefetch_thread_leaks()
        baseline = {t.ident for t in threading.enumerate()}
        outcomes = {"identical": 0, "cancelled": 0, "deadline": 0,
                    "retried": 0}
        errors = []
        timers = []
        timers_lock = threading.Lock()

        def worker(i):
            r = _random.Random(seed * 1000 + i)
            set_active_conf(conf)
            sess = TpuSession(conf)
            plans = shapes(sess)
            for q in range(queries_per_thread):
                shape = r.randrange(len(plans))
                action = r.choice(["none", "none", "cancel",
                                   "deadline", "tiny-deadline"])
                timeout = None
                if action == "deadline":
                    timeout = r.uniform(0.02, 0.2)
                elif action == "tiny-deadline":
                    timeout = 1e-4  # certain to trip: proves the path
                elif action == "cancel":
                    tm = threading.Timer(r.uniform(0.01, 0.15),
                                         sess.cancel, ("chaos cancel",))
                    tm.daemon = True
                    with timers_lock:
                        timers.append(tm)
                    tm.start()
                rejected = 0
                while True:
                    try:
                        rows = plans[shape].collect(timeout=timeout)
                        if rows == oracles[shape]:
                            outcomes["retried" if rejected
                                     else "identical"] += 1
                        else:
                            errors.append(
                                f"t{i} q{q} shape{shape} diverged "
                                f"({len(rows)} rows)")
                        break
                    except QueryCancelled:
                        outcomes["cancelled"] += 1
                        break
                    except DeadlineExceeded:
                        outcomes["deadline"] += 1
                        break
                    except AdmissionRejected:
                        rejected += 1
                        if rejected > 25:
                            errors.append(f"t{i} q{q}: admission never "
                                          f"succeeded after {rejected}")
                            break
                        time.sleep(0.01 * rejected)
                    except BaseException as e:  # noqa: BLE001
                        errors.append(f"t{i} q{q}: unexpected "
                                      f"{type(e).__name__}: {e}")
                        break

        threads = [threading.Thread(target=worker, args=(i,),
                                    name=f"chaos-conc-{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        with timers_lock:
            for tm in timers:
                tm.cancel()
                tm.join(5)
        for msg in errors:
            print(f"[chaos] FAIL [concurrency]: {msg}",
                  file=sys.stderr, flush=True)
        failures += len(errors)

        sem = query_semaphore(conf)
        checks = [
            ("every typed outcome observed at least once",
             outcomes["deadline"] > 0 and outcomes["identical"] > 0),
            ("admission semaphore drained",
             sem.active() == 0 and sem.queue_depth() == 0),
            ("budget slices all unregistered",
             device_budget().active_owners() == set()),
            ("zero prefetch thread leaks",
             prefetch_thread_leaks() == leaks_before),
        ]
        # worker threads (prefetch producers, timers) must all be gone;
        # give slow daemon exits a settle window before declaring leaks
        settle = time.monotonic() + 5.0
        stray = [t for t in threading.enumerate()
                 if t.ident not in baseline and t.is_alive()]
        while stray and time.monotonic() < settle:
            time.sleep(0.1)
            stray = [t for t in threading.enumerate()
                     if t.ident not in baseline and t.is_alive()]
        checks.append(("zero leaked threads", not stray))
        # cross-budget isolation: no spill may have evicted a LIVE
        # sibling query's batch (idle/finished owners are fair game)
        recs = ev.read_all_events(events_dir)
        violations = [r for r in recs
                      if r.get("event") == "CrossQuerySpill"
                      and r.get("owner_active")]
        checks.append(("zero cross-budget violations", not violations))
        admitted = sum(1 for r in recs
                       if r.get("event") == "QueryAdmitted")
        checks.append(("admission events logged", admitted > 0))
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [concurrency]: {what}"
                      + (f" (stray={[t.name for t in stray]})"
                         if what == "zero leaked threads" else "")
                      + (f" ({len(violations)} violations)"
                         if what == "zero cross-budget violations"
                         else ""),
                      file=sys.stderr, flush=True)
                failures += 1
        # restore process-wide state for whatever runs next
        disarm_fault_plan()
        reset_query_semaphore()
        reset_device_budget(None)
        reset_spill_catalog()
        ev.configure_from_conf(SrtConf({}))
        print(f"[chaos] {'PASS' if not failures else 'FAIL'} "
              f"[concurrency: {n_threads} threads x "
              f"{queries_per_thread} queries, outcomes={outcomes}] "
              f"{time.monotonic() - t0:.1f}s "
              f"({len(checks)} checks)", flush=True)
    return failures


def _adaptive_check(n_workers: int = 2) -> int:
    """Adaptive-execution leg: seeded skewed data under deliberately
    WRONG compile-time settings (broadcast disabled by a 1-row
    threshold, a skew threshold far below the hot partition, a row
    floor far above every partition) on a real cluster, swept adaptive
    ON and OFF. The two sweeps must produce identical, oracle-matching
    results, the ON sweep's event log must carry at least one of every
    decision event (AdaptivePlanChanged for coalescePartitions /
    skewJoin / joinStrategy, SkewSplit), and an injected 4 s straggler
    under speculation must leave a SpeculativeTask launch/result pair.
    Returns failure count."""
    import numpy as np

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.obs import events as ev
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    from spark_rapids_tpu.plan import TpuSession

    failures = 0
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="srt_adaptive_") as tmp:
        session = TpuSession(SrtConf({}))
        rng = np.random.default_rng(37)
        n = 12_000
        # ~90% of rows share one hot key: the skew the compile-time
        # plan knows nothing about
        keys = np.where(rng.random(n) < 0.9, 7,
                        rng.integers(0, 40, n))
        fact_dir = os.path.join(tmp, "fact")
        session.create_dataframe({
            "k": keys.tolist(),
            "v": rng.uniform(0, 10, n).tolist(),
        }).write.parquet(fact_dir)
        dim_dir = os.path.join(tmp, "dim")
        session.create_dataframe({
            "k": list(range(40)),
            "w": [i * 2 for i in range(40)],
        }).write.parquet(dim_dir)
        events_dir = os.path.join(tmp, "events")

        # a downstream group-by would PIN the join's partitioning and
        # (correctly) stand the join rules down, so the join runs bare
        def join_plan(sess):
            f = sess.read.parquet(fact_dir)
            d = sess.read.parquet(dim_dir)
            return f.join(d, ([col("k")], [col("k")]), how="inner")

        def agg_plan(sess):
            return sess.read.parquet(fact_dir).group_by("k").agg(
                Alias(Sum(col("v")), "s"), Alias(CountStar(), "c"))

        def canon(which, rows):
            if which == "join":
                return sorted((r["k"], round(r["v"], 6), r["w"])
                              for r in rows)
            return sorted((r["k"], r["c"], round(r["s"], 6))
                          for r in rows)

        oracle_sess = TpuSession(SrtConf(
            {"srt.sql.adaptive.enabled": "false",
             "srt.sql.broadcastRowThreshold": 1}))
        oracles = {
            "join": canon("join", join_plan(oracle_sess).collect()),
            "agg": canon("agg", agg_plan(oracle_sess).collect())}

        # driver-side sink: SpeculativeTask launch/result events are
        # emitted by the DRIVER's barrier, i.e. this process
        ev.install(ev.EventLogWriter(events_dir))
        driver = ClusterDriver(num_workers=n_workers,
                               barrier_timeout=60,
                               heartbeat_interval=0.5,
                               heartbeat_timeout=10)
        procs = launch_local_workers(driver, n_workers)
        base_conf = {"srt.shuffle.partitions": 4,
                     "srt.cluster.barrierTimeoutSec": 60,
                     "srt.eventLog.enabled": "true",
                     "srt.eventLog.dir": events_dir}
        # (name, plan builder, wrong-settings conf)
        runs = [
            ("skew split", join_plan,
             {"srt.sql.broadcastRowThreshold": 1,
              "srt.sql.adaptive.autoBroadcastJoinRows": 1,
              "srt.sql.adaptive.skewJoin.partitionRows": 1000,
              "srt.sql.adaptive.coalescePartitions.minPartitionRows":
                  1}),
            ("broadcast demote", join_plan,
             {"srt.sql.broadcastRowThreshold": 1,
              "srt.sql.adaptive.autoBroadcastJoinRows": 100000}),
            ("speculated straggler + coalesce", agg_plan,
             {"srt.sql.adaptive.coalescePartitions.minPartitionRows":
                  1 << 16,
              "srt.sql.adaptive.speculation.enabled": "true",
              "srt.sql.adaptive.speculation.minWaitSec": "0.3",
              "srt.sql.adaptive.speculation.slowWorkerFactor": "1.0",
              "srt.test.faultPlan":
                  "seed=7|cluster.barrier:delay@1+4.0~workers=1;"}),
        ]
        try:
            driver.wait_for_workers(timeout=120)
            for name, build, extra in runs:
                which = "join" if build is join_plan else "agg"
                for label, on in (("adaptive=on", "true"),
                                  ("adaptive=off", "false")):
                    if build is agg_plan and on == "false":
                        continue  # the off leg would just wait 4s
                    conf = dict(base_conf, **extra)
                    conf["srt.sql.adaptive.enabled"] = on
                    t = time.monotonic()
                    try:
                        rows = driver.run(build(session).plan, conf)
                    except Exception as e:
                        print(f"[chaos] FAIL [adaptive: {name} "
                              f"{label}]: job raised "
                              f"{type(e).__name__}: {e}",
                              file=sys.stderr, flush=True)
                        failures += 1
                        continue
                    ok = canon(which, rows) == oracles[which]
                    print(f"[chaos] {'PASS' if ok else 'FAIL'} "
                          f"[adaptive: {name} {label}] "
                          f"{time.monotonic() - t:.1f}s", flush=True)
                    if not ok:
                        failures += 1
        finally:
            ev.install(None)
            driver.shutdown()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
        recs = ev.read_all_events(events_dir)
        rules = {r.get("rule") for r in recs
                 if r.get("event") == "AdaptivePlanChanged"}
        spec_phases = {r.get("phase") for r in recs
                       if r.get("event") == "SpeculativeTask"}
        checks = [
            ("coalescePartitions decision logged",
             "coalescePartitions" in rules),
            ("skewJoin decision logged", "skewJoin" in rules),
            ("joinStrategy decision logged", "joinStrategy" in rules),
            ("SkewSplit events logged",
             any(r.get("event") == "SkewSplit" for r in recs)),
            ("speculation launch + result logged",
             {"launch", "result"} <= spec_phases),
        ]
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [adaptive]: {what}",
                      file=sys.stderr, flush=True)
                failures += 1
        print(f"[chaos] {'PASS' if not failures else 'FAIL'} "
              f"[adaptive: skew/demote/coalesce/speculation sweep] "
              f"{time.monotonic() - t0:.1f}s ({len(checks)} checks)",
              flush=True)
    return failures


def _push_shuffle_check(n_workers: int = 2) -> int:
    """Push-shuffle leg: one join+agg plan on a real cluster swept
    across push on (eager push + segment consolidation), push off
    (classic pull), corrupt-on-wire (receiver NAKs, sender resends),
    corrupt-at-rest-in-segment (per-entry quarantine, pull refetches
    exactly that block), and a worker killed mid-push (stage retry on
    the survivor; its stale pushed segments must never serve). Every
    sweep must produce oracle-identical results — push is replication,
    so no push-path fault may change WHAT a query returns, only where
    bytes travel. Returns failure count."""
    import numpy as np

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.obs import events as ev
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    from spark_rapids_tpu.plan import TpuSession

    failures = 0
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="srt_push_") as tmp:
        session = TpuSession(SrtConf({}))
        rng = np.random.default_rng(41)
        n = 6_000
        fact_dir = os.path.join(tmp, "fact")
        session.create_dataframe({
            "k": rng.integers(0, 40, n).tolist(),
            "v": rng.uniform(0, 10, n).tolist(),
        }).write.parquet(fact_dir)
        dim_dir = os.path.join(tmp, "dim")
        session.create_dataframe({
            "k": list(range(40)),
            "w": [float(1 + i % 5) for i in range(40)],
        }).write.parquet(dim_dir)
        events_dir = os.path.join(tmp, "events")

        def logical(sess):
            fact = sess.read.parquet(fact_dir)
            dim = sess.read.parquet(dim_dir)
            return fact.join(dim, on="k") \
                .group_by("k").agg(Alias(Sum(col("v") * col("w")), "s"),
                                   Alias(CountStar(), "c")) \
                .sort("k")

        def canon(rows):
            return sorted((r["k"], r["c"], round(r["s"], 6))
                          for r in rows)

        oracle = canon(logical(TpuSession(SrtConf({}))).collect())

        driver = ClusterDriver(num_workers=n_workers,
                               barrier_timeout=60,
                               heartbeat_interval=0.5,
                               heartbeat_timeout=6)
        procs = launch_local_workers(driver, n_workers)
        base_conf = {"srt.shuffle.partitions": 4,
                     "srt.cluster.barrierTimeoutSec": 60,
                     "srt.eventLog.enabled": "true",
                     "srt.eventLog.dir": events_dir}
        # (name, extra job conf, FaultInjected site that must appear).
        # The crash leg runs LAST: it permanently costs a worker, and
        # the ~w=1; match pins the os._exit to worker 1's push path so
        # the survivor (w=0) carries the stage retry.
        legs = [
            ("push on", {}, None),
            ("push off", {"srt.shuffle.push.enabled": "false"}, None),
            ("corrupt on wire",
             {"srt.test.faultPlan":
                  "seed=51|shuffle.block.pushwire:corrupt@1"},
             "shuffle.block.pushwire"),
            ("corrupt at rest in segment",
             {"srt.test.faultPlan":
                  "seed=53|shuffle.segment.store:corrupt@1"},
             "shuffle.segment.store"),
            ("worker kill mid-push",
             {"srt.test.faultPlan": "seed=55|push.send:crash@1~w=1;"},
             "push.send"),
        ]
        results = {}
        try:
            driver.wait_for_workers(timeout=120)
            for name, extra, _site in legs:
                job_conf = dict(base_conf, **extra)
                t = time.monotonic()
                try:
                    rows = driver.run(logical(session).plan, job_conf)
                except Exception as e:
                    print(f"[chaos] FAIL [push: {name}]: job raised "
                          f"{type(e).__name__}: {e}",
                          file=sys.stderr, flush=True)
                    failures += 1
                    continue
                results[name] = canon(rows)
                ok = results[name] == oracle
                print(f"[chaos] {'PASS' if ok else 'FAIL'} "
                      f"[push: {name}] {time.monotonic() - t:.1f}s "
                      f"workers={driver.num_workers}", flush=True)
                if not ok:
                    failures += 1
        finally:
            driver.shutdown()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
        recs = ev.read_all_events(events_dir)
        fired = {r.get("site") for r in recs
                 if r.get("event") == "FaultInjected"}
        checks = [
            # identical-recovery: flipping push on/off must not change
            # the answer (same rows either way, both oracle-equal)
            ("push on/off identical results",
             "push on" in results and "push off" in results
             and results["push on"] == results["push off"]),
            # each fault must actually have hit the push path — a leg
            # that silently never pushed would pass vacuously
            ("on-wire corruption fired on push path",
             "shuffle.block.pushwire" in fired),
            ("at-rest segment corruption fired",
             "shuffle.segment.store" in fired),
            ("mid-push crash fired", "push.send" in fired),
            ("worker loss recovered via stage retry",
             any(e["type"] == "stage_retry"
                 for e in driver.recovery_events)),
        ]
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [push]: {what}",
                      file=sys.stderr, flush=True)
                failures += 1
        print(f"[chaos] {'PASS' if not failures else 'FAIL'} "
              f"[push: on/off/corrupt-wire/corrupt-rest/kill sweep] "
              f"{time.monotonic() - t0:.1f}s ({len(checks)} checks)",
              flush=True)
    return failures


def _membership_check(n_workers: int = 3) -> int:
    """Elastic-membership leg: one cluster taken through the full
    membership lifecycle. (1) k=2 buddy replication with every remote
    pull serve dying — readers must degrade to manifest-covered
    replica fetches and finish with ZERO stage retries, bit-identical;
    (2) a SIGTERM graceful decommission landing MID-query — the worker
    finishes its job first (zero retries), migrates, deregisters, and
    the survivors serve the next query; (3) a hard SIGKILL mid-query —
    eviction + stage/job retry recover the answer, the dead
    incarnation's epoch is fenced (a zombie barrier frame is refused),
    a replacement rejoins over the dead endpoint and serves queries,
    and the driver's recovery_time_ns p99 stays under budget. The
    mid-stream kill-and-resume probe from the scale roadmap folds in
    here as leg 3. Returns failure count."""
    import pickle
    import signal
    import socket as _socket
    import struct

    import numpy as np

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.obs import events as ev
    from spark_rapids_tpu.obs import registry as obs_registry
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    from spark_rapids_tpu.plan import TpuSession

    failures = 0
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="srt_member_") as tmp:
        session = TpuSession(SrtConf({}))
        rng = np.random.default_rng(61)
        n = 6_000
        fact_dir = os.path.join(tmp, "fact")
        session.create_dataframe({
            "k": rng.integers(0, 40, n).tolist(),
            "v": rng.uniform(0, 10, n).tolist(),
        }).write.parquet(fact_dir)
        dim_dir = os.path.join(tmp, "dim")
        session.create_dataframe({
            "k": list(range(40)),
            "w": [float(1 + i % 3) for i in range(40)],
        }).write.parquet(dim_dir)
        events_dir = os.path.join(tmp, "events")

        def logical(sess):
            f = sess.read.parquet(fact_dir)
            d = sess.read.parquet(dim_dir)
            return f.join(d, on="k") \
                .group_by("k").agg(Alias(Sum(col("v") * col("w")), "s"),
                                   Alias(CountStar(), "c")) \
                .sort("k")

        def canon(rows):
            return sorted((r["k"], r["c"], round(r["s"], 6))
                          for r in rows)

        oracle = canon(logical(TpuSession(SrtConf({}))).collect())

        driver = ClusterDriver(num_workers=n_workers, barrier_timeout=60,
                               heartbeat_interval=0.5,
                               heartbeat_timeout=6)
        procs = launch_local_workers(driver, n_workers)
        base_conf = {"srt.shuffle.partitions": 4,
                     "srt.cluster.barrierTimeoutSec": 60,
                     "srt.sql.broadcastRowThreshold": 1,
                     "srt.eventLog.enabled": "true",
                     "srt.eventLog.dir": events_dir}
        checks = []

        def _run_async(conf):
            out: dict = {}
            # barrier keys survive a finished job, so "in flight" means
            # a key that was NOT there before this one was dispatched
            seen = set(driver._barriers) | set(driver._spec_barriers)

            def _go():
                try:
                    out["rows"] = driver.run(logical(session).plan, conf)
                except Exception as e:  # noqa: BLE001
                    out["error"] = e
            th = threading.Thread(target=_go)
            th.start()
            # wait until the job is IN FLIGHT (first stage-barrier
            # arrival) so the chaos action lands mid-query, never
            # pre-empting the dispatch
            deadline = time.monotonic() + 60
            while not ((set(driver._barriers)
                        | set(driver._spec_barriers)) - seen) \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            return th, out

        try:
            driver.wait_for_workers(timeout=120)

            # --- leg 1: buddy replication vs dead pull serves ---
            t = time.monotonic()
            recov_before = len(driver.recovery_events)
            conf = dict(base_conf,
                        **{"srt.shuffle.push.enabled": "false",
                           "srt.shuffle.replication.factor": "2",
                           "srt.shuffle.fetch.maxRetries": "1",
                           "srt.shuffle.fetch.backoffBaseSec": "0.01",
                           "srt.test.faultPlan":
                               "seed=61|transport.serve:reset%1.0*999"})
            leg_fail = 0
            try:
                rows = driver.run(logical(session).plan, conf)
            except Exception as e:  # noqa: BLE001
                print(f"[chaos] FAIL [membership: buddy fetch]: job "
                      f"raised {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
                leg_fail += 1
            else:
                delta = [e["type"] for e in
                         driver.recovery_events[recov_before:]]
                recs = ev.read_all_events(events_dir)
                checks += [
                    ("buddy-fetch result bit-identical",
                     canon(rows) == oracle),
                    ("buddy-fetch zero stage/job retries", not delta),
                    ("buddy-fetch recovery span recorded",
                     any(r.get("event") == "RecoveryTimed"
                         and r.get("kind") == "buddy_fetch"
                         and r.get("recovery_time_ns", 0) > 0
                         for r in recs)),
                    ("replica fetches logged",
                     any(r.get("event") == "ReplicaFetch"
                         for r in recs)),
                ]
            print(f"[chaos] {'PASS' if not leg_fail else 'FAIL'} "
                  f"[membership: buddy fetch vs dead serves] "
                  f"{time.monotonic() - t:.1f}s", flush=True)
            failures += leg_fail

            # --- leg 2: SIGTERM graceful decommission mid-query ---
            t = time.monotonic()
            recov_before = len(driver.recovery_events)
            th, out = _run_async(dict(base_conf))
            procs[-1].send_signal(signal.SIGTERM)
            th.join(120)
            # the worker decommissions only AFTER its job replies;
            # wait for the driver-side completion record
            deadline = time.monotonic() + 60
            while not any(
                    e["type"] == "decommission"
                    for e in driver.recovery_events[recov_before:]) \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
            delta = [e["type"] for e in
                     driver.recovery_events[recov_before:]]
            recs = ev.read_all_events(events_dir)
            leg_ok = not th.is_alive() and "error" not in out
            checks += [
                ("decommission query completed",
                 leg_ok and canon(out.get("rows") or []) == oracle),
                ("decommission zero stage/job retries",
                 "stage_retry" not in delta
                 and "job_retry" not in delta),
                ("decommission recorded", "decommission" in delta),
                ("WorkerDecommissioned event logged",
                 any(r.get("event") == "WorkerDecommissioned"
                     for r in recs)),
                ("roster shrank by one",
                 driver.num_workers == n_workers - 1),
            ]
            # survivors serve the next query
            rows = driver.run(logical(session).plan, dict(base_conf))
            checks.append(("survivors serve post-decommission query",
                           canon(rows) == oracle))
            print(f"[chaos] PASS [membership: SIGTERM decommission "
                  f"mid-query] {time.monotonic() - t:.1f}s", flush=True)

            # --- leg 3: hard kill mid-query, fence, rejoin ---
            t = time.monotonic()
            # the decommissioned process may still be tearing down:
            # wait it out so the victim below is a live roster member
            deadline = time.monotonic() + 30
            while len([p for p in procs if p.poll() is None]) \
                    > n_workers - 1 and time.monotonic() < deadline:
                time.sleep(0.1)
            roster = {eid: ep for _s, ep, eid in driver._workers}
            recov_before = len(driver.recovery_events)
            th, out = _run_async(dict(base_conf))
            victim = [p for p in procs if p.poll() is None][-1]
            victim.kill()
            th.join(180)
            if "error" in out:
                print(f"[chaos] [membership] kill-leg query raised "
                      f"{type(out['error']).__name__}: {out['error']}",
                      file=sys.stderr, flush=True)
            elif canon(out.get("rows") or []) != oracle:
                got = canon(out.get("rows") or [])
                print(f"[chaos] [membership] kill-leg mismatch: "
                      f"{len(got)} groups vs {len(oracle)}, "
                      f"count={sum(g[1] for g in got)} vs "
                      f"{sum(g[1] for g in oracle)}, "
                      f"diff={[g for g in got if g not in oracle][:3]}"
                      f" missing="
                      f"{[g for g in oracle if g not in got][:3]}",
                      file=sys.stderr, flush=True)
            delta = [e["type"] for e in
                     driver.recovery_events[recov_before:]]
            recs = ev.read_all_events(events_dir)
            checks += [
                ("kill-recovery result bit-identical",
                 not th.is_alive() and "error" not in out
                 and canon(out.get("rows") or []) == oracle),
                # mid-dialogue deaths are caught by socket-close before
                # the heartbeat monitor fires; either way a retry must
                # have recovered the attempt
                ("stage/job retry recorded",
                 "stage_retry" in delta or "job_retry" in delta),
                ("WorkerEvicted event logged",
                 any(r.get("event") == "WorkerEvicted" for r in recs)),
            ]
            live = {eid for _s, _ep, eid in driver._workers}
            dead = set(roster) - live
            fence_ok = False
            rejoin_ok = False
            if len(dead) == 1:
                (dead_eid,) = dead
                dead_ep = roster[dead_eid]
                # zombie probe: a barrier frame carrying the fenced
                # epoch must be refused before touching the registry
                frame = struct.Struct(">I")
                payload = pickle.dumps(
                    {"type": "barrier", "shuffle_id": 999, "worker": 9,
                     "pos": -1, "epoch": driver._epochs[dead_eid]})
                with _socket.create_connection(driver.address,
                                               timeout=10) as s:
                    s.sendall(frame.pack(len(payload)) + payload)
                    (ln,) = frame.unpack(s.recv(4))
                    reply = pickle.loads(s.recv(ln))
                fence_ok = reply.get("type") == "fenced"
                # rejoin over the dead endpoint; ownership reroutes
                procs.extend(launch_local_workers(
                    driver, 1, env={"SRT_REJOIN_ENDPOINT": dead_ep}))
                driver.wait_for_n_workers(n_workers - 1, timeout=120)
                deadline = time.monotonic() + 30
                new_ep = next(ep for _s, ep, eid in driver._workers
                              if eid not in roster)
                while driver._heartbeats.resolve(dead_ep) != new_ep \
                        and time.monotonic() < deadline:
                    time.sleep(0.2)
                rows = driver.run(logical(session).plan,
                                  dict(base_conf))
                rejoin_ok = (canon(rows) == oracle
                             and driver._heartbeats.resolve(dead_ep)
                             == new_ep)
            checks += [
                ("zombie barrier frame fenced", fence_ok),
                ("rejoined worker serves queries", rejoin_ok),
            ]
            hist = obs_registry.registry().histogram("recovery_time_ns")
            snap = hist.snapshot() if hist is not None else {}
            checks += [
                ("recovery_time histogram populated",
                 snap.get("count", 0) >= 1),
                ("recovery_time p99 under 120s budget",
                 0 < snap.get("p99", 0) < 120e9),
            ]
            recs = ev.read_all_events(events_dir)
            checks.append(("zero prefetch thread leaks across "
                           "membership churn",
                           not any(r.get("event") == "PrefetchThreadLeak"
                                   for r in recs)))
            print(f"[chaos] PASS [membership: kill + fence + rejoin] "
                  f"{time.monotonic() - t:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"[chaos] FAIL [membership]: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            failures += 1
        finally:
            driver.shutdown()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [membership]: {what}",
                      file=sys.stderr, flush=True)
                failures += 1
        print(f"[chaos] {'PASS' if not failures else 'FAIL'} "
              f"[membership: replication/decommission/kill/rejoin] "
              f"{time.monotonic() - t0:.1f}s ({len(checks)} checks)",
              flush=True)
    return failures


def _rows_match(rows, oracle):
    if [r["k"] for r in rows] != [r["k"] for r in oracle]:
        return False
    for got, want in zip(rows, oracle):
        if got["c"] != want["c"]:
            return False
        if abs(got["s"] - want["s"]) > 1e-6 * max(1.0, abs(want["s"])):
            return False
    return True


def _serving_check() -> int:
    """Serving front-door leg (spark_rapids_tpu/serve/):

    1. a client CHILD PROCESS is SIGKILLed mid-stream — the server
       must cancel the query, release the admission permit and budget
       slice, close live prefetch iterators (zero leaked threads),
       drop the session, and keep serving;
    2. a seeded byte-flip on a cached result batch
       (``serve.result_cache:corrupt@1``) must evict the entry and
       recompute BIT-IDENTICALLY, never serve garbage;
    3. a load-shed probe at queue-depth 0 — the shed is a retryable
       SHED frame and the hog completes untouched.

    Returns failure count."""
    import signal
    import subprocess

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.exec.pipeline import prefetch_thread_leaks
    from spark_rapids_tpu.memory.budget import device_budget
    from spark_rapids_tpu.plan import TpuSession
    from spark_rapids_tpu.robustness.admission import (
        query_semaphore, reset_query_semaphore)
    from spark_rapids_tpu.robustness.faults import (arm_fault_plan,
                                                    disarm_fault_plan)
    from spark_rapids_tpu.serve import ServeLoadShed, SqlClient, \
        SqlServer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failures = 0
    slow_sql = "SELECT k, sum(v) AS s FROM f GROUP BY k ORDER BY k"

    with tempfile.TemporaryDirectory(prefix="srt_serve_") as tmp:
        session = TpuSession(SrtConf({
            "srt.shuffle.partitions": 2,
            "srt.sql.resultCache.enabled": "true"}))
        fact_dir = os.path.join(tmp, "fact")
        session.create_dataframe({
            "k": [i % 40 for i in range(8000)],
            "v": [float(i % 97) for i in range(8000)],
        }).write.parquet(fact_dir)
        session.create_or_replace_temp_view(
            "f", session.read.parquet(fact_dir))
        oracle = session.sql(slow_sql).collect()

        # --- leg 1: SIGKILL a client child mid-stream --------------
        t = time.monotonic()
        name = "serve: client SIGKILL mid-stream"
        leaks0 = prefetch_thread_leaks()
        with SqlServer(session) as server:
            # hold the query in its scan so the kill provably lands
            # while it is in flight server-side
            arm_fault_plan("seed=7|scan.file:delay@1+3.0")
            try:
                child = subprocess.Popen(
                    [sys.executable, "-c",
                     "import sys; sys.path.insert(0, sys.argv[1]); "
                     "from spark_rapids_tpu.serve import SqlClient; "
                     "c = SqlClient(sys.argv[2], tenant='victim'); "
                     "print('connected', flush=True); "
                     "c.submit(sys.argv[3])",
                     root, server.endpoint, slow_sql],
                    cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                    stdout=subprocess.PIPE, text=True)
                assert child.stdout is not None
                child.stdout.readline()  # "connected": session is up
                deadline = time.monotonic() + 30
                while query_semaphore(session.conf).active() == 0 \
                        and time.monotonic() < deadline:
                    time.sleep(0.02)
                in_flight = query_semaphore(session.conf).active() > 0
                child.send_signal(signal.SIGKILL)
                child.wait(timeout=30)
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline and (
                        server.open_sessions()
                        or query_semaphore(session.conf).active()
                        or device_budget().active_owners()):
                    time.sleep(0.05)
                with SqlClient(server.endpoint) as probe:
                    after = probe.submit(slow_sql, cache=False)
            finally:
                disarm_fault_plan()
            checks = [
                ("query was in flight at kill time", in_flight),
                ("admission permit released",
                 query_semaphore(session.conf).active() == 0),
                (f"budget slices released "
                 f"({device_budget().active_owners()})",
                 device_budget().active_owners() == set()),
                ("session torn down", server.open_sessions() == 0),
                ("disconnect cancelled the query server-side",
                 server.disconnect_cancels >= 1),
                (f"zero leaked prefetch threads "
                 f"({prefetch_thread_leaks() - leaks0})",
                 prefetch_thread_leaks() == leaks0),
                ("server keeps serving after the kill",
                 after.info.get("status") == "ok"
                 and [dict(r) for r in (
                     {k: after.to_pydict()[k][i]
                      for k in after.to_pydict()}
                     for i in range(after.num_rows))] == oracle),
            ]
            leg_fail = sum(1 for _w, ok in checks if not ok)
            for what, ok in checks:
                if not ok:
                    print(f"[chaos] FAIL [{name}]: {what}",
                          file=sys.stderr, flush=True)
            print(f"[chaos] {'PASS' if not leg_fail else 'FAIL'} "
                  f"[{name}] {time.monotonic() - t:.1f}s", flush=True)
            failures += leg_fail

            # --- leg 2: seeded corrupt cached result batch ---------
            t = time.monotonic()
            name = "serve: corrupt cached result -> evict + recompute"
            with SqlClient(server.endpoint, tenant="c2") as c:
                fill = c.submit(slow_sql)
                arm_fault_plan("seed=9|serve.result_cache:corrupt@1")
                try:
                    recomputed = c.submit(slow_sql)
                finally:
                    disarm_fault_plan()
                again = c.submit(slow_sql)
            cache = server.result_cache
            checks = [
                ("fill was a miss", fill.info.get("cache") == "miss"),
                ("corrupted entry evicted "
                 f"(corrupt_evictions={cache.corrupt_evictions})",
                 cache.corrupt_evictions >= 1),
                ("recompute was a miss, not served garbage",
                 recomputed.info.get("cache") == "miss"),
                ("recompute bit-identical to the fill",
                 recomputed.payloads == fill.payloads),
                ("clean refill serves the hit",
                 again.info.get("cache") == "hit"
                 and again.payloads == fill.payloads),
            ]
            leg_fail = sum(1 for _w, ok in checks if not ok)
            for what, ok in checks:
                if not ok:
                    print(f"[chaos] FAIL [{name}]: {what}",
                          file=sys.stderr, flush=True)
            print(f"[chaos] {'PASS' if not leg_fail else 'FAIL'} "
                  f"[{name}] {time.monotonic() - t:.1f}s", flush=True)
            failures += leg_fail

        # --- leg 3: load-shed probe at queue-depth 0 ---------------
        t = time.monotonic()
        name = "serve: load-shed at queue-depth cap"
        shed_sess = TpuSession(SrtConf({
            "srt.shuffle.partitions": 2,
            "srt.sql.concurrentQueryTasks": "1",
            "srt.sql.admission.maxQueueDepth": "0"}))
        shed_sess.create_or_replace_temp_view(
            "f", shed_sess.read.parquet(fact_dir))
        reset_query_semaphore(shed_sess.conf)
        arm_fault_plan("seed=11|scan.file:delay@1+2.0")
        try:
            with SqlServer(shed_sess) as server:
                outcome = {}

                def hog():
                    try:
                        with SqlClient(server.endpoint,
                                       tenant="hog") as c:
                            outcome["hog"] = \
                                c.submit(slow_sql).info["status"]
                    except BaseException as e:  # noqa: BLE001
                        outcome["hog"] = repr(e)

                th = threading.Thread(target=hog)
                th.start()
                deadline = time.monotonic() + 15
                while query_semaphore(shed_sess.conf).active() == 0 \
                        and time.monotonic() < deadline:
                    time.sleep(0.02)
                shed = retryable = False
                with SqlClient(server.endpoint, tenant="shed") as c:
                    try:
                        c.submit(slow_sql)
                    except ServeLoadShed as e:
                        shed, retryable = True, e.retryable
                th.join(60)
                checks = [
                    ("second submit load-shed as SHED frame", shed),
                    ("shed marked retryable", retryable),
                    ("server counted the shed",
                     server.load_shed >= 1),
                    (f"hog completed untouched ({outcome.get('hog')})",
                     outcome.get("hog") == "ok"),
                ]
        finally:
            disarm_fault_plan()
            reset_query_semaphore()
        leg_fail = sum(1 for _w, ok in checks if not ok)
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [{name}]: {what}",
                      file=sys.stderr, flush=True)
        print(f"[chaos] {'PASS' if not leg_fail else 'FAIL'} "
              f"[{name}] {time.monotonic() - t:.1f}s", flush=True)
        failures += leg_fail
    return failures


def _streaming_ingest_check() -> int:
    """Exactly-once ingestion leg: the streaming ingester child
    (``python -m spark_rapids_tpu.delta.streaming``) is SIGKILLed
    mid-ingest at a seeded fault point in each layer of the commit
    protocol — data-file staging, staged->final rename, the commit
    link, the pre-link fsync — then relaunched with no plan. Each
    resume must land exactly-once row counts (the txn log skips the
    batches that survived the kill), leave ZERO orphans after the
    vacuum sweep, and zero staging leftovers. A final in-process leg
    fences a stale-epoch incumbent and asserts the refusal is
    observable (StaleWriterFenced). Returns failure count."""
    import subprocess

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.delta import AcidTable, StaleWriterEpoch
    from spark_rapids_tpu.delta.streaming import (DeltaIngestor,
                                                  demo_batch_dict,
                                                  demo_expected,
                                                  demo_schema)
    from spark_rapids_tpu.obs import events as ev
    from spark_rapids_tpu.plan import TpuSession

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    failures = 0
    # hit counts: CREATE and the epoch acquisition are commits 1-2
    # (they stage no data files), so these land mid-stream, never on
    # the bootstrap commits
    sites = [("delta.stage", "crash@2"),
             ("delta.rename", "crash@2"),
             ("delta.commit", "crash@4"),
             ("delta.commit.fsync", "crash@3")]
    batches, rows = 6, 50
    expect = demo_expected(batches, rows)
    session = TpuSession(SrtConf({}))
    with tempfile.TemporaryDirectory(prefix="srt_ingest_") as tmp:
        for i, (site, action) in enumerate(sites):
            t = time.monotonic()
            name = f"ingest: kill at {site}"
            table = os.path.join(tmp, f"t{i}")
            cmd = [sys.executable, "-m",
                   "spark_rapids_tpu.delta.streaming", table, "chaos",
                   str(batches), str(rows), "--create"]
            p = subprocess.run(
                cmd + ["--fault-plan", f"seed={31 + i}|{site}:{action}"],
                cwd=root, env=env, capture_output=True, text=True,
                timeout=180)
            checks = [(f"child killed mid-ingest (rc 137, got "
                       f"{p.returncode})", p.returncode == 137)]
            p = subprocess.run(cmd, cwd=root, env=env,
                               capture_output=True, text=True,
                               timeout=180)
            checks.append((f"resume run exits 0 (got {p.returncode})",
                           p.returncode == 0))
            at = AcidTable.for_path(session, table)
            got = at.to_df().collect()
            sum_v = sum(r["v"] for r in got)
            checks += [
                (f"exactly-once rows ({len(got)}/{expect['rows']})",
                 len(got) == expect["rows"]),
                ("no duplicated ids",
                 len({r["id"] for r in got}) == expect["distinct_ids"]),
                (f"sum(v) exact ({sum_v} vs {expect['sum_v']})",
                 abs(sum_v - expect["sum_v"]) < 1e-6),
            ]
            at.vacuum(retention_sec=0.0)
            live = set(at.log.snapshot()[1])
            on_disk = {f for f in os.listdir(table)
                       if f.endswith(".parquet")}
            leftovers = [f for d in (table, at.log.log_dir)
                         for f in os.listdir(d) if f.endswith(".tmp")]
            checks += [
                ("zero orphans after sweep", on_disk == live),
                (f"zero staging leftovers ({leftovers})",
                 not leftovers),
            ]
            leg_fail = 0
            for what, ok in checks:
                if not ok:
                    print(f"[chaos] FAIL [{name}]: {what}",
                          file=sys.stderr, flush=True)
                    leg_fail += 1
            print(f"[chaos] {'PASS' if not leg_fail else 'FAIL'} "
                  f"[{name}] {time.monotonic() - t:.1f}s",
                  flush=True)
            failures += leg_fail

        # --- stale-epoch fencing: the zombie writer is refused ---
        t = time.monotonic()
        name = "ingest: stale-epoch writer fenced"
        events_dir = os.path.join(tmp, "events")
        ev.install(ev.EventLogWriter(events_dir))
        try:
            table = AcidTable.create(session, os.path.join(tmp, "fence"),
                                     demo_schema())

            def bf(b):
                return session.create_dataframe(
                    demo_batch_dict(b, 20), demo_schema())

            a = DeltaIngestor(table, "app")
            a.ingest(bf, 2)
            b = DeltaIngestor(table, "app")   # fences a
            fenced = False
            try:
                a.ingest(bf, 3)
            except StaleWriterEpoch:
                fenced = True
            recs = ev.read_all_events(events_dir)
            fev = [r for r in recs if r["event"] == "StaleWriterFenced"]
            stats = b.ingest(bf, 3)
            rows_now = table.to_df().collect()
            checks = [
                ("stale incumbent raises StaleWriterEpoch", fenced),
                ("refusal emits StaleWriterFenced", bool(fev)),
                ("event names both epochs",
                 bool(fev) and fev[0].get("writerEpoch") == a.epoch
                 and fev[0].get("currentEpoch") == b.epoch),
                (f"replacement resumes exactly-once ({stats})",
                 stats == {"committed": 1, "skipped": 2}),
                ("no rows lost or duplicated", len(rows_now) == 60
                 and len({r["id"] for r in rows_now}) == 60),
            ]
        finally:
            ev.install(None)
        leg_fail = 0
        for what, ok in checks:
            if not ok:
                print(f"[chaos] FAIL [{name}]: {what}",
                      file=sys.stderr, flush=True)
                leg_fail += 1
        print(f"[chaos] {'PASS' if not leg_fail else 'FAIL'} [{name}] "
              f"{time.monotonic() - t:.1f}s", flush=True)
        failures += leg_fail
    return failures


def _mesh_child() -> int:
    """Child body of the SPMD-mesh leg (separate process: the
    8-virtual-device XLA flag must be set before jax initializes, and
    the parent's jax is live by the time legs run).

    1. differential: the same join+agg+sort plan through the
       stage-per-program mesh executor and through single-stream
       execution must produce identical rows;
    2. seeded fault at the stage-execution boundary
       (``mesh.stage.run:reset@1``) — ``run_on_mesh_or_fallback``
       must degrade CLEANLY to serialized execution and still return
       the oracle rows, never a partial or wrong answer;
    3. with the plan disarmed the very next run must come back on the
       mesh path (the fallback is per-query, not sticky).

    Returns failure count (process exit code)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    import numpy as np

    from spark_rapids_tpu import parallel as par
    from spark_rapids_tpu.columnar.vector import batch_to_pydict
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.plan import TpuSession, overrides
    from spark_rapids_tpu.plan.host_table import to_pydict
    from spark_rapids_tpu.plan.mesh_executor import (
        run_on_mesh, run_on_mesh_or_fallback)
    from spark_rapids_tpu.robustness import faults

    conf = SrtConf({"srt.shuffle.partitions": 8})
    sess = TpuSession(conf)
    mesh = par.data_mesh(8)
    rng = np.random.default_rng(31)
    n = 4000
    fact = sess.create_dataframe({
        "k": rng.integers(0, 40, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist()})
    dim = sess.create_dataframe({
        "k": list(range(40)),
        "w": [float(1 + i % 3) for i in range(40)]})
    df = fact.filter(col("v") < 8.0).join(dim, on="k") \
        .group_by("k").agg(Alias(Sum(col("v") * col("w")), "s"),
                           Alias(CountStar(), "c")).sort("k")

    def _rows_of_batches(batches):
        out = []
        for b in batches:
            d = batch_to_pydict(b)
            ks = list(d)
            for i in range(len(d[ks[0]]) if ks else 0):
                out.append(tuple(d[k][i] for k in ks))
        return out

    single = to_pydict(sess.execute(df.plan))
    ks = list(single)
    oracle = [tuple(single[k][i] for k in ks)
              for i in range(len(single[ks[0]]) if ks else 0)]

    def _canon(rows):
        return sorted(tuple(round(v, 6) if isinstance(v, float) else v
                            for v in r) for r in rows)

    failures = 0
    # 1. mesh-on vs mesh-off identity
    mesh_rows = _rows_of_batches(run_on_mesh(
        overrides.apply_overrides(df.plan, conf), mesh, conf))
    if _canon(mesh_rows) != _canon(oracle):
        print(f"[chaos] FAIL [mesh identity]: mesh={len(mesh_rows)} "
              f"rows != single={len(oracle)} rows (or values differ)",
              file=sys.stderr, flush=True)
        failures += 1
    else:
        print(f"[chaos] PASS [mesh identity] {len(mesh_rows)} rows "
              f"bit-identical mesh vs single-stream", flush=True)
    # 2. seeded fault inside stage execution -> clean degradation
    faults.arm_fault_plan("seed=7|mesh.stage.run:reset@1")
    try:
        batches, mode = run_on_mesh_or_fallback(
            overrides.apply_overrides(df.plan, conf), mesh, conf)
    finally:
        faults.disarm_fault_plan()
    rows = _rows_of_batches(batches)
    if mode != "serialized" or _canon(rows) != _canon(oracle):
        print(f"[chaos] FAIL [mesh fault degradation]: mode={mode} "
              f"rows={len(rows)} (want serialized + oracle rows)",
              file=sys.stderr, flush=True)
        failures += 1
    else:
        print("[chaos] PASS [mesh fault degradation] stage fault "
              "-> serialized fallback, rows intact", flush=True)
    # 3. fallback is per-query: next run returns to the mesh path
    batches, mode = run_on_mesh_or_fallback(
        overrides.apply_overrides(df.plan, conf), mesh, conf)
    rows = _rows_of_batches(batches)
    if mode != "mesh" or _canon(rows) != _canon(oracle):
        print(f"[chaos] FAIL [mesh recovery]: mode={mode} after "
              f"disarm (want mesh)", file=sys.stderr, flush=True)
        failures += 1
    else:
        print("[chaos] PASS [mesh recovery] disarmed run back on "
              "the mesh path", flush=True)
    return failures


def _mesh_check() -> int:
    """SPMD-mesh leg: run ``_mesh_child`` in a subprocess (the
    virtual-device-count XLA flag cannot be applied to this process's
    already-initialized jax) and fold its verdict in. Returns failure
    count."""
    import subprocess
    t0 = time.monotonic()
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-child"],
            capture_output=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("[chaos] FAIL [mesh leg]: child timed out (300s)",
              file=sys.stderr, flush=True)
        return 1
    sys.stdout.write(p.stdout.decode("utf-8", "replace"))
    sys.stdout.flush()
    if p.returncode != 0:
        print(f"[chaos] FAIL [mesh leg]: child rc={p.returncode}: "
              f"{p.stderr.decode('utf-8', 'replace')[-300:]}",
              file=sys.stderr, flush=True)
        return 1
    print(f"[chaos] PASS [mesh leg] {time.monotonic() - t0:.1f}s",
          flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="2 workers, 2 plans (tier-1 smoke)")
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--budget", type=float, default=None,
                    help="wall-clock budget in seconds (hard exit 2)")
    ap.add_argument("--mesh-child", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.mesh_child:
        return _mesh_child()
    n_workers = args.workers or (2 if args.quick else 3)
    budget = args.budget or (360.0 if args.quick else 660.0)

    # a hung barrier or lost abort would otherwise stall forever: the
    # watchdog turns "hang" into a loud, bounded failure
    def _expired():
        print(f"[chaos] FAIL: wall-clock budget of {budget:.0f}s "
              f"exhausted — treating as hang", file=sys.stderr,
              flush=True)
        os._exit(2)

    watchdog = threading.Timer(budget, _expired)
    watchdog.daemon = True
    watchdog.start()
    t0 = time.monotonic()

    import numpy as np

    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    from spark_rapids_tpu.plan import TpuSession

    plans = ([TRANSIENT_PLANS[0], CORRUPTION_PLANS[0], CRASH_PLAN]
             if args.quick
             else TRANSIENT_PLANS + CORRUPTION_PLANS + [CRASH_PLAN])

    with tempfile.TemporaryDirectory(prefix="srt_chaos_") as tmp:
        session = TpuSession(SrtConf({}))
        rng = np.random.default_rng(29)
        n = 8_000
        fact_dir = os.path.join(tmp, "fact")
        session.create_dataframe({
            "k": rng.integers(0, 40, n).tolist(),
            "v": rng.uniform(0, 10, n).tolist(),
        }).write.parquet(fact_dir)
        dim_dir = os.path.join(tmp, "dim")
        session.create_dataframe({
            "k": list(range(40)),
            "w": [float(1 + i % 3) for i in range(40)],
        }).write.parquet(dim_dir)

        def logical(sess):
            # the filter keeps a scan -> filter -> partial-agg chain in
            # the plan so the fusion legs actually execute the fused
            # pipeline (exec/fused.py) under fault injection; the
            # fact ⋈ dim join plus the FINAL merge above the shuffle
            # exercise the v2 fused-join and fused-final-merge programs
            # in the same sweep
            fact = sess.read.parquet(fact_dir).filter(col("v") < 8.0)
            dim = sess.read.parquet(dim_dir)
            return fact.join(dim, on="k") \
                .group_by("k").agg(Alias(Sum(col("v") * col("w")), "s"),
                                   Alias(CountStar(), "c")) \
                .sort("k")

        oracle = logical(TpuSession(SrtConf({}))).collect()
        print(f"[chaos] oracle: {len(oracle)} groups from {n} rows",
              flush=True)

        driver = ClusterDriver(num_workers=n_workers, barrier_timeout=60,
                               heartbeat_interval=0.5, heartbeat_timeout=6)
        procs = launch_local_workers(driver, n_workers)
        failures = 0
        events_dir = os.path.join(tmp, "events")
        event_offsets: dict = {}
        # pipelining x fusion matrix: every plan runs with background
        # prefetch producers AND operator fusion enabled (faults now
        # fire on producer threads / inside the fused program and must
        # still recover); the sweep adds a fusion-off leg so recovery
        # behavior can be asserted IDENTICAL with and without fusion,
        # and the full sweep keeps the synchronous (pipeline-off) leg.
        # The crash plan runs one leg only — it permanently costs a
        # worker, and a rerun would arm a crash for an already-evicted
        # worker id (an unwinnable plan, not a recovery bug). Legs:
        # (pipeline_label, pipeline, fusion_label, fusion)
        legs = ([("on", "true", "on", "true"),
                 ("on", "true", "off", "false")] if args.quick
                else [("on", "true", "on", "true"),
                      ("on", "true", "off", "false"),
                      ("off", "false", "on", "true")])

        def _reseed(spec, offset):
            # each leg must be a fresh experiment: workers keep their
            # fault counters when re-armed with an identically-worded
            # plan (arm_from_conf preserves counters across stage
            # retries within a job), so a second leg reusing the spec
            # verbatim would find its @1 clauses already consumed.
            # Re-seeding yields a distinct spec string -> fresh arm.
            head, rest = spec.split("|", 1)
            return f"seed={int(head[len('seed='):]) + offset}|{rest}"

        runs = []
        for name, spec in plans:
            plan_legs = legs[:1] if (name, spec) == CRASH_PLAN else legs
            for i, (pipe_label, pipe, fuse_label, fuse) \
                    in enumerate(plan_legs):
                leg_spec = spec if i == 0 else _reseed(spec, 1000 * i)
                runs.append((f"{name} | pipeline={pipe_label} "
                             f"fusion={fuse_label}",
                             name, fuse_label, leg_spec, pipe, fuse))
        # per-(plan, fusion-leg) recovery deltas, compared after the
        # sweep: a fault plan must recover the SAME way with fusion on
        # and off
        leg_recovery: dict = {}
        try:
            driver.wait_for_workers(timeout=120)
            prev_armed: set = set()
            for name, base_name, fuse_label, spec, pipelined, fused \
                    in runs:
                job_conf = {"srt.shuffle.partitions": 4,
                            "srt.cluster.barrierTimeoutSec": 60,
                            "srt.eventLog.enabled": "true",
                            "srt.eventLog.dir": events_dir,
                            "srt.exec.pipeline.enabled": pipelined,
                            "srt.exec.fusion.enabled": fused,
                            "srt.test.faultPlan": spec}
                t = time.monotonic()
                recov_before = len(driver.recovery_events)
                try:
                    rows = driver.run(logical(session).plan, job_conf)
                except Exception as e:
                    print(f"[chaos] FAIL [{name}]: job raised "
                          f"{type(e).__name__}: {e}", file=sys.stderr,
                          flush=True)
                    failures += 1
                    _new_fault_events(events_dir, event_offsets)
                    continue
                ok = _rows_match(rows, oracle)
                recov = [e["type"] for e in driver.recovery_events]
                leg_recovery[(base_name, fuse_label)] = \
                    recov[recov_before:]
                print(f"[chaos] {'PASS' if ok else 'FAIL'} [{name}] "
                      f"{time.monotonic() - t:.1f}s workers="
                      f"{driver.num_workers} recovery={recov}",
                      flush=True)
                if not ok:
                    failures += 1
                # every injected fault must show in the event log.
                # Async sites (the worker heartbeat loop) fire on their
                # own cadence, not the job's: a fast job can return
                # before a single beat hit the armed plan, so poll a
                # few beat intervals before declaring a clause unfired
                fired = _new_fault_events(events_dir, event_offsets)
                grace = time.monotonic() + 3.0
                while _unfired_deterministic(spec, fired) \
                        and time.monotonic() < grace:
                    time.sleep(0.3)
                    fired += _new_fault_events(events_dir,
                                               event_offsets)
                failures += _check_fault_events(name, spec, fired,
                                                prev_armed)
                from spark_rapids_tpu.robustness.faults import FaultPlan
                prev_armed = {(sp.site, sp.kind)
                              for sp in FaultPlan.parse(spec).specs}
        finally:
            driver.shutdown()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except Exception:
                    p.kill()
        # the crash plan must actually have exercised stage-level
        # recovery, else the sweep silently stopped proving anything
        if not any(e["type"] == "stage_retry"
                   for e in driver.recovery_events):
            print("[chaos] FAIL: crash plan produced no stage_retry "
                  "recovery event", file=sys.stderr, flush=True)
            failures += 1
        # fusion must not change HOW a fault recovers: every plan run
        # both ways must produce the same recovery-event sequence
        for base in {b for b, _ in leg_recovery}:
            on = leg_recovery.get((base, "on"))
            off = leg_recovery.get((base, "off"))
            if on is None or off is None:
                continue
            if on != off:
                print(f"[chaos] FAIL [{base}]: recovery diverged "
                      f"between fusion legs: on={on} off={off}",
                      file=sys.stderr, flush=True)
                failures += 1
    # deterministic local spill-corruption probe (no cluster involved)
    failures += _spill_corruption_check()
    # distributed-telemetry leg: 4-worker run, merged history report
    failures += _telemetry_check()
    # concurrent-serving leg: admission + budget slices + cancellation
    failures += _concurrency_check()
    # adaptive-execution leg: skew/demote/coalesce/speculation sweep
    failures += _adaptive_check()
    # push-shuffle leg: eager push / segments / locality under faults
    failures += _push_shuffle_check()
    failures += _membership_check()
    # SPMD-mesh leg: mesh-vs-single identity + seeded stage fault ->
    # clean serialized degradation (subprocess, 8 virtual devices)
    failures += _mesh_check()
    # exactly-once streaming-ingest leg: SIGKILL the ingester child at
    # seeded commit-protocol fault points, resume, assert exactly-once
    failures += _streaming_ingest_check()
    failures += _serving_check()
    watchdog.cancel()
    print(f"[chaos] done in {time.monotonic() - t0:.1f}s, "
          f"{failures} failure(s)", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
