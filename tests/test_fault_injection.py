"""Deterministic fault injection (robustness/faults.py): spec grammar,
seeded replay, and the hardened paths it exercises — bounded fetch
retry, endpoint failover, stage-level re-execution after a worker
crash, and forced OOM inside a retry-protected aggregate.

Reference analogues: RmmSparkRetrySuiteBase forced-OOM tests
(RmmSpark.forceRetryOOM), RapidsShuffleClient retry/failover handling,
and Spark's FetchFailed → map-stage resubmission contract.
"""

import os
import time

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.vector import batch_from_pydict
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.parallel.serializer import serialize_batch
from spark_rapids_tpu.parallel.shuffle_manager import ShuffleManager
from spark_rapids_tpu.parallel.transport import (ShuffleBlockServer,
                                                 stream_with_failover)
from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.robustness import faults
from spark_rapids_tpu.robustness.faults import (FaultPlan, FaultSpec,
                                                arm_fault_plan,
                                                disarm_fault_plan,
                                                fault_point)


@pytest.fixture(autouse=True)
def _disarm():
    """No test leaves a plan armed in this process."""
    yield
    disarm_fault_plan()


# ------------------------------------------------------------ spec grammar

def test_spec_parse_unparse_roundtrip():
    for s in ["transport.connect:refuse@1",
              "transport.serve_block:reset@2*3~m=1;",
              "cluster.barrier:crash@1~attempt=0;workers=1;pos=0;",
              "memory.reserve:retry_oom@1~HashAggregateExec",
              "transport.block:delay@1+0.25",
              "cluster.heartbeat:drop@2*5~executor=exec-1;",
              "shuffle.block.store:corrupt@1~map=0;",
              "shuffle.block.wire:corrupt%0.5*2",
              "spill.materialize:truncate@3"]:
        spec = FaultSpec.parse(s)
        assert spec.unparse() == s
        assert FaultSpec.parse(spec.unparse()).unparse() == s


def test_spec_rejects_unknown_kind():
    with pytest.raises(ValueError):
        FaultSpec.parse("transport.connect:explode@1")
    with pytest.raises(ValueError):
        FaultSpec.parse("no-colon-here")


def test_plan_spec_string_roundtrip():
    spec = ("seed=7|transport.connect:refuse@1"
            "|cluster.barrier:crash@1~attempt=0;workers=1;pos=1;")
    plan = FaultPlan.parse(spec)
    assert plan.seed == 7
    assert FaultPlan.parse(plan.spec_string()).spec_string() \
        == plan.spec_string()


def test_seeded_probabilistic_replay_is_deterministic():
    """Same seed + same hit sequence → identical firing pattern; a
    different seed diverges (the point of seeded replay)."""
    spec = "transport.block:delay%0.5*1000+0.0"

    def fire_pattern(seed):
        plan = FaultPlan([FaultSpec.parse(spec)], seed=seed)
        for i in range(200):
            plan.hit("transport.block", f"hit{i}")
        return [e.hit for e in plan.log]

    a, b = fire_pattern(42), fire_pattern(42)
    assert a and a == b
    assert fire_pattern(43) != a


def test_nth_and_count_semantics():
    # @nth fires exactly once, on the nth matching hit
    plan = FaultPlan([FaultSpec.parse("site.x:drop@2")])
    fired = []
    for i in range(6):
        try:
            plan.hit("site.x", "d")
        except faults.FaultDrop:
            fired.append(i)
    assert fired == [1]
    assert len(plan.fired("site.x")) == 1
    # *count caps a probabilistic clause's total fires
    plan = FaultPlan([FaultSpec.parse("site.x:drop%1.0*2")])
    fired = []
    for i in range(6):
        try:
            plan.hit("site.x", "d")
        except faults.FaultDrop:
            fired.append(i)
    assert fired == [0, 1]


def test_match_filters_on_detail():
    plan = FaultPlan([FaultSpec.parse("site.y:drop@1~k=3;")])
    for k in range(5):
        try:
            plan.hit("site.y", f"k={k};")
        except faults.FaultDrop:
            assert k == 3
    assert [e.detail for e in plan.fired()] == ["k=3;"]


def test_corrupt_on_non_data_site_raises_data_corruption():
    """A corrupt clause armed on a plain (non-data) fault_point site
    models an entry that reads back as garbage: the hit raises
    DataCorruption instead of mutating bytes it doesn't have."""
    from spark_rapids_tpu.robustness.integrity import DataCorruption
    plan = FaultPlan([FaultSpec.parse("scan.file:corrupt@1")])
    with pytest.raises(DataCorruption):
        plan.hit("scan.file", "some/file.parquet")
    assert len(plan.fired("scan.file")) == 1


def test_corrupt_replay_same_spec_same_bytes():
    """The determinism contract for corruption faults: re-running the
    same spec over the same payload sequence flips the same byte of the
    same hit (what makes a chaos failure reproducible)."""
    spec = "seed=19|shuffle.block.store:corrupt%0.4*3"
    payloads = [bytes([i] * 64) for i in range(20)]

    def replay():
        plan = FaultPlan.parse(spec)
        outs = [plan.mutate("shuffle.block.store", p, f"map={i};")
                for i, p in enumerate(payloads)]
        return outs, [(e.hit, e.detail) for e in plan.log]

    a, la = replay()
    b, lb = replay()
    assert a == b and la == lb
    assert la                                # it did fire
    assert any(x != p for x, p in zip(a, payloads))


def test_unarmed_fault_point_is_cheap():
    """Unarmed sites must cost one global load + compare — guard the
    zero-overhead contract with a (very generous) wall-clock bound."""
    disarm_fault_plan()
    assert not faults.armed()
    t0 = time.perf_counter()
    for _ in range(200_000):
        fault_point("transport.block", "x")
    assert time.perf_counter() - t0 < 1.0


# ------------------------------------------------- transport retry paths

def _mgr_with_blocks(shuffle_id=7, reduce_id=0, n_blocks=4, rows=50):
    mgr = ShuffleManager(SrtConf({}))
    for m in range(n_blocks):
        b = batch_from_pydict(
            {"i": list(range(m * rows, (m + 1) * rows))},
            schema=[("i", dt.INT64)])
        mgr.host_store.put((shuffle_id, m, reduce_id), serialize_batch(b))
    return mgr


def test_connect_refused_then_backoff_then_success():
    """One injected connection refusal: the bounded-retry fetch backs
    off and completes on the second attempt, losing no blocks."""
    mgr = _mgr_with_blocks()
    srv = ShuffleBlockServer(mgr)
    plan = arm_fault_plan("transport.connect:refuse@1")
    try:
        got = sorted(m for m, _ in stream_with_failover(
            srv.endpoint, 7, 0, max_retries=2, backoff_base_s=0.01))
        assert got == [0, 1, 2, 3]
        events = plan.fired("transport.connect")
        assert len(events) == 1 and events[0].kind == "refuse"
    finally:
        srv.close()


def test_midframe_reset_fails_over_to_alternate_endpoint():
    """Server A dies mid-frame while sending block m=1; with no retry
    budget the client fails over (heartbeat-registry resolver role) to
    server B and the cross-attempt seen-set keeps block m=0 unique."""
    mgr_a = _mgr_with_blocks()
    mgr_b = _mgr_with_blocks()
    srv_a = ShuffleBlockServer(mgr_a)
    srv_b = ShuffleBlockServer(mgr_b)
    # fires on EVERY serve of block m=1 at either server's handler, but
    # count*1 caps it to the first — which is server A's
    plan = arm_fault_plan("transport.serve_block:reset@1~m=1;")
    try:
        rows = []
        seen_maps = []
        for m, data in stream_with_failover(
                srv_a.endpoint, 7, 0,
                endpoint_resolver=lambda ep: srv_b.endpoint,
                max_retries=0, backoff_base_s=0.01):
            seen_maps.append(m)
            from spark_rapids_tpu.parallel.serializer import \
                deserialize_batch
            b = deserialize_batch(data)
            vals, _mask = b.column("i").to_numpy(b.num_rows)
            rows.extend(vals.tolist())
        assert sorted(seen_maps) == [0, 1, 2, 3]
        assert sorted(rows) == list(range(200))  # complete, no dupes
        assert len(plan.fired("transport.serve_block")) == 1
    finally:
        srv_a.close()
        srv_b.close()


# ------------------------------------------- forced OOM inside aggregate

def test_forced_retry_oom_inside_aggregate_recovers():
    """RetryOOM injected at the first device reservation made under the
    aggregate's operator scope (its merge holds partials as spillables
    via withRetryNoSplit): the retry framework spills and re-runs, and
    the query result is oracle-identical."""
    conf = {"srt.shuffle.mode": "MULTITHREADED",
            "srt.shuffle.partitions": 2}
    data = {"k": [i % 7 for i in range(600)],
            "v": [float(i) for i in range(600)]}

    def run():
        s = TpuSession(SrtConf(conf))
        df = s.create_dataframe(data)
        return {r["k"]: r for r in df.group_by("k").agg(
            Alias(Sum(col("v")), "s"), Alias(CountStar(), "c")).collect()}

    oracle = run()
    plan = arm_fault_plan("memory.reserve:retry_oom@1~HashAggregateExec")
    try:
        got = run()
    finally:
        disarm_fault_plan()
    events = plan.fired("memory.reserve")
    assert len(events) == 1 and events[0].kind == "retry_oom"
    assert "HashAggregateExec" in events[0].detail
    assert set(got) == set(oracle)
    for k, r in got.items():
        assert r["c"] == oracle[k]["c"]
        assert r["s"] == pytest.approx(oracle[k]["s"], rel=1e-9)


def test_forced_split_oom_inside_aggregate_surfaces():
    """Aggregates run under withRetryNoSplit — a forced
    SplitAndRetryOOM is NOT their contract, so it must surface as the
    typed error (loud failure), never as silently wrong rows."""
    from spark_rapids_tpu.memory.budget import SplitAndRetryOOM
    plan = arm_fault_plan(
        "memory.reserve:split_oom@1~HashAggregateExec")
    s = TpuSession(SrtConf({"srt.shuffle.mode": "MULTITHREADED",
                            "srt.shuffle.partitions": 2}))
    df = s.create_dataframe({"k": [i % 5 for i in range(400)],
                             "v": [float(i) for i in range(400)]})
    with pytest.raises(SplitAndRetryOOM):
        df.group_by("k").agg(Alias(Sum(col("v")), "s")).collect()
    assert len(plan.fired("memory.reserve")) == 1


# ------------------------------------- stage-level rerun after a crash

@pytest.fixture(scope="module")
def crash_dataset(tmp_path_factory):
    root = tmp_path_factory.mktemp("fault_cluster")
    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(11)
    n = 9_000
    fact = session.create_dataframe({
        "k": rng.integers(0, 40, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist(),
    })
    fact_dir = str(root / "fact")
    fact.write.parquet(fact_dir)
    return {"fact": fact_dir, "n": n}


# slow: ~13 s, crashes a real worker subprocess
@pytest.mark.slow
def test_worker_crash_at_stage_boundary_stage_level_rerun(crash_dataset):
    """Flagship acceptance path: logical worker 1 crashes at the final
    (range-exchange) barrier of a two-stage job, AFTER the hash
    exchange's map outputs completed. The driver must detect the loss
    by heartbeat, re-plan at STAGE granularity — reusing the completed
    hash-exchange outputs, re-executing only the dead worker's shards —
    and produce oracle-identical sorted rows."""
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    # plan positions are pre-order: pos 0 = range exchange (sort),
    # pos 1 = hash exchange (group-by). Runtime barrier order is pos 1
    # first, so a crash at pos 0 leaves pos 1 complete and reusable.
    spec = "seed=3|cluster.barrier:crash@1~attempt=0;workers=1;pos=0;"
    job_conf = {"srt.shuffle.partitions": 4,
                "srt.cluster.barrierTimeoutSec": 60,
                "srt.test.faultPlan": spec}
    driver = ClusterDriver(num_workers=3, barrier_timeout=60,
                           heartbeat_interval=0.5, heartbeat_timeout=6)
    procs = launch_local_workers(driver, 3)
    try:
        driver.wait_for_workers(timeout=90)
        session = TpuSession(SrtConf({}))
        plan = session.read.parquet(crash_dataset["fact"]) \
            .group_by("k").agg(Alias(Sum(col("v")), "s"),
                               Alias(CountStar(), "c")) \
            .sort("k").plan
        rows = driver.run(plan, job_conf)
        # oracle: single-process, fault-free
        expect = TpuSession(SrtConf({})).read \
            .parquet(crash_dataset["fact"]) \
            .group_by("k").agg(Alias(Sum(col("v")), "s"),
                               Alias(CountStar(), "c")) \
            .sort("k").collect()
        assert [r["k"] for r in rows] == [r["k"] for r in expect]
        for got, want in zip(rows, expect):
            assert got["c"] == want["c"]
            assert got["s"] == pytest.approx(want["s"], rel=1e-9)
        # the recovery must have been stage-level, reusing the hash
        # exchange (plan position 1) — not a whole-job retry
        stage = [e for e in driver.recovery_events
                 if e["type"] == "stage_retry"]
        assert stage, driver.recovery_events
        assert stage[0]["reused_positions"] == [1], driver.recovery_events
        assert driver.num_workers == 2
    finally:
        driver.shutdown()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


# ------------------------------------------------------- chaos smoke

# slow: ~140 s of multi-process chaos legs (tools/chaos_check.py --quick)
@pytest.mark.slow
def test_chaos_check_quick():
    """tools/chaos_check.py --quick: a seeded fault-plan sweep over a
    real 2-worker cluster must stay oracle-identical and exit 0 within
    its own wall-clock budget."""
    import subprocess
    import sys as _sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [_sys.executable, os.path.join(root, "tools", "chaos_check.py"),
         "--quick"],
        cwd=root, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    assert "0 failure(s)" in proc.stdout
