"""Robust concurrent serving (robustness/admission.py): admission
control, per-query budget-slice isolation, and cancellation/deadline
propagation through the session, spill, shuffle, and prefetch layers.

Reference analogues: GpuSemaphore's 1000-permit concurrentGpuTasks
carve-up (GpuSemaphore.scala), Spark's job-group cancellation, and the
RAPIDS retry-OOM state machine's per-task isolation.
"""

import threading
import time

import pytest

from spark_rapids_tpu.conf import SrtConf, set_active_conf
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.memory.budget import (MemoryBudget, device_budget,
                                            reset_device_budget)
from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.robustness.admission import (AdmissionRejected,
                                                   DeadlineExceeded,
                                                   QueryCancelled,
                                                   QueryContext,
                                                   QuerySemaphore,
                                                   query_scope,
                                                   reset_query_semaphore,
                                                   set_current_query)
from spark_rapids_tpu.robustness.faults import disarm_fault_plan


@pytest.fixture(autouse=True)
def _clean():
    """No test leaves a fault plan, query binding, resized semaphore,
    or shrunken device budget behind in this process."""
    yield
    disarm_fault_plan()
    set_current_query(None)
    reset_query_semaphore()
    reset_device_budget(None)


# ------------------------------------------------------ admission semantics

def test_semaphore_fast_admit_fifo_and_reentrancy():
    sem = QuerySemaphore(2, max_queue_depth=4, backoff_base_s=0.01)
    sem.acquire()
    sem.acquire()  # re-entrant on the same thread: no self-deadlock
    assert sem.active() == 1
    sem.release()
    sem.release()
    assert sem.active() == 0
    assert sem.admitted == 1  # re-entry is not a new admission


def test_admission_rejected_when_queue_full():
    sem = QuerySemaphore(1, max_queue_depth=1, backoff_base_s=0.01)
    sem.acquire()  # occupy the single slot from this thread
    results = {}

    def queued():
        tok = QueryContext("queued")
        try:
            sem.acquire(tok)
            results["queued"] = "admitted"
            sem.release()
        except BaseException as e:  # noqa: BLE001 — recorded for assert
            results["queued"] = type(e).__name__

    def shed():
        # arrives once the queue slot is taken -> load-shed
        deadline = time.monotonic() + 2.0
        while sem.queue_depth() < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        try:
            sem.acquire(QueryContext("shed"))
            results["shed"] = "admitted"
            sem.release()
        except AdmissionRejected:
            results["shed"] = "rejected"

    t1 = threading.Thread(target=queued)
    t2 = threading.Thread(target=shed)
    t1.start()
    t2.start()
    t2.join(5)
    assert results.get("shed") == "rejected"
    assert sem.rejected == 1
    sem.release()  # frees the queued query
    t1.join(5)
    assert results.get("queued") == "admitted"
    assert sem.active() == 0 and sem.queue_depth() == 0


def test_cancel_and_deadline_while_queued():
    sem = QuerySemaphore(1, max_queue_depth=4, backoff_base_s=0.01)
    sem.acquire()
    results = {}

    def run(name, tok):
        try:
            sem.acquire(tok)
            results[name] = "admitted"
            sem.release()
        except BaseException as e:  # noqa: BLE001
            results[name] = type(e).__name__

    cancel_tok = QueryContext("c")
    dead_tok = QueryContext("d")
    dead_tok.set_timeout(0.15)
    t1 = threading.Thread(target=run, args=("cancel", cancel_tok))
    t2 = threading.Thread(target=run, args=("deadline", dead_tok))
    t1.start()
    t2.start()
    time.sleep(0.05)
    cancel_tok.cancel("user abort")
    t1.join(5)
    t2.join(5)
    assert results == {"cancel": "QueryCancelled",
                       "deadline": "DeadlineExceeded"}
    # abandoned tickets must not wedge the queue
    assert sem.queue_depth() == 0
    sem.release()


# --------------------------------------------------- session-level teardown

def _frame(session, n=50_000):
    return session.create_dataframe(
        {"a": list(range(n)), "b": [float(i % 97) for i in range(n)]})


def test_collect_timeout_deadline_and_engine_stays_healthy():
    s = TpuSession(SrtConf({}))
    df = _frame(s).filter(col("a") > 10).group_by("b") \
        .agg(Alias(Sum(col("a")), "s"), Alias(CountStar(), "c")).sort("b")
    oracle = df.collect()
    with pytest.raises(DeadlineExceeded):
        df.collect(timeout=1e-6)
    # clean teardown: no permit, slice, or query binding leaks, and the
    # very same plan reruns bit-identically
    from spark_rapids_tpu.robustness.admission import (current_query,
                                                       query_semaphore)
    assert current_query() is None
    assert query_semaphore(s.conf).active() == 0
    assert device_budget().active_owners() == set()
    assert df.collect() == oracle


def test_session_cancel_mid_query():
    s = TpuSession(SrtConf({}))
    df = _frame(s, n=200_000).group_by("b") \
        .agg(Alias(Sum(col("a")), "s")).sort("b")
    oracle = df.collect()

    def canceller():
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if s.cancel("test abort"):
                return
            time.sleep(0.0005)

    t = threading.Thread(target=canceller)
    t.start()
    try:
        df.collect()
        # the query can legitimately win the race; the contract is
        # "typed error OR complete", never a wedge or a corrupt engine
    except QueryCancelled:
        pass
    t.join(10)
    assert device_budget().active_owners() == set()
    assert df.collect() == oracle


def test_cancel_mid_fused_program():
    """Fused scan->filter->project->agg chains pull through the same
    TpuExec.execute loop, so the per-batch check covers them; a
    deadline armed at launch surfaces DeadlineExceeded, and the fused
    plan reruns identically afterwards."""
    s = TpuSession(SrtConf({"srt.exec.fusion.enabled": "true"}))
    df = _frame(s).filter(col("b") < 90.0) \
        .group_by("b").agg(Alias(Sum(col("a")), "s")).sort("b")
    oracle = df.collect()
    with pytest.raises(DeadlineExceeded):
        df.collect(timeout=1e-6)
    assert df.collect() == oracle


# ------------------------------------------------ spill / budget isolation

def test_cancel_mid_spill_and_live_victim_filtering():
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.memory.spill import (SpillableBatch,
                                               reset_spill_catalog)
    reset_device_budget(1 << 30)
    cat = reset_spill_catalog()
    try:
        with query_scope(QueryContext("qa")):
            a = SpillableBatch(batch_from_pydict(
                {"v": list(range(4096))}))
        with query_scope(QueryContext("qb")):
            b = SpillableBatch(batch_from_pydict(
                {"v": list(range(4096))}))
        # victim scoping: with qa live, qb's spill request must not
        # evict qa's batch — only its own
        freed = cat.synchronous_spill(1, requester="qb",
                                      active_owners={"qa", "qb"})
        assert freed > 0
        assert b.tier != "device" and a.tier == "device"
        # a cancelled requester aborts the spill sweep mid-walk
        tok = QueryContext("qc")
        tok.cancel("mid-spill abort")
        with query_scope(tok):
            with pytest.raises(QueryCancelled):
                cat.synchronous_spill(1 << 20)
        a.close()
        b.close()
    finally:
        reset_device_budget(None)
        reset_spill_catalog()


def test_budget_slices_share_borrow_and_release():
    b = MemoryBudget(limit_bytes=1000)
    # single registered query: the idle pool is borrowable -> full limit
    b.register_query("solo", slots=4)
    b.reserve(900, owner="solo")
    b.release(900, owner="solo")
    b.unregister_query("solo")
    # all slots live: each query is capped at its share
    b.register_query("a", slots=2)
    b.register_query("b", slots=2)
    b.reserve(400, owner="a")
    from spark_rapids_tpu.memory.budget import RetryOOM
    with pytest.raises(RetryOOM) as ei:
        b.reserve(200, owner="a")  # 600 > share 500, no idle pool
    assert "slice" in str(ei.value)
    b.reserve(400, owner="b")  # b's own share is untouched by a
    b.release(400, owner="a")
    b.release(400, owner="b")
    b.unregister_query("a")
    b.unregister_query("b")
    assert b.active_owners() == set()
    assert b.used == 0


def test_concurrent_queries_bit_identical_vs_serial():
    """Four queries racing through a 2-permit semaphore over a shared
    shrunken device budget must each produce the serial answer —
    admission queueing, slice caps, and cross-query spills may change
    WHEN things run, never WHAT they compute."""
    from spark_rapids_tpu.memory.spill import reset_spill_catalog
    conf = SrtConf({"srt.sql.concurrentQueryTasks": "2",
                    "srt.sql.admission.maxQueueDepth": "8",
                    "srt.sql.admission.backoffBaseSec": "0.01"})
    oracle_s = TpuSession(SrtConf({}))
    shapes = [
        lambda s: _frame(s).filter(col("a") > 100).group_by("b")
        .agg(Alias(Sum(col("a")), "s")).sort("b"),
        lambda s: _frame(s).group_by("b")
        .agg(Alias(CountStar(), "c")).sort("b"),
    ]
    oracles = [sh(oracle_s).collect() for sh in shapes]
    reset_query_semaphore(conf)
    reset_device_budget(16 << 20)  # small enough to exercise slices
    reset_spill_catalog()
    try:
        results = [None] * 4
        errors = []

        def run(i):
            set_active_conf(conf)
            try:
                sess = TpuSession(conf)
                for attempt in range(20):
                    try:
                        results[i] = shapes[i % 2](sess).collect()
                        return
                    except AdmissionRejected:
                        time.sleep(0.02 * (attempt + 1))
                errors.append((i, "admission never succeeded"))
            except BaseException as e:  # noqa: BLE001
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors
        for i, got in enumerate(results):
            assert got == oracles[i % 2], f"query {i} diverged"
        assert device_budget().active_owners() == set()
    finally:
        reset_device_budget(None)
        reset_spill_catalog()


# --------------------------------------------------- shuffle / prefetch

def test_cancel_aborts_shuffle_write_and_fetch():
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.conf import SHUFFLE_MODE
    from spark_rapids_tpu.parallel.shuffle_manager import ShuffleManager
    mgr = ShuffleManager(SrtConf({SHUFFLE_MODE.key: "MULTITHREADED"}))
    mgr.register_shuffle(1, 2)
    parts = [batch_from_pydict({"v": [p * 10 + i for i in range(4)]})
             for p in range(2)]
    mgr.write_map_output(1, 0, parts)  # untagged thread: writes fine
    tok = QueryContext("qx")
    tok.cancel("abort in flight")
    with query_scope(tok):
        with pytest.raises(QueryCancelled):
            mgr.write_map_output(1, 1, parts)
        with pytest.raises(QueryCancelled):
            list(mgr.read_partition(1, 0))
    # the manager survives a cancelled caller: a clean query still reads
    rows = []
    from spark_rapids_tpu.columnar.vector import batch_to_pydict
    for b in mgr.read_partition(1, 0):
        rows.extend(batch_to_pydict(b)["v"])
    assert rows == [0, 1, 2, 3]
    mgr.unregister_shuffle(1)


def test_prefetch_close_leak_counter_and_event():
    from spark_rapids_tpu.exec.pipeline import (PrefetchIterator,
                                                prefetch_thread_leaks)
    release = threading.Event()

    def stuck_source():
        yield 1
        release.wait(30)  # ignores stop: models a wedged producer
        yield 2

    before = prefetch_thread_leaks()
    it = PrefetchIterator(stuck_source, depth=1, name="test-stuck")
    assert next(iter(it)) == 1
    it.close(join_timeout=0.05)
    assert prefetch_thread_leaks() == before + 1
    release.set()  # let the real thread exit; no lasting leak


def test_prefetch_producer_observes_cancel_token():
    from spark_rapids_tpu.exec.pipeline import PrefetchIterator
    tok = QueryContext("qp")
    produced = []

    def source():
        for i in range(10_000):
            produced.append(i)
            yield i

    it = PrefetchIterator(source, depth=1, query=tok)
    itr = iter(it)
    assert next(itr) == 0
    tok.cancel("stop producing")
    with pytest.raises(QueryCancelled):
        for _ in range(10_000):
            next(itr)
    it.close()
    # the producer drained instead of racing to the end
    assert len(produced) < 10_000


# ------------------------------------------------------- cluster teardown

# slow: ~10 s, real worker subprocesses
@pytest.mark.slow
def test_cluster_deadline_and_cancel_propagation(tmp_path):
    """Typed interrupts across the process boundary: a worker-side
    deadline (shipped via the job conf) and a driver-side cancel
    broadcast must both surface as the typed error WITHOUT triggering
    stage/job retry, and the fleet must stay in protocol sync — the
    next clean job is oracle-identical."""
    import numpy as np

    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)

    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(5)
    n = 6_000
    fact_dir = str(tmp_path / "fact")
    session.create_dataframe({
        "k": rng.integers(0, 20, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist(),
    }).write.parquet(fact_dir)
    df = session.read.parquet(fact_dir).group_by("k") \
        .agg(Alias(Sum(col("v")), "s"), Alias(CountStar(), "c")).sort("k")
    oracle = df.collect()
    base_conf = {"srt.shuffle.partitions": 2}

    driver = ClusterDriver(num_workers=2, heartbeat_interval=0.5,
                           heartbeat_timeout=15)
    procs = launch_local_workers(driver, 2)
    try:
        driver.wait_for_workers(timeout=90)
        # worker-side deadline: armed from srt.sql.queryTimeout in the
        # shipped job conf; the first per-batch check trips it
        with pytest.raises(DeadlineExceeded):
            driver.run(df.plan, dict(base_conf,
                                     **{"srt.sql.queryTimeout": "0.0001"}))
        # a typed interrupt is NOT a worker loss: no retry attempted
        assert driver.recovery_events == []
        rows = driver.run(df.plan, base_conf)
        assert rows == oracle  # fleet healthy + in sync after teardown

        # driver-side cancel: the reply wait polls the driver thread's
        # query token and broadcasts cancel to every worker. The delay
        # fault holds each worker in its scan long enough for the
        # broadcast to land deterministically.
        result = {}

        def run_cancelled():
            tok = QueryContext("qc-driver")
            tok.cancel("user abort")
            with query_scope(tok):
                try:
                    driver.run(df.plan, dict(
                        base_conf,
                        **{"srt.test.faultPlan":
                           "seed=1|scan.file:delay@1+1.0"}))
                    result["r"] = "completed"
                except QueryCancelled:
                    result["r"] = "cancelled"
                except BaseException as e:  # noqa: BLE001
                    result["r"] = repr(e)

        t = threading.Thread(target=run_cancelled)
        t.start()
        t.join(120)
        assert result.get("r") == "cancelled"
        assert driver.recovery_events == []
        rows = driver.run(df.plan, base_conf)
        assert rows == oracle
    finally:
        driver.shutdown()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


# ---------------------------------------------------------- conf plumbing

def test_shuffle_heartbeat_timeout_conf_hoist():
    import warnings

    from spark_rapids_tpu import conf as conf_mod
    from spark_rapids_tpu.parallel.shuffle_manager import \
        ShuffleHeartbeatManager
    # unified with srt.cluster.heartbeatTimeoutSec (30.0 default)
    assert ShuffleHeartbeatManager().timeout_s == 30.0
    # the old key is a deprecated alias: it forwards to the new key
    # and warns once per process
    conf_mod._ALIAS_WARNED.discard("srt.shuffle.heartbeat.timeoutSec")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        set_active_conf(SrtConf({"srt.shuffle.heartbeat.timeoutSec":
                                 "7.5"}))
    assert any(issubclass(w.category, DeprecationWarning)
               and "srt.cluster.heartbeatTimeoutSec" in str(w.message)
               for w in caught), [str(w.message) for w in caught]
    try:
        assert ShuffleHeartbeatManager().timeout_s == 7.5
        # the new key wins when both are set
        set_active_conf(SrtConf(
            {"srt.shuffle.heartbeat.timeoutSec": "7.5",
             "srt.cluster.heartbeatTimeoutSec": "11.0"}))
        assert ShuffleHeartbeatManager().timeout_s == 11.0
        # an explicit argument (the cluster driver's pass-through) wins
        assert ShuffleHeartbeatManager(timeout_s=3.0).timeout_s == 3.0
    finally:
        set_active_conf(SrtConf({}))
