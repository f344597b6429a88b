"""Asynchronous pipelined execution tests (spark_rapids_tpu/exec/pipeline.py):

- PrefetchIterator: producer order preserved at depth>1, byte-budget
  backpressure caps peak in-flight bytes (with the oversized-item
  progress guarantee), original-exception propagation to the consuming
  thread (``DataCorruption`` / ``FetchFailed`` keep their types for the
  retry machinery), clean shutdown with no leaked threads;
- fault-harness integration: an armed ``scan.file:corrupt`` plan fires
  on the prefetch producer thread and still surfaces at ``collect()``;
- planner pass: PrefetchExec inserted above eligible scans, withheld
  for input_file_name()/spark_partition_id() plans, exchanges tagged;
- pipeline-on vs pipeline-off bit-identical results on an NDS sample
  query;
- satellites: the shared shuffle fetch pool is reused across reduces
  and fails fast on a dead peer; CoalesceBatchesExec passes an
  already-full batch through untouched and meters coalesceWaitTime.
"""

import os
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar.vector import ColumnarBatch, batch_from_pydict
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec import pipeline
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.pipeline import PrefetchExec, PrefetchIterator
from spark_rapids_tpu.plan import overrides
from spark_rapids_tpu.plan.session import TpuSession
from spark_rapids_tpu.robustness.faults import (arm_fault_plan,
                                                disarm_fault_plan)
from spark_rapids_tpu.robustness.integrity import DataCorruption


@pytest.fixture(autouse=True)
def _disarmed():
    disarm_fault_plan()
    yield
    disarm_fault_plan()


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("srt-prefetch")]


# ---------------------------------------------------------------------------
# PrefetchIterator unit behavior
# ---------------------------------------------------------------------------

def test_ordering_preserved_under_depth():
    for depth in (1, 2, 4, 16):
        pf = PrefetchIterator(lambda: iter(range(200)), depth=depth)
        try:
            assert list(pf) == list(range(200))
        finally:
            pf.close()


def test_byte_budget_caps_peak_in_flight_bytes():
    item = b"x" * 1000

    def produce():
        for _ in range(50):
            yield item

    pf = PrefetchIterator(produce, depth=64, max_bytes=3000,
                          nbytes=len)
    got = 0
    for chunk in pf:
        got += 1
        time.sleep(0.001)  # slow consumer: the producer runs ahead
    assert got == 50
    # the queue never held more than the byte budget
    assert pf._bytes_peak <= 3000
    pf.close()


def test_oversized_item_admitted_alone():
    """A single item larger than the whole budget must still flow
    (progress guarantee) — admitted only into an empty queue."""
    big = b"y" * 10_000
    pf = PrefetchIterator(lambda: iter([big, big, big]), depth=8,
                          max_bytes=100, nbytes=len)
    try:
        assert [len(x) for x in pf] == [10_000] * 3
        assert pf._depth_peak == 1  # never two oversized items queued
    finally:
        pf.close()


def test_producer_exception_propagates_original_object():
    err = DataCorruption("seeded corruption on producer thread")

    def produce():
        yield 1
        yield 2
        raise err

    pf = PrefetchIterator(produce, depth=2)
    got = []
    with pytest.raises(DataCorruption) as ei:
        for x in pf:
            got.append(x)
    # items produced before the failure drain first, THEN the original
    # exception object (type intact for retry isinstance checks)
    assert got == [1, 2]
    assert ei.value is err
    pf.close()


def test_fetch_failed_keeps_type_across_threads():
    from spark_rapids_tpu.parallel.transport import FetchFailed

    def produce():
        yield 0
        raise FetchFailed("10.0.0.1:99", 7, 3, OSError("boom"))

    pf = PrefetchIterator(produce)
    with pytest.raises(FetchFailed) as ei:
        list(pf)
    assert ei.value.endpoint == "10.0.0.1:99"
    assert ei.value.shuffle_id == 7 and ei.value.reduce_id == 3
    assert isinstance(ei.value, ConnectionError)  # retry classification
    pf.close()


def test_close_stops_producer_and_discards_with_callback():
    discarded = []
    done = threading.Event()

    def produce():
        try:
            for i in range(10_000):
                yield i
        finally:
            done.set()

    pf = PrefetchIterator(produce, depth=4,
                          on_discard=discarded.append)
    assert next(pf) == 0
    pf.close()
    assert done.wait(5.0), "producer generator was not torn down"
    assert discarded, "queued items were not discarded through on_discard"
    assert not [t for t in _prefetch_threads() if t.is_alive()]


def test_clean_shutdown_leaks_no_threads():
    before = {t for t in threading.enumerate()}
    for _ in range(5):
        pf = PrefetchIterator(lambda: iter(range(100)), depth=3)
        assert len(list(pf)) == 100
        pf.close()
    # also an abandoned (never-drained) iterator
    pf = PrefetchIterator(lambda: iter(range(100)), depth=3)
    next(pf)
    pf.close()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and [
            t for t in _prefetch_threads() if t.is_alive()]:
        time.sleep(0.01)
    leaked = [t for t in set(threading.enumerate()) - before
              if t.name.startswith("srt-prefetch") and t.is_alive()]
    assert not leaked, f"leaked prefetch threads: {leaked}"


def test_wait_metric_counts_only_blocking():
    from spark_rapids_tpu.exec.base import Metric
    wait = Metric("prefetchWaitTime", unit="ns")

    def slow():
        for i in range(3):
            time.sleep(0.02)
            yield i

    pf = PrefetchIterator(slow, depth=2, wait_metric=wait)
    assert list(pf) == [0, 1, 2]
    pf.close()
    assert wait.value > 0  # consumer had to block on the slow producer


# ---------------------------------------------------------------------------
# planner pass
# ---------------------------------------------------------------------------

def _write_table(session, tmp_path, n=2000):
    rng = np.random.default_rng(11)
    path = os.path.join(str(tmp_path), "t")
    session.create_dataframe({
        "k": rng.integers(0, 25, n).tolist(),
        "v": rng.uniform(0, 9, n).tolist(),
    }).write.parquet(path)
    return path


def _tree_types(root):
    out = [type(root).__name__]
    for c in getattr(root, "children", []):
        out.extend(_tree_types(c))
    return out


def test_planner_inserts_prefetch_above_scan(tmp_path):
    session = TpuSession(SrtConf({"srt.shuffle.partitions": 2}))
    path = _write_table(session, tmp_path)
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import Alias
    df = session.read.parquet(path).group_by("k") \
        .agg(Alias(Sum(col("v")), "s"))
    root = overrides.apply_overrides(df.plan, session.conf)
    assert "PrefetchExec" in _tree_types(root)
    # exchanges carry the planner's safety tag rather than a wrapper
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec

    def find(n, cls):
        hits = [n] if isinstance(n, cls) else []
        for c in getattr(n, "children", []):
            hits.extend(find(c, cls))
        return hits
    for ex in find(root, ShuffleExchangeExec):
        assert getattr(ex, "_pipeline_ok", False)


def test_planner_withholds_pipeline_for_context_exprs(tmp_path):
    session = TpuSession(SrtConf({"srt.shuffle.partitions": 2}))
    path = _write_table(session, tmp_path)
    from spark_rapids_tpu.expr.misc import (input_file_name,
                                            spark_partition_id)
    df = session.read.parquet(path).with_column("f", input_file_name())
    root = overrides.apply_overrides(df.plan, session.conf)
    assert "PrefetchExec" not in _tree_types(root)
    df2 = session.read.parquet(path).with_column("p", spark_partition_id())
    root2 = overrides.apply_overrides(df2.plan, session.conf)
    assert "PrefetchExec" not in _tree_types(root2)


def test_planner_respects_conf_off(tmp_path):
    session = TpuSession(SrtConf({"srt.exec.pipeline.enabled": "false"}))
    path = _write_table(session, tmp_path)
    df = session.read.parquet(path)
    root = overrides.apply_overrides(df.plan, session.conf)
    assert "PrefetchExec" not in _tree_types(root)


# ---------------------------------------------------------------------------
# end-to-end: faults on producer threads, parity, thread hygiene
# ---------------------------------------------------------------------------

def test_producer_thread_fault_surfaces_at_collect(tmp_path):
    """An armed corrupt-file fault fires on the PREFETCH PRODUCER
    thread (the scan runs there) and must surface as DataCorruption on
    the consuming thread at collect() — not hang, not vanish."""
    session = TpuSession(SrtConf({"srt.shuffle.partitions": 2}))
    path = _write_table(session, tmp_path)
    df = session.read.parquet(path).group_by("k").count()
    arm_fault_plan("seed=5|scan.file:corrupt@1")
    with pytest.raises(DataCorruption):
        df.collect()
    disarm_fault_plan()
    # and the engine recovers cleanly for the next (unfaulted) run
    assert len(TpuSession(SrtConf({"srt.shuffle.partitions": 2}))
               .read.parquet(path).group_by("k").count().collect()) == 25


def test_pipeline_on_off_bit_identical_nds(tmp_path):
    """NDS sample query: pipelined and synchronous execution must
    produce bit-identical results (same rows, same order)."""
    from spark_rapids_tpu.datagen import generate_table
    from spark_rapids_tpu.models.nds import NDS_QUERIES, nds_specs

    def run(pipelined):
        session = TpuSession(SrtConf({
            "srt.shuffle.partitions": 2,
            "srt.exec.pipeline.enabled": "true" if pipelined else "false",
        }))
        data_dir = os.path.join(str(tmp_path), "nds")
        needed = {"store_sales", "date_dim", "item"}
        for spec in nds_specs(3_000):
            if spec.name not in needed:
                continue
            out = os.path.join(data_dir, spec.name)
            if not os.path.exists(out):
                generate_table(session, spec, out, chunk_rows=1 << 16)
            session.create_or_replace_temp_view(
                spec.name, session.read.parquet(out))
        return session.sql(NDS_QUERIES["q3"]).collect()

    assert run(pipelined=True) == run(pipelined=False)


def test_no_thread_leak_after_query(tmp_path):
    session = TpuSession(SrtConf({"srt.shuffle.partitions": 2}))
    path = _write_table(session, tmp_path)
    df = session.read.parquet(path).group_by("k").count().sort("k")
    assert len(df.collect()) == 25
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and [
            t for t in _prefetch_threads() if t.is_alive()]:
        time.sleep(0.01)
    assert not [t for t in _prefetch_threads() if t.is_alive()]


def test_limit_abandons_pipeline_without_leak(tmp_path):
    """A consumer that stops early (limit) abandons live prefetchers;
    their producers must be shut down, not leaked."""
    session = TpuSession(SrtConf({"srt.shuffle.partitions": 2}))
    path = _write_table(session, tmp_path, n=5000)
    rows = session.read.parquet(path).limit(7).collect()
    assert len(rows) == 7
    import gc
    gc.collect()  # abandoned generators close via GC finalization
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and [
            t for t in _prefetch_threads() if t.is_alive()]:
        time.sleep(0.01)
    assert not [t for t in _prefetch_threads() if t.is_alive()]


# ---------------------------------------------------------------------------
# satellites: shared fetch pool, coalesce fast path
# ---------------------------------------------------------------------------

def test_fetch_pool_reused_across_reduces():
    """The process-wide fetch pool replaces per-endpoint thread churn:
    repeated multi-peer fetches must reuse the same srt-fetch workers,
    never spawn new ones."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.parallel.serializer import serialize_batch
    from spark_rapids_tpu.parallel.shuffle_manager import ShuffleManager
    from spark_rapids_tpu.parallel.transport import (ShuffleBlockServer,
                                                     fetch_all_partitions,
                                                     fetch_pool)

    def mgr_with_blocks():
        mgr = ShuffleManager(SrtConf({}))
        for m in range(3):
            for r in range(2):
                b = batch_from_pydict({"i": list(range(32))},
                                      schema=[("i", dt.INT64)])
                mgr.host_store.put((9, m, r), serialize_batch(b))
        return mgr

    servers = [ShuffleBlockServer(mgr_with_blocks()) for _ in range(2)]
    try:
        pool = fetch_pool()
        n_threads = len([t for t in threading.enumerate()
                         if t.name.startswith("srt-fetch")])
        assert n_threads == pool.size
        for _ in range(3):
            for r in range(2):
                got = list(fetch_all_partitions(
                    [s.endpoint for s in servers], 9, r,
                    max_concurrent=2))
                assert len(got) == 2 * 3  # 2 peers x 3 maps
        after = len([t for t in threading.enumerate()
                     if t.name.startswith("srt-fetch")])
        assert after == n_threads, "fetch pool spawned extra threads"
    finally:
        for s in servers:
            s.close()


def test_fetch_fails_fast_on_dead_peer():
    """A dead endpoint must abort the fetch on FIRST error — not after
    every live peer drains (the old deferred-error behavior)."""
    from spark_rapids_tpu.parallel.transport import fetch_all_partitions
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead = "127.0.0.1:%d" % s.getsockname()[1]
    s.close()
    t0 = time.monotonic()
    with pytest.raises(OSError):
        list(fetch_all_partitions([dead, dead, dead], 7, 0,
                                  max_concurrent=3))
    assert time.monotonic() - t0 < 30.0


def test_coalesce_fast_path_passes_full_batch_through():
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.exec.base import ExecContext, TpuExec
    from spark_rapids_tpu.exec.basic import CoalesceBatchesExec

    schema = [("a", dt.INT64)]
    big = batch_from_pydict({"a": list(range(512))}, schema=schema)
    small1 = batch_from_pydict({"a": list(range(10))}, schema=schema)
    small2 = batch_from_pydict({"a": list(range(10, 20))}, schema=schema)

    class Src(TpuExec):
        @property
        def output_schema(self):
            return schema

        def do_execute(self, ctx):
            yield small1
            yield small2
            yield big

    node = CoalesceBatchesExec(Src(), target_rows=256)
    ctx = ExecContext(SrtConf({}))
    out = list(node.do_execute(ctx))
    # smalls coalesce into one batch; the already-full batch is passed
    # through as the SAME object (no concat / spill round-trip)
    assert len(out) == 2
    assert out[1] is big
    assert "coalesceWaitTime" in ctx.metrics_for(node.exec_id)


# ---------------------------------------------------------------------------
# kept producer threads (_ProducerPool)
# ---------------------------------------------------------------------------

def _drain_on(affinity, name="pooled", conf=None, seen=None):
    """Run one iterator to its end; returns (producer thread ident, its
    name while producing)."""
    seen = {} if seen is None else seen

    def source():
        from spark_rapids_tpu.conf import active_conf
        t = threading.current_thread()
        seen.update(ident=t.ident, name=t.name, conf=active_conf())
        yield from range(3)
    pf = PrefetchIterator(source, name=name, affinity=affinity, conf=conf)
    try:
        assert list(pf) == [0, 1, 2]
    finally:
        pf.close()
    return seen["ident"], seen["name"]


def test_same_affinity_is_served_by_the_same_thread():
    """A scan's host buffers live in the heap glibc tied to the thread
    that made them: the producer of a table is the same thread from query
    to query, whatever plan node asks, and parks under another name."""
    first, name = _drain_on("scan:/tmp/t1", name="PrefetchExec#1")
    assert name == "srt-prefetch-PrefetchExec#1"
    for node in ("PrefetchExec#7", "PrefetchExec#9", "PrefetchExec#1"):
        again, name = _drain_on("scan:/tmp/t1", name=node)
        assert again == first and name == f"srt-prefetch-{node}"
    other, _ = _drain_on("scan:/tmp/t2")
    assert other != first
    assert not [t for t in _prefetch_threads() if t.is_alive()]
    parked = [t for t in threading.enumerate()
              if t.name == pipeline._PARKED and t.ident in (first, other)]
    assert len(parked) == 2


def test_two_live_iterators_of_one_affinity_get_two_threads():
    gate = threading.Event()
    idents = []

    def source():
        idents.append(threading.get_ident())
        gate.wait(10)
        yield 1
    a = PrefetchIterator(source, affinity="scan:/tmp/shared")
    b = PrefetchIterator(source, affinity="scan:/tmp/shared")
    try:
        deadline = time.monotonic() + 5.0
        while len(idents) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        assert list(a) == [1] and list(b) == [1]
    finally:
        gate.set()
        a.close()
        b.close()
    assert len(set(idents)) == 2


def test_parked_thread_forgets_the_last_jobs_conf():
    from spark_rapids_tpu.conf import SrtConf
    mine = SrtConf({"srt.exec.pipeline.depth": "7"})
    seen = {}
    ident, _ = _drain_on("scan:/tmp/conf", conf=mine, seen=seen)
    assert seen["conf"] is mine
    again, _ = _drain_on("scan:/tmp/conf", conf=None, seen=seen)
    assert again == ident and seen["conf"] is not mine


def test_wedged_producer_is_replaced_not_waited_for():
    release = threading.Event()

    def stuck():
        yield 1
        release.wait(30)

    it = PrefetchIterator(stuck, depth=1, affinity="scan:/tmp/wedged")
    assert next(it) == 1
    it.close(join_timeout=0.05)  # counted as a leak: still in its source
    try:
        ident, _ = _drain_on("scan:/tmp/wedged")  # a new thread serves it
        assert ident is not None
    finally:
        release.set()


def test_pool_keeps_a_bounded_number_of_parked_threads(monkeypatch):
    monkeypatch.setattr(pipeline, "_MAX_PARKED", 4)
    for i in range(12):
        _drain_on(f"scan:/tmp/many-{i}")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and \
            len(pipeline._PRODUCERS._parked) > 4:
        time.sleep(0.01)
    assert len(pipeline._PRODUCERS._parked) <= 4


# ---------------------------------------------------------------------------
# RunAhead: ordered results computed ahead on kept threads
# ---------------------------------------------------------------------------

def _parked_idents():
    return {t.ident for t in threading.enumerate()
            if t.name == pipeline._PARKED}


def _gen_task(cost, items, seen=None, delay=0.0, error=None):
    def make():
        if seen is not None:
            seen.append(threading.get_ident())
        for item in items:
            time.sleep(delay)
            yield item
        if error is not None:
            raise error
    return cost, make


def test_run_ahead_results_keep_submission_order():
    # later tasks finish first: the first sleeps longest
    tasks = [_gen_task(1, [(i, j) for j in range(3)],
                       delay=0.004 * (8 - i) / 8) for i in range(8)]
    ahead = pipeline.RunAhead(tasks, threads=4, max_bytes=100)
    try:
        got = list(ahead)
    finally:
        ahead.close()
    assert got == [(i, (i, j)) for i in range(8) for j in range(3)]
    assert ahead.pooled == 8 and 0 < ahead.ahead <= 8
    assert not [t for t in _prefetch_threads() if t.is_alive()]


def test_run_ahead_error_surfaces_in_order_with_its_partial_items():
    boom = DataCorruption("file 2 is garbage")
    tasks = [_gen_task(1, ["a0", "a1"], delay=0.01), _gen_task(1, ["b0"]),
             _gen_task(1, ["c0"], error=boom), _gen_task(1, ["d0"])]
    leaks = pipeline.prefetch_thread_leaks()
    ahead = pipeline.RunAhead(tasks, threads=4, max_bytes=100)
    got = []
    try:
        with pytest.raises(DataCorruption) as ei:
            for _, item in ahead:
                got.append(item)
    finally:
        ahead.close()
    assert ei.value is boom
    assert got == ["a0", "a1", "b0", "c0"]
    assert pipeline.prefetch_thread_leaks() == leaks


def test_run_ahead_budget_bounds_what_is_decoded_ahead():
    """Cost admitted and not yet taken never passes the budget, and a
    task over the budget alone runs on the consumer, item by item."""
    me = threading.get_ident()
    seen, produced = [], []

    def big():
        seen.append(threading.get_ident())
        for j in range(3):
            produced.append(j)
            yield f"big{j}"
    tasks = [_gen_task(40, [f"s{i}"], seen) for i in range(6)]
    tasks.insert(3, (1000, big))
    ahead = pipeline.RunAhead(tasks, threads=4, max_bytes=100)
    try:
        it = iter(ahead)
        got = [next(it) for _ in range(4)]
        # the over-budget task streams: one item made, one item taken
        assert got[-1] == (3, "big0") and produced == [0]
        assert seen.count(me) == 1 and len(seen) == 4
        got += list(it)
    finally:
        ahead.close()
    assert [item for _, item in got] == [
        "s0", "s1", "s2", "big0", "big1", "big2", "s3", "s4", "s5"]
    assert ahead._bytes_peak == 80 and ahead.pooled == 6
    assert ahead.ahead <= ahead.pooled


def test_run_ahead_close_mid_stream_parks_every_thread():
    gate = threading.Event()

    def slow():
        gate.wait(10)
        yield "late"
    tasks = [_gen_task(1, ["first"])] + [(1, slow) for _ in range(9)]
    before = pipeline.prefetch_thread_leaks()
    ahead = pipeline.RunAhead(tasks, threads=3, max_bytes=100)
    assert next(iter(ahead)) == (0, "first")
    threading.Timer(0.05, gate.set).start()
    ahead.close()  # drops the queue, waits for the three that run
    assert all(p.is_set() for p in ahead._parked)
    assert not ahead._queue and not ahead._done
    assert pipeline.prefetch_thread_leaks() == before
    assert not [t for t in _prefetch_threads() if t.is_alive()]


def test_live_streams_share_the_reader_threads_of_the_process():
    """Four streams of four threads each over the same bound: never
    more than four reader threads inside a task at once, and every
    stream still delivers everything in order."""
    lock = threading.Lock()
    inside, most = [0], [0]

    def make(tag):
        def run():
            with lock:
                inside[0] += 1
                most[0] = max(most[0], inside[0])
            time.sleep(0.003)
            with lock:
                inside[0] -= 1
            yield tag
        return run
    streams = [pipeline.RunAhead([(1, make((s, i))) for i in range(8)],
                                 threads=4, max_bytes=100, name=f"s{s}")
               for s in range(4)]
    got = [[] for _ in streams]
    pulls = [threading.Thread(target=lambda k=k: got[k].extend(streams[k]))
             for k in range(4)]
    try:
        for t in pulls:
            t.start()
        for t in pulls:
            t.join(30)
    finally:
        for ahead in streams:
            ahead.close()
    assert got == [[(i, (s, i)) for i in range(8)] for s in range(4)]
    assert most[0] <= 4
    assert max(a.threads_peak for a in streams) == most[0] > 1
    assert pipeline._READERS.live == 0 and pipeline._READERS.bytes == 0
    # one live stream alone is bounded as before: by its own threads
    alone = pipeline.RunAhead([_gen_task(1, [i], delay=0.002)
                               for i in range(6)], threads=2, max_bytes=100)
    try:
        assert [item for _, item in alone] == list(range(6))
    finally:
        alone.close()
    assert 1 <= alone.threads_peak <= 2


def test_live_streams_share_the_byte_budget_and_none_waits_on_another():
    """A stream that holds something admits only while all live streams
    together stay within the budget; one that holds nothing always
    admits its next task, so a stream whose consumer is not pulling
    cannot stall the others."""
    parked = pipeline.RunAhead([_gen_task(40, [i]) for i in range(4)],
                               threads=2, max_bytes=100, name="parked")
    try:
        # nobody pulls ``parked``: it fills its share of the budget
        deadline = time.monotonic() + 10
        while len(parked._done) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert pipeline._READERS.bytes == 80 and parked._next == 2
        other = pipeline.RunAhead([_gen_task(40, [i]) for i in range(4)],
                                  threads=2, max_bytes=100, name="other")
        try:
            # 80 + 40 > 100: one task at a time, and yet all of them
            assert other._next == 1
            assert [item for _, item in other] == [0, 1, 2, 3]
            assert other.pooled == 4
        finally:
            other.close()
        assert pipeline._READERS.bytes == 80
        assert [item for _, item in parked] == [0, 1, 2, 3]
    finally:
        parked.close()
    assert pipeline._READERS.bytes == 0 and pipeline._READERS.live == 0


@pytest.mark.parametrize("pulling", ["one_at_a_time", "all_at_once"])
def test_runs_held_together_never_wait_on_another_streams_bytes(pulling):
    """Streams whose tasks give their bytes back a run at a time (a scan
    batch's files, ``hold_until``), with more runs between them than the
    budget holds: a run whose first task is admitted is admitted to its
    end whatever the other streams hold, so a consumer that has taken
    part of a run never waits for a task the budget refuses."""
    def stream(s):
        return pipeline.RunAhead(
            [_gen_task(30, [(s, i)], delay=0.001) for i in range(6)],
            threads=2, max_bytes=100, name=f"runs{s}",
            hold_until=[1, 1, 3, 3, 5, 5])
    want = [[(i, (s, i)) for i in range(6)] for s in range(4)]
    streams = [stream(0)]
    try:
        # nobody pulls stream 0 yet: it runs ahead to the budget's edge
        deadline = time.monotonic() + 10
        while len(streams[0]._done) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert pipeline._READERS.bytes == 90 and streams[0]._next == 3
        streams += [stream(s) for s in (1, 2, 3)]
        # each of the others: its first run whole (30 + 30), over the
        # process's budget, and not the first task of its second
        assert [a._next for a in streams[1:]] == [2, 2, 2]
        assert pipeline._READERS.bytes == 90 + 3 * 60
        got = [[] for _ in streams]
        if pulling == "one_at_a_time":
            # the others finish while stream 0's consumer stands still
            order = [[1], [2], [3], [0]]
        else:
            order = [[0, 1, 2, 3]]
        for ks in order:
            pulls = [threading.Thread(
                target=lambda k=k: got[k].extend(streams[k]),
                daemon=True) for k in ks]
            for t in pulls:
                t.start()
            for t in pulls:
                t.join(30)
            assert not [t for t in pulls if t.is_alive()], "a stream stalled"
        assert got == want
    finally:
        for ahead in streams:
            ahead.close()
    assert pipeline._READERS.bytes == 0 and pipeline._READERS.live == 0


def test_run_ahead_same_table_same_threads_conf_and_fault_scope():
    from spark_rapids_tpu.conf import active_conf
    from spark_rapids_tpu.robustness import faults
    mine = SrtConf({"srt.exec.pipeline.depth": "7"})
    arm_fault_plan("seed=1|memory.reserve:retry_oom@999")

    def run(conf):
        seen = []

        def make():
            seen.append((threading.get_ident(), active_conf(),
                         faults.current_op()))
            time.sleep(0.01)  # every worker takes a task
            yield 1
        with faults.op_scope("FileSourceScanExec#4"):
            ahead = pipeline.RunAhead([(1, make)] * 3, threads=3,
                                      max_bytes=100, conf=conf,
                                      affinity="decode:/tmp/t1/part-0")
        try:
            assert [item for _, item in ahead] == [1, 1, 1]
        finally:
            ahead.close()
        return seen
    first = run(mine)
    assert {c for _, c, _ in first} == {mine}
    assert {op for _, _, op in first} == {"FileSourceScanExec#4"}
    idents = {i for i, _, _ in first}
    assert len(idents) == 3 and idents <= _parked_idents()
    again = run(None)
    assert {i for i, _, _ in again} == idents
    assert mine not in {c for _, c, _ in again}


def test_run_ahead_stress_more_workers_than_cores():
    import sys
    n = 400
    tasks = [_gen_task(3, [i]) for i in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ahead = pipeline.RunAhead(tasks, threads=4 * (os.cpu_count() or 2),
                                  max_bytes=50)
        try:
            got = list(ahead)
        finally:
            ahead.close()
    finally:
        sys.setswitchinterval(interval)
    assert got == [(i, i) for i in range(n)]
    assert ahead.pooled == n and ahead._bytes == 0
    assert ahead._bytes_peak <= 50
    assert all(p.is_set() for p in ahead._parked)


def test_run_ahead_holds_a_run_of_tasks_until_its_last_is_taken():
    """``hold_until``: the tasks that fill one buffer stay on the budget
    until the consumer has the last of them; the peak still fits."""
    tasks = [_gen_task(30, [i]) for i in range(8)]
    ahead = pipeline.RunAhead(tasks, threads=4, max_bytes=100,
                              hold_until=[2, 2, 2, 5, 5, 5, 6, 7])
    try:
        it = iter(ahead)
        assert [next(it), next(it)] == [(0, 0), (1, 1)]
        # two taken and held, the third admitted, no room for a fourth
        assert ahead._held == 60 and ahead._bytes == 90
        assert next(it) == (2, 2) and ahead._held == 0
        assert list(it) == [(i, i) for i in range(3, 8)]
    finally:
        ahead.close()
    assert ahead._bytes == 0 and ahead._bytes_peak <= 100
    assert ahead.pooled == 8


def test_placed_scan_keeps_the_budget_and_parks_its_threads(
        tmp_path, monkeypatch):
    """A scan that decodes into its batches' own buffers: what is on the
    budget (a batch's files until the batch is taken, padding included)
    never passes maxBytesInFlight, and closing it in the middle of a
    batch leaves every reader thread parked."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io import scan as scan_mod
    for i in range(6):
        pq.write_table(pa.table({
            "a": np.arange(500 * i, 500 * (i + 1), dtype=np.int64),
            "b": np.arange(500, dtype=np.float64)}),
            str(tmp_path / f"part-{i}.parquet"))
    made = []

    class Spy(pipeline.RunAhead):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            made.append(self)
    monkeypatch.setattr(scan_mod, "RunAhead", Spy)
    # a batch: three files of 500 rows, 18 bytes a row, padded to 2048
    batch = 2048 * 18
    conf = SrtConf({"srt.sql.format.parquet.nativeDecode.enabled": "true",
                    "srt.sql.reader.batchSizeRows": "1024",
                    "srt.exec.pipeline.maxBytesInFlight": str(batch + 9000)})
    node = scan_mod.FileSourceScanExec(
        scan_mod.FileScan(str(tmp_path), "parquet"))
    ctx = ExecContext(conf)
    rows = [int(b.num_rows) for b in node.execute(ctx)]
    assert rows == [1500, 1500]
    counters = ctx.metrics_for(node.exec_id)
    assert counters["scanInPlaceBatches"].value == 2
    assert batch <= made[0]._bytes_peak <= batch + 9000
    assert made[0]._bytes == 0
    before = pipeline.prefetch_thread_leaks()
    batches = node.execute(ExecContext(conf))
    assert int(next(batches).num_rows) == 1500
    batches.close()
    assert all(p.is_set() for p in made[1]._parked)
    assert not made[1]._queue and not made[1]._done
    assert pipeline.prefetch_thread_leaks() == before
    assert not [t for t in _prefetch_threads() if t.is_alive()]


# ---------------------------------------------------------------------------
# a join starts the producers beneath it when it starts (start_sources)
# ---------------------------------------------------------------------------

class _GatedSource(TpuExec):
    """Leaf exec over pre-built batches: records each execution and the
    thread it ran on, waits for ``gate`` before every batch, and raises
    ``error`` (if any) in place of its first batch. ``rows``: the live
    rows every batch claims (0: the schema and no row)."""

    def __init__(self, data, nbatches=1, gate=None, error=None, rows=None):
        super().__init__()
        n = len(next(iter(data.values())))
        per = -(-n // nbatches)
        self._batches = [batch_from_pydict({k: v[i:i + per]
                                            for k, v in data.items()})
                         for i in range(0, n, per)]
        if rows is not None:
            self._batches = [ColumnarBatch(b.columns, b.names,
                                           jnp.int32(rows))
                             for b in self._batches]
        self._gate, self._error = gate, error
        self.started = threading.Event()
        self.runs = []  # the thread of each execution

    @property
    def output_schema(self):
        return self._batches[0].schema()

    def do_execute(self, ctx):
        self.runs.append(threading.current_thread().name)
        self.started.set()
        for b in self._batches:
            if self._gate is not None:
                assert self._gate.wait(10), "gate never opened"
            if self._error is not None:
                raise self._error
            yield b


_PROBE = {"k": [5, None, 12, 40, -3, 12, 19, 10, None, 1 << 40, 20, 11],
          "v": list(range(12))}
_DIMENSION = {"dk": [14, 10, 20, 12, 17, 11],
              "name": ["n14", "n10", None, "n12", "n17", "n11"]}


class _Opaque(TpuExec):
    """Passes its child through, and does not say so (the base class's
    ``_streams_child``): what a sort, an aggregate or an exchange is to
    ``start_sources``."""

    def __init__(self, child):
        super().__init__(child)

    @property
    def output_schema(self):
        return self.children[0].output_schema

    def do_execute(self, ctx):
        yield from self.children[0].execute(ctx)


def _early_join(probe_src, build_src, over_build=lambda node: node):
    """``BroadcastHashJoin(Prefetch(probe), Broadcast(Prefetch(build)))``
    as the planner's pipelining pass leaves it."""
    from spark_rapids_tpu.exec import BroadcastHashJoinExec
    from spark_rapids_tpu.exec.exchange import BroadcastExchangeExec
    from spark_rapids_tpu.expr import col
    exchange = BroadcastExchangeExec(over_build(PrefetchExec(build_src)))
    exchange._pipeline_ok = True
    return BroadcastHashJoinExec(PrefetchExec(probe_src), exchange,
                                 [col("k")], [col("dk")])


def _early_starts(ctx, node):
    m = ctx.metrics_for(node.exec_id).get("prefetchEarlyStarts")
    return m.value if m is not None else 0


def _drain(node, ctx, out):
    try:
        out.append(sum(int(b.num_rows) for b in node.execute(ctx)))
    except BaseException as e:  # noqa: BLE001 — handed to the test
        out.append(e)


def _assert_all_parked(ctx):
    assert ctx.early_sources == {}
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and _prefetch_threads():
        time.sleep(0.01)
    assert not _prefetch_threads()
    with pipeline._LIVE_LOCK:
        assert all(it._closed for it in pipeline._LIVE)


def test_join_starts_its_probe_producer_before_the_build_is_drained():
    gate = threading.Event()
    probe, build = _GatedSource(_PROBE, nbatches=2), \
        _GatedSource(_DIMENSION, gate=gate)
    join = _early_join(probe, build)
    ctx, out = ExecContext(), []
    consumer = threading.Thread(target=_drain, args=(join, ctx, out))
    consumer.start()
    try:
        # the build side has not produced its first batch (the gate is
        # shut), and the probe side's producer is already running
        assert probe.started.wait(10) and build.started.wait(10)
        assert not gate.is_set() and consumer.is_alive()
        assert probe.runs[0].startswith("srt-prefetch")
    finally:
        gate.set()
        consumer.join(30)
    assert not consumer.is_alive() and out == [5]
    probe_prefetch, exchange = join.children
    assert _early_starts(ctx, probe_prefetch) == 1
    assert _early_starts(ctx, exchange) == 1
    # the scan under the build started on the broadcast producer's
    # thread, at that thread's first pull: not counted again
    assert _early_starts(ctx, exchange.children[0]) == 0
    assert len(probe.runs) == len(build.runs) == 1
    _assert_all_parked(ctx)


def test_only_a_streaming_build_side_runs_beside_the_probe():
    """An operator between the exchange and its scan that may not be
    run ahead of its consumer: the build side starts when the join
    drains it, as before; the probe side's scan still starts early."""
    probe, build = _GatedSource(_PROBE, nbatches=2), \
        _GatedSource(_DIMENSION)
    join = _early_join(probe, build, over_build=_Opaque)
    ctx = ExecContext()
    assert sum(int(b.num_rows) for b in join.execute(ctx)) == 5
    exchange = join.children[1]
    assert _early_starts(ctx, join.children[0]) == 1
    assert _early_starts(ctx, exchange) == 0
    assert _early_starts(ctx, exchange.children[0].children[0]) == 0
    _assert_all_parked(ctx)


def test_early_probe_producer_is_closed_over_an_empty_build():
    leaks = pipeline.prefetch_thread_leaks()
    # three probe batches under a queue of two: the producer stands in
    # backpressure when the join ends without a pull
    probe = _GatedSource(_PROBE, nbatches=3)
    join = _early_join(probe, _GatedSource(_DIMENSION, rows=0))
    ctx = ExecContext()
    assert list(join.execute(ctx)) == []
    probe_prefetch = join.children[0]
    assert _early_starts(ctx, probe_prefetch) == 1 and probe.runs
    assert "numOutputRows" not in ctx.metrics_for(probe_prefetch.exec_id)
    assert pipeline.prefetch_thread_leaks() == leaks
    _assert_all_parked(ctx)


@pytest.mark.parametrize("case", ["probe error", "build error", "cancel"])
def test_early_producers_unwind_with_a_typed_error(case):
    from spark_rapids_tpu.robustness.admission import (QueryCancelled,
                                                       QueryContext)
    err = DataCorruption(f"seeded: {case}")
    gate = threading.Event()
    probe = _GatedSource(_PROBE, nbatches=2,
                         error=err if case == "probe error" else None)
    build = _GatedSource(_DIMENSION, gate=gate,
                         error=err if case == "build error" else None)
    join = _early_join(probe, build)
    query = QueryContext("early-starts") if case == "cancel" else None
    ctx, out = ExecContext(query=query), []
    consumer = threading.Thread(target=_drain, args=(join, ctx, out))
    consumer.start()
    try:
        # both producers run while the consumer waits for the build
        assert probe.started.wait(10) and build.started.wait(10)
        if query is not None:
            query.cancel("test")
    finally:
        gate.set()
        consumer.join(30)
    assert not consumer.is_alive()
    if case == "cancel":
        assert isinstance(out[0], QueryCancelled)
    else:  # the producer's own exception object, at the consumer
        assert out[0] is err
    _assert_all_parked(ctx)


def test_a_rerun_plan_starts_fresh_producers():
    probe, build = _GatedSource(_PROBE, nbatches=2), \
        _GatedSource(_DIMENSION)
    join = _early_join(probe, build)
    for run in (1, 2):
        join.reset_for_rerun()
        ctx = ExecContext()
        assert sum(int(b.num_rows) for b in join.execute(ctx)) == 5
        assert _early_starts(ctx, join.children[0]) == 1
        assert _early_starts(ctx, join.children[1]) == 1
        assert len(probe.runs) == len(build.runs) == run
        _assert_all_parked(ctx)
    # nothing of a run is kept on the (cached) nodes
    nodes = [join, *join.children, join.children[1].children[0]]
    assert not [v for n in nodes for v in vars(n).values()
                if isinstance(v, PrefetchIterator)]
