"""Elastic cluster membership (parallel/cluster.py): graceful
decommission with block migration, kill-then-rejoin under epoch
fencing, buddy-replicated shuffle durability, and the recovery_time
span — plus a slow soak smoke for RSS/thread-count creep."""

import os
import pickle
import signal
import socket
import struct
import threading
import time

import numpy as np
import pytest

# slow: every test here drives real worker subprocesses (~70 s in all)
pytestmark = pytest.mark.slow

from spark_rapids_tpu.conf import SrtConf, set_active_conf
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                               launch_local_workers)
from spark_rapids_tpu.plan import TpuSession

_FRAME = struct.Struct(">I")


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    root = tmp_path_factory.mktemp("membership_data")
    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(11)
    n = 8_000
    fact = session.create_dataframe({
        "k": rng.integers(0, 40, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist(),
    })
    fact_dir = str(root / "fact")
    fact.write.parquet(fact_dir)
    dim = session.create_dataframe({
        "k": list(range(40)),
        "name": [f"n{i}" for i in range(40)],
    })
    dim_dir = str(root / "dim")
    dim.write.parquet(dim_dir)
    return {"fact": fact_dir, "dim": dim_dir}


def _plan(dataset):
    session = TpuSession(SrtConf({}))
    f = session.read.parquet(dataset["fact"])
    d = session.read.parquet(dataset["dim"])
    return f.join(d, "k").group_by("name").agg(
        Alias(Sum(col("v")), "s"), Alias(CountStar(), "c")).plan


def _oracle(dataset):
    session = TpuSession(SrtConf({}))
    f = session.read.parquet(dataset["fact"])
    d = session.read.parquet(dataset["dim"])
    rows = f.join(d, "k").group_by("name").agg(
        Alias(Sum(col("v")), "s"), Alias(CountStar(), "c")).collect()
    return _canon(rows)


def _canon(rows):
    return sorted((r["name"], r["c"], round(r["s"], 6)) for r in rows)


def _shutdown(driver, procs):
    driver.shutdown()
    for p in procs:
        try:
            p.wait(timeout=10)
        except Exception:
            p.kill()


def test_decommission_during_query_zero_retries(dataset):
    """A decommission issued WHILE a query runs: the worker finishes
    its job first (so the query completes with zero stage retries),
    then drains, migrates its blocks to a peer, and deregisters."""
    driver = ClusterDriver(num_workers=3, barrier_timeout=60)
    procs = launch_local_workers(driver, 3)
    conf = {"srt.shuffle.partitions": 4,
            "srt.sql.broadcastRowThreshold": 1}
    try:
        driver.wait_for_workers(timeout=120)
        oracle = _oracle(dataset)
        plan = _plan(dataset)
        result: list = []
        t = threading.Thread(
            target=lambda: result.append(driver.run(plan, conf)))
        t.start()
        # land the decommission frame MID-job: wait for the first
        # shuffle-barrier arrival (proof the job is executing), so the
        # frame queues behind the job dialogue and replays only after
        # the worker's result reply — never pre-empting the query
        deadline = time.monotonic() + 60
        while not driver._barriers and not driver._spec_barriers:
            assert time.monotonic() < deadline, "job never started"
            time.sleep(0.01)
        ok = driver.decommission(timeout=90.0)
        t.join(timeout=120)
        assert not t.is_alive()
        assert ok, "decommission did not complete"
        assert _canon(result[0]) == oracle
        kinds = [e["type"] for e in driver.recovery_events]
        assert "decommission" in kinds
        assert "stage_retry" not in kinds and "job_retry" not in kinds
        assert driver.num_workers == 2
        # the survivors serve the next query
        rows = driver.run(_plan(dataset), conf)
        assert _canon(rows) == oracle
        assert [e["type"] for e in driver.recovery_events].count(
            "stage_retry") == 0
    finally:
        _shutdown(driver, procs)


def test_replica_migration_roundtrip():
    """Unit-level durability contract: migrate_blocks + manifest
    publish makes the buddy's replica store serve the origin's exact
    framed blocks; without the manifest there is NO coverage (a
    partial replica set must never masquerade as complete)."""
    set_active_conf(SrtConf({"srt.shuffle.mode": "MULTITHREADED"}))
    try:
        from spark_rapids_tpu.columnar import dtypes as dt
        from spark_rapids_tpu.columnar.vector import (ColumnarBatch,
                                                      column_from_numpy)
        from spark_rapids_tpu.parallel.shuffle_manager import \
            ShuffleManager
        from spark_rapids_tpu.parallel.transport import (
            ShuffleBlockServer, _replica_stream)
        ma, mb = ShuffleManager(), ShuffleManager()
        sa, sb = ShuffleBlockServer(ma), ShuffleBlockServer(mb)
        try:
            ma.register_shuffle(5, 2)
            mb.register_shuffle(5, 2)
            vals = np.arange(64, dtype=np.int64)
            batch = ColumnarBatch(
                [column_from_numpy(vals, 64, dtype=dt.INT64)], ["v"], 64)
            ma.write_map_output(5, 0, [batch, batch], local_ok=False)
            ma.write_map_output(5, 1, [batch, batch], local_ok=False)
            # replica pushes without a manifest: no coverage yet
            ma.replicate_map_output(5, 0, sb.endpoint, who="t")
            ma.drain_pushes()
            assert mb.replicas.coverage(sa.endpoint, 5, 0) is None
            with pytest.raises(ConnectionError):
                list(_replica_stream(sb.endpoint, sa.endpoint, 5, 0,
                                     frozenset(), 10.0))
            # full migration + manifest: bit-identical replica serve
            migrated = ma.migrate_blocks(sb.endpoint,
                                         time.monotonic() + 30)
            ma.drain_pushes()
            for sid in migrated:
                assert ma.publish_replica_manifest(sid, sb.endpoint)
            assert migrated == [5]
            from spark_rapids_tpu.robustness import integrity
            for rid in (0, 1):
                want = [(b[1],
                         integrity.strip(ma.host_store.get(b)))
                        for b in ma.host_store.blocks_for_reduce(5, rid)]
                got = [(m, bytes(f)) for m, f in _replica_stream(
                    sb.endpoint, sa.endpoint, 5, rid, frozenset(),
                    10.0)]
                assert got == want
            # exclude list: already-held blocks never re-cross the wire
            assert list(_replica_stream(sb.endpoint, sa.endpoint, 5, 0,
                                        frozenset({0, 1}), 10.0)) == []
        finally:
            sa.close()
            sb.close()
    finally:
        set_active_conf(SrtConf({}))


def test_kill_rejoin_epoch_fencing(dataset):
    """Hard kill -> recovery on the survivor; a replacement registering
    over the dead endpoint rejoins the roster and reroutes block
    ownership; the dead incarnation's epoch is fenced (its frames are
    refused, so a zombie can never commit); the driver's recovery_time
    histogram is populated."""
    driver = ClusterDriver(num_workers=2, barrier_timeout=30,
                           heartbeat_interval=0.5, heartbeat_timeout=6)
    procs = launch_local_workers(driver, 2)
    conf = {"srt.shuffle.partitions": 4,
            "srt.cluster.barrierTimeoutSec": 30,
            "srt.sql.broadcastRowThreshold": 1}
    try:
        driver.wait_for_workers(timeout=120)
        oracle = _oracle(dataset)
        assert _canon(driver.run(_plan(dataset), conf)) == oracle
        roster = {eid: ep for _s, ep, eid in driver._workers}
        procs[1].kill()
        procs[1].wait(timeout=10)
        # recovery: the next query must still be correct
        assert _canon(driver.run(_plan(dataset), conf)) == oracle
        live = {eid for _s, _ep, eid in driver._workers}
        (dead_eid,) = set(roster) - live
        dead_ep = roster[dead_eid]
        dead_epoch = driver._epochs[dead_eid]
        assert dead_epoch in driver._fenced_epochs
        # zombie probe: a frame carrying the fenced epoch is refused
        # BEFORE it can touch the registry
        with socket.create_connection(driver.address, timeout=10) as s:
            payload = pickle.dumps({"type": "barrier", "shuffle_id": 999,
                                    "worker": 9, "pos": -1,
                                    "epoch": dead_epoch})
            s.sendall(_FRAME.pack(len(payload)) + payload)
            head = s.recv(4)
            (n,) = _FRAME.unpack(head)
            reply = pickle.loads(s.recv(n))
        assert reply["type"] == "fenced", reply
        # driver-side recovery span observed
        from spark_rapids_tpu.obs import registry as obs_registry
        hist = obs_registry.registry().histogram("recovery_time_ns")
        assert hist is not None and hist.snapshot()["count"] >= 1
        # rejoin: a replacement declares the dead endpoint as its prior
        # incarnation; ownership reroutes, roster returns to 2
        procs.extend(launch_local_workers(
            driver, 1, env={"SRT_REJOIN_ENDPOINT": dead_ep}))
        driver.wait_for_n_workers(2, timeout=120)
        new_ep = next(ep for _s, ep, eid in driver._workers
                      if eid not in roster)
        deadline = time.monotonic() + 30
        while driver._heartbeats.resolve(dead_ep) != new_ep:
            assert time.monotonic() < deadline, \
                "resolve() never rerouted to the replacement"
            time.sleep(0.2)
        # the rejoined pair serves queries again
        assert _canon(driver.run(_plan(dataset), conf)) == oracle
        assert driver.num_workers == 2
    finally:
        _shutdown(driver, procs)


def test_buddy_replication_survives_dead_serves(dataset):
    """k=2 replication: with every remote pull serve dying, each
    reader degrades to manifest-covered replica fetches from the
    origin's buddy (itself, in a 2-worker ring) — the query completes
    with ZERO stage retries and bit-identical rows."""
    import tempfile

    from spark_rapids_tpu.obs import events as ev
    driver = ClusterDriver(num_workers=2, barrier_timeout=60)
    procs = launch_local_workers(driver, 2)
    with tempfile.TemporaryDirectory() as events_dir:
        conf = {"srt.shuffle.partitions": 4,
                "srt.sql.broadcastRowThreshold": 1,
                "srt.shuffle.push.enabled": "false",
                "srt.shuffle.replication.factor": "2",
                "srt.shuffle.fetch.maxRetries": "1",
                "srt.shuffle.fetch.backoffBaseSec": "0.01",
                "srt.test.faultPlan":
                    "seed=5|transport.serve:reset%1.0*999",
                "srt.eventLog.enabled": "true",
                "srt.eventLog.dir": events_dir}
        try:
            driver.wait_for_workers(timeout=120)
            oracle = _oracle(dataset)
            rows = driver.run(_plan(dataset), conf)
            assert _canon(rows) == oracle
            kinds = [e["type"] for e in driver.recovery_events]
            assert "stage_retry" not in kinds and \
                "job_retry" not in kinds, driver.recovery_events
            events = ev.read_all_events(events_dir)
            recovered = [e for e in events
                         if e.get("event") == "RecoveryTimed"
                         and e.get("kind") == "buddy_fetch"]
            assert recovered, "no buddy-fetch recovery recorded"
            assert all(e["recovery_time_ns"] > 0 for e in recovered)
            assert any(e.get("event") == "ReplicaFetch" for e in events)
        finally:
            _shutdown(driver, procs)


@pytest.mark.slow
def test_soak_two_worker_membership(dataset):
    """~50-query soak on a 2-worker cluster with the resource sampler
    on: RSS and thread count must stay bounded (first evidence toward
    ROADMAP item 5's no-creep-over-hours claim)."""
    driver = ClusterDriver(num_workers=2, barrier_timeout=60)
    procs = launch_local_workers(driver, 2)
    conf = {"srt.shuffle.partitions": 4,
            "srt.sql.broadcastRowThreshold": 1,
            "srt.obs.resource.intervalMs": "200"}

    def rss_kb(pid: int) -> int:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    try:
        driver.wait_for_workers(timeout=120)
        oracle = _oracle(dataset)
        plan = _plan(dataset)
        # warm-up: compile caches and steady-state pools fill here
        for _ in range(5):
            assert _canon(driver.run(plan, conf)) == oracle
        base_rss = [rss_kb(p.pid) for p in procs]
        base_threads = threading.active_count()
        for _ in range(45):
            assert _canon(driver.run(plan, conf)) == oracle
        for p, b in zip(procs, base_rss):
            grown = rss_kb(p.pid) - b
            # generous bound: steady-state churn, not linear leak
            assert grown < 200_000, \
                f"worker {p.pid} RSS grew {grown} kB over 45 queries"
        assert threading.active_count() <= base_threads + 4
        kinds = [e["type"] for e in driver.recovery_events]
        assert "heartbeat_eviction" not in kinds
    finally:
        _shutdown(driver, procs)
