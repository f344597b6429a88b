"""Multi-host runtime driver (parallel/cluster.py): real worker
subprocesses on localhost executing staged plans with a cross-process
TCP shuffle — the reference's single-host multi-executor test topology
(SURVEY §4)."""

import os

import numpy as np
import pytest

# slow: every test here drives real worker subprocesses (~80 s in all)
pytestmark = pytest.mark.slow

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                               launch_local_workers)
from spark_rapids_tpu.plan import TpuSession


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """Partitioned parquet inputs written once for the module."""
    root = tmp_path_factory.mktemp("cluster_data")
    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(7)
    n = 20_000
    fact = session.create_dataframe({
        "k": rng.integers(0, 50, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist(),
    })
    fact_dir = str(root / "fact")
    fact.write.parquet(fact_dir, num_files=6) \
        if hasattr(fact.write, "num_files") else fact.write.parquet(fact_dir)
    dim = session.create_dataframe({
        "k": list(range(50)),
        "name": [f"n{i}" for i in range(50)],
    })
    dim_dir = str(root / "dim")
    dim.write.parquet(dim_dir)
    return {"fact": fact_dir, "dim": dim_dir, "n": n}


@pytest.fixture(scope="module")
def cluster():
    driver = ClusterDriver(num_workers=2)
    procs = launch_local_workers(driver, 2)
    try:
        driver.wait_for_workers(timeout=90)
        yield driver
    finally:
        driver.shutdown()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


def _logical(session, dataset, q):
    fact = session.read.parquet(dataset["fact"])
    dim = session.read.parquet(dataset["dim"])
    return q(fact, dim).plan


def test_grouped_aggregate_across_workers(cluster, dataset):
    session = TpuSession(SrtConf({}))
    plan = _logical(session, dataset,
                    lambda f, d: f.group_by("k").agg(
                        Alias(Sum(col("v")), "s"),
                        Alias(CountStar(), "c")))
    rows = cluster.run(plan, {"srt.shuffle.partitions": 4})
    # oracle: single-process run
    expect = {r["k"]: r for r in TpuSession(SrtConf({})).read
              .parquet(dataset["fact"]).group_by("k")
              .agg(Alias(Sum(col("v")), "s"),
                   Alias(CountStar(), "c")).collect()}
    assert len(rows) == len(expect)
    for r in rows:
        e = expect[r["k"]]
        assert r["c"] == e["c"]
        assert r["s"] == pytest.approx(e["s"], rel=1e-9)


def test_broadcast_join_replicated_build(cluster, dataset):
    session = TpuSession(SrtConf({}))
    plan = _logical(
        session, dataset,
        lambda f, d: f.join(d, "k").group_by("name").agg(
            Alias(CountStar(), "c")))
    rows = cluster.run(plan, {"srt.shuffle.partitions": 4,
                              "srt.sql.broadcastRowThreshold": 1000})
    oracle = {r["name"]: r["c"] for r in TpuSession(SrtConf({})).read
              .parquet(dataset["fact"]).join(
                  TpuSession(SrtConf({})).read.parquet(dataset["dim"]),
                  "k")
              .group_by("name").agg(Alias(CountStar(), "c")).collect()}
    got = {r["name"]: r["c"] for r in rows}
    assert got == oracle


def test_shuffled_join_across_workers(cluster, dataset):
    """SHUFFLED hash join (broadcast disabled by a tiny threshold) with
    AQE left at its default of enabled: the adaptive broadcast downgrade
    and partition-coalescing paths must stay OFF under a cluster context
    — a worker deciding from its local-only row counts would drop other
    workers' build rows."""
    session = TpuSession(SrtConf({}))
    plan = _logical(
        session, dataset,
        lambda f, d: f.join(d, "k").group_by("name").agg(
            Alias(Sum(col("v")), "s"),
            Alias(CountStar(), "c")))
    job_conf = {"srt.shuffle.partitions": 4,
                "srt.sql.broadcastRowThreshold": 1}
    rows = cluster.run(plan, job_conf)
    oracle_session = TpuSession(SrtConf(job_conf))
    oracle = {r["name"]: r for r in oracle_session.read
              .parquet(dataset["fact"]).join(
                  oracle_session.read.parquet(dataset["dim"]), "k")
              .group_by("name").agg(Alias(Sum(col("v")), "s"),
                                    Alias(CountStar(), "c")).collect()}
    got = {r["name"]: r for r in rows}
    assert set(got) == set(oracle)
    for name, r in got.items():
        assert r["c"] == oracle[name]["c"]
        assert r["s"] == pytest.approx(oracle[name]["s"], rel=1e-9)


def test_global_sort_order_preserved(cluster, dataset):
    session = TpuSession(SrtConf({}))
    fact = session.read.parquet(dataset["fact"])
    plan = fact.group_by("k").agg(Alias(Sum(col("v")), "s")) \
        .sort("k").plan
    rows = cluster.run(plan, {"srt.shuffle.partitions": 4})
    ks = [r["k"] for r in rows]
    assert ks == sorted(ks)
    assert len(ks) == 50


def test_worker_loss_recovery(dataset):
    """Losing a worker between jobs re-runs on the survivors
    (failure-detection/recovery role, SURVEY §5): results stay correct
    because sharding re-derives from the surviving worker set."""
    driver = ClusterDriver(num_workers=3, barrier_timeout=20)
    procs = launch_local_workers(driver, 3)
    job_conf = {"srt.shuffle.partitions": 4,
                "srt.cluster.barrierTimeoutSec": 20}
    try:
        driver.wait_for_workers(timeout=90)
        session = TpuSession(SrtConf({}))
        plan = _logical(session, dataset,
                        lambda f, d: f.group_by("k").agg(
                            Alias(CountStar(), "c")))
        first = driver.run(plan, job_conf)
        assert len(first) == 50
        # kill one worker; the next job must still produce full results
        procs[1].kill()
        procs[1].wait(timeout=10)
        rows = driver.run(plan, job_conf)
        assert driver.num_workers == 2
        got = {r["k"]: r["c"] for r in rows}
        want = {r["k"]: r["c"] for r in first}
        assert got == want
    finally:
        driver.shutdown()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


@pytest.fixture(scope="module")
def skew_dataset(tmp_path_factory):
    """Fact table with one hot key (90% of rows) for skew-join AQE."""
    root = tmp_path_factory.mktemp("cluster_skew")
    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(13)
    n = 16_000
    keys = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 50, n))
    fact = session.create_dataframe({
        "k": keys.tolist(),
        "v": rng.uniform(0, 10, n).tolist(),
    })
    fact_dir = str(root / "fact")
    fact.write.parquet(fact_dir)
    dim = session.create_dataframe({
        "k": list(range(50)),
        "name": [f"n{i}" for i in range(50)],
    })
    dim_dir = str(root / "dim")
    dim.write.parquet(dim_dir)
    return {"fact": fact_dir, "dim": dim_dir, "n": n}


def test_cluster_skewed_join_adaptive(cluster, skew_dataset):
    """AQE stays ON under the cluster: global gathered stats drive a
    skew split of the hot reduce partition, and results still match the
    single-process oracle (VERDICT r3 #7)."""
    session = TpuSession(SrtConf({}))
    conf = {"srt.shuffle.partitions": 4,
            "srt.sql.broadcastRowThreshold": 1,
            "srt.sql.adaptive.skewJoin.partitionRows": 1000,
            "srt.sql.adaptive.coalescePartitions.minPartitionRows": 1}
    plan = _logical(session, skew_dataset,
                    lambda f, d: f.join(d, ([col("k")], [col("k")]),
                                        how="inner"))
    rows = cluster.run(plan, conf)
    # the skewed partition must actually have been split somewhere
    skewed = sum(v.get("skewedJoinPartitions", 0)
                 for wm in cluster.last_metrics for v in wm.values())
    assert skewed >= 1, cluster.last_metrics
    # oracle: single process, adaptive off
    oracle_sess = TpuSession(SrtConf(
        {"srt.sql.adaptive.enabled": False,
         "srt.sql.broadcastRowThreshold": 1}))
    f = oracle_sess.read.parquet(skew_dataset["fact"])
    d = oracle_sess.read.parquet(skew_dataset["dim"])
    expect = f.join(d, ([col("k")], [col("k")]), how="inner").collect()
    assert len(rows) == len(expect)
    got_v = sorted(round(r["v"], 6) for r in rows)
    exp_v = sorted(round(r["v"], 6) for r in expect)
    assert got_v == exp_v


def test_cluster_adaptive_coalesce_aggregate(cluster, dataset):
    """Adaptive coalescing under the cluster: global stats, identical
    groups on every worker, correct grouped results."""
    session = TpuSession(SrtConf({}))
    conf = {"srt.shuffle.partitions": 8,
            "srt.sql.adaptive.coalescePartitions.minPartitionRows":
                1 << 16}
    plan = _logical(session, dataset,
                    lambda f, d: f.group_by("k").agg(
                        Alias(Sum(col("v")), "s"),
                        Alias(CountStar(), "c")))
    rows = cluster.run(plan, conf)
    expect = {r["k"]: r for r in TpuSession(SrtConf({})).read
              .parquet(dataset["fact"]).group_by("k")
              .agg(Alias(Sum(col("v")), "s"),
                   Alias(CountStar(), "c")).collect()}
    assert len(rows) == len(expect)
    for r in rows:
        e = expect[r["k"]]
        assert r["c"] == e["c"]
        assert abs(r["s"] - e["s"]) < 1e-6
    coalesced = sum(v.get("adaptiveCoalescedPartitions", 0)
                    for wm in cluster.last_metrics
                    for v in wm.values())
    assert coalesced >= 1, cluster.last_metrics
