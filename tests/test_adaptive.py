"""Adaptive query execution: runtime shuffle statistics drive partition
coalescing and join-strategy switching.

Reference: Spark AQE hooks (GpuQueryStagePrepOverrides,
GpuCustomShuffleReaderExec, DynamicJoinSelection) — here the exchange
exposes MapOutputStatistics-style row counts, the FINAL aggregate and
shuffled join consume coalesced partition groups (one grouping applied
to BOTH join sides), and a small materialized build side downgrades a
shuffled join to a broadcast-style stream that skips the probe shuffle.
"""

import pytest

from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.expr.aggregates import Count, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.testing import (IntGen, assert_tpu_cpu_equal_df,
                                      gen_table)


def make_session(**extra):
    base = {"srt.shuffle.partitions": 8,
            "srt.sql.broadcastRowThreshold": 1,  # force shuffled joins
            "srt.sql.adaptive.coalescePartitions.minPartitionRows": "64"}
    base.update(extra)
    return TpuSession(SrtConf(base))


def make_df(s, gens, n, seed=0):
    data, schema = gen_table(gens, n, seed)
    return s.create_dataframe(data, schema)


def _run_with_metrics(df):
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.plan import overrides
    from spark_rapids_tpu.plan.host_table import batch_to_table, \
        concat_tables, empty_like
    physical = overrides.apply_overrides(df.plan, df.session.conf)
    ctx = ExecContext(df.session.conf)
    tables = [batch_to_table(b) for b in physical.execute(ctx)
              if int(b.num_rows) > 0]
    out = concat_tables(tables) if tables else empty_like(df.plan.schema)
    merged = {}
    for em in ctx.metrics.values():
        for name, metric in em.items():
            merged[name] = merged.get(name, 0) + metric.value
    return out, merged


def test_aggregate_partition_coalescing(monkeypatch):
    s = make_session()
    df = make_df(s, {"k": IntGen(lo=0, hi=40), "v": IntGen()}, 200, seed=3)
    q = df.group_by(col("k")).agg(Sum(col("v")).alias("sv"),
                                  Count(col("v")).alias("n"))
    assert_tpu_cpu_equal_df(q)
    _, metrics = _run_with_metrics(q)
    # 200 rows over 8 partitions of a 64-row budget -> groups merged
    assert metrics.get("adaptiveCoalescedPartitions", 0) >= 4


def test_join_coordinated_coalescing():
    s = make_session()
    left = make_df(s, {"k": IntGen(lo=0, hi=60), "v": IntGen()}, 200,
                   seed=5)
    right = make_df(s, {"k": IntGen(lo=0, hi=60), "w": IntGen()}, 150,
                    seed=7)
    # build side above the adaptive broadcast threshold -> stays a
    # partitioned join but with coalesced, ALIGNED groups
    q = left.join(right, ([col("k")], [col("k")]), how="inner")
    assert_tpu_cpu_equal_df(q)
    q2 = left.join(right, ([col("k")], [col("k")]), how="left")
    assert_tpu_cpu_equal_df(q2)


def test_adaptive_broadcast_switch():
    s = make_session(**{"srt.sql.adaptive.autoBroadcastJoinRows": "1000"})
    left = make_df(s, {"k": IntGen(lo=0, hi=30), "v": IntGen()}, 400,
                   seed=9)
    right = make_df(s, {"k": IntGen(lo=0, hi=30), "w": IntGen()}, 50,
                    seed=11)
    q = left.join(right, ([col("k")], [col("k")]), how="inner")
    out, metrics = _run_with_metrics(q)
    assert metrics.get("adaptiveBroadcastJoins", 0) == 1
    assert_tpu_cpu_equal_df(q)
    # and the probe side's shuffle never wrote anything
    assert metrics.get("shuffleWriteRows", 0) <= 50


def test_adaptive_off_matches(monkeypatch):
    s = make_session(**{"srt.sql.adaptive.enabled": "false"})
    left = make_df(s, {"k": IntGen(lo=0, hi=30), "v": IntGen()}, 200,
                   seed=13)
    right = make_df(s, {"k": IntGen(lo=0, hi=30), "w": IntGen()}, 60,
                    seed=15)
    assert_tpu_cpu_equal_df(
        left.join(right, ([col("k")], [col("k")]), how="inner"))
    df = make_df(s, {"k": IntGen(lo=0, hi=40), "v": IntGen()}, 200,
                 seed=17)
    assert_tpu_cpu_equal_df(df.group_by(col("k")).agg(
        Sum(col("v")).alias("sv")))


def test_coalesce_groups_shapes():
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    g = ShuffleExchangeExec.coalesce_groups([10, 10, 50, 5, 5, 100], 60)
    # greedy adjacent: [10,10,50]=70, then [5,5,100]=110
    assert g == [[0, 1, 2], [3, 4, 5]]
    assert ShuffleExchangeExec.coalesce_groups([100, 200], 50) == \
        [[0], [1]]
    # trailing small tail folds into the last group
    assert ShuffleExchangeExec.coalesce_groups([100, 5], 50) == [[0, 1]]
    assert ShuffleExchangeExec.coalesce_groups([1, 2], 50) == [[0, 1]]


def test_stacked_joins_pin_partitioning():
    # (A join B) join C reuses the inner join's hash partitioning with
    # no re-exchange: AQE must NOT change the inner join's partition
    # count (coalescing/broadcast switch stand down under the pin)
    s = make_session(**{"srt.sql.adaptive.autoBroadcastJoinRows": "1000"})
    a = make_df(s, {"k": IntGen(lo=0, hi=25), "v": IntGen()}, 200, seed=19)
    b = make_df(s, {"k": IntGen(lo=0, hi=25), "w": IntGen()}, 40, seed=21)
    c = make_df(s, {"k": IntGen(lo=0, hi=25), "x": IntGen()}, 60, seed=23)
    q = (a.join(b, ([col("k")], [col("k")]), how="inner")
          .join(c, ([col("k")], [col("k")]), how="inner"))
    assert_tpu_cpu_equal_df(q)
    q2 = (a.join(b, ([col("k")], [col("k")]), how="left")
           .join(c, ([col("k")], [col("k")]), how="left"))
    assert_tpu_cpu_equal_df(q2)


def test_agg_over_join_pin():
    s = make_session()
    a = make_df(s, {"k": IntGen(lo=0, hi=30), "v": IntGen()}, 300, seed=25)
    b = make_df(s, {"k": IntGen(lo=0, hi=30), "w": IntGen()}, 80, seed=27)
    q = (a.join(b, ([col("k")], [col("k")]), how="inner")
          .group_by(col("k")).agg(Sum(col("v")).alias("sv"),
                                  Count(col("w")).alias("n")))
    assert_tpu_cpu_equal_df(q)


def test_skewed_join_split_local():
    """A hot-key reduce partition splits into map slices; results match
    the non-adaptive plan exactly (GpuCustomShuffleReaderExec skewed
    partition specs)."""
    s = make_session(**{
        "srt.sql.adaptive.skewJoin.partitionRows": 500,
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": 1})
    import numpy as np
    rng = np.random.default_rng(3)
    keys = np.where(rng.random(8000) < 0.9, 7,
                    rng.integers(0, 50, 8000))
    fact = s.create_dataframe({"k": keys.tolist(),
                               "v": rng.uniform(0, 10, 8000).tolist()})
    dim = s.create_dataframe({"k": list(range(50)),
                              "w": [i * 2 for i in range(50)]})
    df = fact.join(dim, ([col("k")], [col("k")]), how="inner")
    out, metrics = _run_with_metrics(df)
    assert metrics.get("skewedJoinPartitions", 0) >= 1, metrics
    # oracle: numpy — every key is in dim, each joins exactly once
    assert out.num_rows == len(keys)
    got = sorted(zip(*(out.column("k").values.tolist(),
                       out.column("w").values.tolist())))
    import numpy as np
    exp = sorted(zip(keys.tolist(), (np.asarray(keys) * 2).tolist()))
    assert got == exp


def test_skewed_join_split_matches_cpu():
    """Differential: skew-split plan vs CPU oracle."""
    s = make_session(**{
        "srt.sql.adaptive.skewJoin.partitionRows": 300,
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": 1})
    import numpy as np
    rng = np.random.default_rng(5)
    keys = np.where(rng.random(4000) < 0.85, 3,
                    rng.integers(0, 20, 4000))
    fact = s.create_dataframe({"k": keys.tolist(),
                               "v": rng.uniform(0, 10, 4000).tolist()})
    dim = s.create_dataframe({"k": list(range(20)),
                              "w": [f"w{i}" for i in range(20)]})
    df = fact.join(dim, ([col("k")], [col("k")]), how="inner")
    assert_tpu_cpu_equal_df(df)


def test_skewed_left_join_split_matches_cpu():
    s = make_session(**{
        "srt.sql.adaptive.skewJoin.partitionRows": 300,
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": 1})
    import numpy as np
    rng = np.random.default_rng(9)
    keys = np.where(rng.random(4000) < 0.85, 3,
                    rng.integers(0, 30, 4000))
    fact = s.create_dataframe({"k": keys.tolist(),
                               "v": rng.uniform(0, 10, 4000).tolist()})
    dim = s.create_dataframe({"k": list(range(20)),
                              "w": [f"w{i}" for i in range(20)]})
    df = fact.join(dim, ([col("k")], [col("k")]), how="left")
    assert_tpu_cpu_equal_df(df)


def test_full_outer_join_shared_exchange_drains_twice():
    """Full outer lowers to left_outer UNION null-extended anti with
    BOTH joins sharing the child exchanges (overrides._build_join);
    the second drain must still find the shuffle registered (the
    consumer-refcounted release in exchange._release — an eager
    unregister after the first drain raised KeyError here)."""
    s = make_session()
    import numpy as np
    rng = np.random.default_rng(11)
    left = s.create_dataframe({
        "k": rng.integers(0, 40, 600).tolist(),
        "a": rng.uniform(0, 1, 600).tolist()})
    right = s.create_dataframe({
        "k": rng.integers(20, 60, 600).tolist(),
        "b": rng.uniform(0, 1, 600).tolist()})
    la = left.group_by("k").agg(Sum(col("a")).alias("sa"))
    rb = right.group_by("k").agg(Sum(col("b")).alias("sb"))
    df = la.join(rb, ([col("k")], [col("k")]), how="full")
    assert_tpu_cpu_equal_df(df)


def test_full_outer_join_with_aqe_coalesce_global_agg():
    """The exact q97 shape: grouped CTEs -> FULL OUTER JOIN -> global
    aggregate, with AQE coalescing active above the shared exchanges."""
    s = make_session()
    import numpy as np
    rng = np.random.default_rng(12)
    df = s.create_dataframe({
        "a": rng.integers(0, 30, 800).tolist(),
        "c": [f"g{i % 7}" for i in range(800)],
        "b": rng.normal(size=800).tolist()})
    s.create_or_replace_temp_view("t97", df)
    out = s.sql("""
        WITH lo AS (SELECT a, c FROM t97 WHERE b > 0.3 GROUP BY a, c),
             hi AS (SELECT a, c FROM t97 WHERE b < -0.3 GROUP BY a, c)
        SELECT SUM(CASE WHEN lo.a IS NOT NULL AND hi.a IS NULL
                        THEN 1 ELSE 0 END) AS lo_only,
               SUM(CASE WHEN lo.a IS NULL AND hi.a IS NOT NULL
                        THEN 1 ELSE 0 END) AS hi_only,
               SUM(CASE WHEN lo.a IS NOT NULL AND hi.a IS NOT NULL
                        THEN 1 ELSE 0 END) AS both_cnt
        FROM lo FULL OUTER JOIN hi ON lo.a = hi.a AND lo.c = hi.c""")
    assert_tpu_cpu_equal_df(out)


def test_final_aggregate_joins_partition_wise():
    """A FINAL grouped aggregate advertises its child exchange's hash
    partitioning; a co-partitioned join must therefore receive one
    output partition per child partition from it (SF1 q11/q74
    regression: the whole-stream default raised 'join children
    partition counts differ' once the build side outgrew adaptive
    broadcast)."""
    import numpy as np
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import Alias, col
    from spark_rapids_tpu.plan.session import TpuSession

    conf = SrtConf({"srt.shuffle.partitions": 4,
                    # force the shuffled-join zip path: no broadcast,
                    # no adaptive re-planning
                    "srt.sql.broadcastRowThreshold": 1,
                    "srt.sql.adaptive.enabled": False})
    sess = TpuSession(conf)
    rng = np.random.default_rng(8)
    n = 6000
    t = sess.create_dataframe({
        "k": rng.integers(0, 97, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist()})
    u = sess.create_dataframe({
        "k": rng.integers(0, 97, n).tolist(),
        "w": rng.uniform(0, 5, n).tolist()})
    agg_t = t.group_by("k").agg(Alias(Sum(col("v")), "sv"),
                                Alias(CountStar(), "ct"))
    agg_u = u.group_by("k").agg(Alias(Sum(col("w")), "sw"))
    joined = agg_t.join(agg_u, "k")
    rows = {r["k"]: (r["sv"], r["ct"], r["sw"]) for r in joined.collect()}
    kt = np.array(t.to_pandas()["k"])
    vt = np.array(t.to_pandas()["v"])
    ku = np.array(u.to_pandas()["k"])
    wu = np.array(u.to_pandas()["w"])
    keys = sorted(set(kt) & set(ku))
    assert len(rows) == len(keys)
    for k in keys:
        sv, ct, sw = rows[k]
        assert ct == int((kt == k).sum())
        assert abs(sv - vt[kt == k].sum()) < 1e-9
        assert abs(sw - wu[ku == k].sum()) < 1e-9


def test_broadcast_join_partition_wise_chain():
    """q11's plan shape: FINAL aggregate -> broadcast join -> shuffled
    join. The broadcast join advertises the aggregate's hash
    partitioning, so the shuffled join above consumes IT partition-wise
    — one joined partition per probe partition, same broadcast build
    for all (and an empty build must empty every partition)."""
    import numpy as np
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import Alias, col
    from spark_rapids_tpu.plan.session import TpuSession

    conf = SrtConf({"srt.shuffle.partitions": 4,
                    # dims under 50 rows broadcast; the big sides shuffle
                    "srt.sql.broadcastRowThreshold": 50,
                    "srt.sql.adaptive.enabled": False})
    sess = TpuSession(conf)
    rng = np.random.default_rng(15)
    n = 5000
    t = sess.create_dataframe({
        "k": rng.integers(0, 61, n).tolist(),
        "j": rng.integers(0, 5, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist()})
    u = sess.create_dataframe({
        "k": rng.integers(0, 61, n).tolist(),
        "w": rng.uniform(0, 5, n).tolist()})
    dim = sess.create_dataframe({"j": list(range(5)),
                                 "tag": [f"d{i}" for i in range(5)]})
    agg_t = t.group_by("k", "j").agg(Alias(Sum(col("v")), "sv"))
    agg_u = u.group_by("k").agg(Alias(Sum(col("w")), "sw"))
    chain = agg_t.join(dim, "j").join(agg_u, "k")
    tree = __import__(
        "spark_rapids_tpu.plan.overrides", fromlist=["apply_overrides"]
    ).apply_overrides(chain.plan, conf).tree_string()
    assert "BroadcastHashJoin" in tree and "ShuffledHashJoin" in tree, \
        tree
    got = {}
    for r in chain.collect():
        got.setdefault(r["k"], 0.0)
        got[r["k"]] += r["sv"]
    kt, jt_, vt = (np.array(t.to_pandas()[c]) for c in ("k", "j", "v"))
    ku, wu = (np.array(u.to_pandas()[c]) for c in ("k", "w"))
    keys = sorted(set(kt) & set(ku))
    assert set(got) == set(keys)
    for k in keys:
        assert abs(got[k] - vt[kt == k].sum()) < 1e-9

    # empty broadcast build: inner join must produce zero rows from
    # EVERY partition (the _empty_result lane, per partition)
    empty_dim = sess.create_dataframe({"j": [], "tag": []},
                                      [("j", __import__(
                                          "spark_rapids_tpu.columnar.dtypes",
                                          fromlist=["INT64"]).INT64),
                                       ("tag", __import__(
                                           "spark_rapids_tpu.columnar.dtypes",
                                           fromlist=["STRING"]).STRING)])
    chain2 = agg_t.join(empty_dim, "j").join(agg_u, "k")
    assert chain2.collect() == []


# ------------------------------------------------- byte-based triggers

def test_byte_target_coalescing():
    """Rows alone would never coalesce (huge row floor); the byte
    target must close groups on measured partition bytes instead."""
    s = make_session(**{
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": "1000000",
        "srt.sql.adaptive.coalescePartitions.targetBytes": "100000000"})
    df = make_df(s, {"k": IntGen(lo=0, hi=40), "v": IntGen()}, 400, seed=3)
    q = df.group_by(col("k")).agg(Sum(col("v")).alias("sv"))
    assert_tpu_cpu_equal_df(q)
    _, metrics = _run_with_metrics(q)
    # 400 rows over 8 partitions, all under both budgets -> one group
    assert metrics.get("adaptiveCoalescedPartitions", 0) >= 4


def test_byte_skew_split():
    """Skew detected by partition BYTES (row threshold out of reach):
    the dominant key's partition must be sub-partitioned and results
    must still match the oracle."""
    s = make_session(**{
        "srt.sql.adaptive.skewJoin.partitionRows": "100000000",
        "srt.sql.adaptive.skewJoin.partitionBytes": "2048",
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": "1"})
    left = make_df(s, {"k": IntGen(lo=0, hi=2), "v": IntGen()}, 600,
                   seed=17)
    right = make_df(s, {"k": IntGen(lo=0, hi=2), "w": IntGen()}, 600,
                    seed=19)
    q = left.join(right, ([col("k")], [col("k")]), how="inner")
    out, metrics = _run_with_metrics(q)
    assert metrics.get("skewedJoinPartitions", 0) >= 1
    assert_tpu_cpu_equal_df(q)


def test_byte_broadcast_demote():
    """Demotion driven by measured build-side BYTES: the row threshold
    is disabled (broadcastRowThreshold=1 keeps the static plan
    shuffled, adaptive row threshold inherits it), so only
    autoBroadcastJoinBytes can trigger the switch."""
    s = make_session(**{"srt.sql.adaptive.autoBroadcastJoinBytes":
                        "104857600"})
    left = make_df(s, {"k": IntGen(lo=0, hi=30), "v": IntGen()}, 400,
                   seed=9)
    right = make_df(s, {"k": IntGen(lo=0, hi=30), "w": IntGen()}, 50,
                    seed=11)
    q = left.join(right, ([col("k")], [col("k")]), how="inner")
    out, metrics = _run_with_metrics(q)
    assert metrics.get("adaptiveBroadcastJoins", 0) == 1
    assert_tpu_cpu_equal_df(q)


def test_max_broadcast_build_bytes_subpartitions():
    """An oversized BROADCAST build (planned at compile time) must be
    sub-partitioned when it exceeds maxBroadcastBuildBytes, with
    results unchanged and the decision logged."""
    import spark_rapids_tpu.obs.events as ev
    import tempfile
    logdir = tempfile.mkdtemp(prefix="srt_adaptive_ev_")
    ev.install(ev.EventLogWriter(logdir))
    try:
        s = TpuSession(SrtConf({
            "srt.shuffle.partitions": 4,
            # generous row threshold -> static plan broadcasts
            "srt.sql.broadcastRowThreshold": "100000",
            "srt.sql.adaptive.maxBroadcastBuildBytes": "512"}))
        left = make_df(s, {"k": IntGen(lo=0, hi=30), "v": IntGen()},
                       400, seed=21)
        right = make_df(s, {"k": IntGen(lo=0, hi=30), "w": IntGen()},
                        200, seed=23)
        q = left.join(right, ([col("k")], [col("k")]), how="inner")
        from spark_rapids_tpu.plan import overrides
        tree = overrides.apply_overrides(
            q.plan, s.conf).tree_string()
        assert "BroadcastHashJoin" in tree, tree
        assert_tpu_cpu_equal_df(q, conf=s.conf)
        recs = ev.read_all_events(logdir)
        sub = [r for r in recs if r.get("event") == "AdaptivePlanChanged"
               and r.get("decision") == "subpartition_broadcast"]
        assert sub, [r.get("event") for r in recs]
        assert sub[0]["slices"] >= 2
    finally:
        ev.install(None)


# -------------------------------------------------- events + conf alias

def test_adaptive_decision_events():
    """Every adaptive plan change must leave an AdaptivePlanChanged
    (and, for skew, SkewSplit) record in the event log."""
    import spark_rapids_tpu.obs.events as ev
    import tempfile
    logdir = tempfile.mkdtemp(prefix="srt_adaptive_ev_")
    ev.install(ev.EventLogWriter(logdir))
    try:
        # coalesce
        s = make_session()
        df = make_df(s, {"k": IntGen(lo=0, hi=40), "v": IntGen()}, 200,
                     seed=3)
        _run_with_metrics(df.group_by(col("k"))
                          .agg(Sum(col("v")).alias("sv")))
        # demote
        s2 = make_session(
            **{"srt.sql.adaptive.autoBroadcastJoinRows": "1000"})
        l2 = make_df(s2, {"k": IntGen(lo=0, hi=30), "v": IntGen()}, 400,
                     seed=9)
        r2 = make_df(s2, {"k": IntGen(lo=0, hi=30), "w": IntGen()}, 50,
                     seed=11)
        _run_with_metrics(l2.join(r2, ([col("k")], [col("k")]),
                                  how="inner"))
        # skew split
        s3 = make_session(**{
            "srt.sql.adaptive.skewJoin.partitionRows": "128",
            "srt.sql.adaptive.coalescePartitions.minPartitionRows": "1"})
        l3 = make_df(s3, {"k": IntGen(lo=0, hi=1), "v": IntGen()}, 600,
                     seed=25)
        r3 = make_df(s3, {"k": IntGen(lo=0, hi=1), "w": IntGen()}, 600,
                     seed=27)
        _run_with_metrics(l3.join(r3, ([col("k")], [col("k")]),
                                  how="inner"))
        recs = ev.read_all_events(logdir)
        by_rule = {}
        for r in recs:
            if r.get("event") == "AdaptivePlanChanged":
                by_rule.setdefault(r.get("rule"), []).append(r)
        assert "coalescePartitions" in by_rule, sorted(by_rule)
        assert "joinStrategy" in by_rule, sorted(by_rule)
        assert "skewJoin" in by_rule, sorted(by_rule)
        demote = by_rule["joinStrategy"][0]
        assert demote["decision"] == "broadcast_build"
        assert demote["build_rows"] <= 1000
        splits = [r for r in recs if r.get("event") == "SkewSplit"]
        assert splits and splits[0]["slices"] >= 2
    finally:
        ev.install(None)


def test_legacy_adaptive_broadcast_rows_alias():
    """The deprecated srt.sql.adaptiveBroadcastRows key must feed the
    new srt.sql.adaptive.autoBroadcastJoinRows entry."""
    from spark_rapids_tpu.conf import ADAPTIVE_BROADCAST_ROWS
    s = make_session(**{"srt.sql.adaptiveBroadcastRows": "777"})
    assert s.conf.get(ADAPTIVE_BROADCAST_ROWS) == 777
    # and it still drives the demotion rule end to end
    left = make_df(s, {"k": IntGen(lo=0, hi=30), "v": IntGen()}, 400,
                   seed=9)
    right = make_df(s, {"k": IntGen(lo=0, hi=30), "w": IntGen()}, 50,
                    seed=11)
    q = left.join(right, ([col("k")], [col("k")]), how="inner")
    _, metrics = _run_with_metrics(q)
    assert metrics.get("adaptiveBroadcastJoins", 0) == 1


# ------------------------------------------------ speculation protocol

def test_speculative_barrier_protocol():
    """Driver-side speculation protocol, single-threaded: worker 0
    arrives, waits past minWait, receives a speculate directive for the
    straggler's unit, reports the result, and the release verdict
    routes ALL reads to worker 0's copies. The late straggler's commit
    loses first-result-wins."""
    from spark_rapids_tpu.parallel.cluster import ClusterDriver
    driver = ClusterDriver(num_workers=2)
    try:
        driver._spec_conf = (1.0, 0.05)          # factor, min_wait
        driver._expected_units = [(0,), (1,)]
        driver._worker_eids = []                 # no heartbeat gating
        sid = 55
        r1 = driver._barrier_speculative({
            "shuffle_id": sid, "worker": 0, "pos": 2,
            "speculation": True, "spec_ok": True,
            "unit": (0,), "map_ids": [100]})
        assert r1 == {"type": "speculate", "unit": [1]}
        r2 = driver._barrier_speculative({
            "shuffle_id": sid, "worker": 0, "pos": 2,
            "speculation": True, "spec_report": True,
            "unit": (1,), "map_ids": [200]})
        assert r2["type"] == "release"
        allowed = r2["winners"]["allowed"]
        assert tuple(allowed[0]) == (100, 200)
        assert tuple(allowed[1]) == ()
        # straggler finally arrives: sticky release, losing commit
        r3 = driver._barrier_speculative({
            "shuffle_id": sid, "worker": 1, "pos": 2,
            "speculation": True, "spec_ok": True,
            "unit": (1,), "map_ids": [150]})
        assert r3["winners"]["allowed"] == allowed
        committed = driver._registry.committed_maps(sid)
        assert committed[(1,)][0] == 0          # worker 0 won unit (1,)
        # a suppressed stage must NOT be reusable across retries
        assert 2 not in driver._registry.complete_positions()
    finally:
        driver.shutdown()


# slow: ~12 s, real worker subprocesses
@pytest.mark.slow
def test_cluster_speculation_end_to_end(tmp_path_factory):
    """Real 2-worker cluster: worker 1 stalls 6s at the barrier via
    fault injection, worker 0 speculates its shard, the job finishes
    early with oracle-identical results, and the event log shows the
    launch and the winning result."""
    import tempfile
    import numpy as np
    import spark_rapids_tpu.obs.events as ev
    from spark_rapids_tpu.expr.aggregates import CountStar
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    root = tmp_path_factory.mktemp("spec_cluster")
    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(31)
    n = 12_000
    fact = session.create_dataframe({
        "k": rng.integers(0, 40, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist()})
    fact_dir = str(root / "fact")
    fact.write.parquet(fact_dir)
    logdir = str(root / "events")
    ev.install(ev.EventLogWriter(logdir))
    driver = ClusterDriver(num_workers=2, barrier_timeout=60)
    procs = launch_local_workers(driver, 2)
    job_conf = {
        "srt.shuffle.partitions": 4,
        "srt.cluster.barrierTimeoutSec": 60,
        "srt.sql.adaptive.speculation.enabled": "true",
        "srt.sql.adaptive.speculation.minWaitSec": "0.3",
        "srt.sql.adaptive.speculation.slowWorkerFactor": "1.0",
        "srt.test.faultPlan":
            "seed=5|cluster.barrier:delay@1+6.0~workers=1;",
    }
    try:
        driver.wait_for_workers(timeout=90)
        sess = TpuSession(SrtConf({}))
        plan = sess.read.parquet(fact_dir).group_by("k").agg(
            Alias(Sum(col("v")), "s"), Alias(CountStar(), "c")).plan
        rows = driver.run(plan, job_conf)
        expect = {r["k"]: r for r in TpuSession(SrtConf({})).read
                  .parquet(fact_dir).group_by("k")
                  .agg(Alias(Sum(col("v")), "s"),
                       Alias(CountStar(), "c")).collect()}
        assert len(rows) == len(expect)
        for r in rows:
            e = expect[r["k"]]
            assert r["c"] == e["c"]
            assert r["s"] == pytest.approx(e["s"], rel=1e-9)
        recs = ev.read_all_events(logdir)
        launches = [r for r in recs
                    if r.get("event") == "SpeculativeTask"
                    and r.get("phase") == "launch"]
        results = [r for r in recs
                   if r.get("event") == "SpeculativeTask"
                   and r.get("phase") == "result"]
        assert launches, [r.get("event") for r in recs]
        assert launches[0]["speculator"] == 0
        assert launches[0]["straggler"] == 1
        assert results and results[0]["won"] is True, results
    finally:
        ev.install(None)
        driver.shutdown()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


# slow: ~13 s, real worker subprocesses
@pytest.mark.slow
def test_stage_retry_with_adaptive_replan(tmp_path_factory):
    """Stage-level retry x adaptive: worker 1 crashes at the final
    (range-exchange) barrier AFTER the hash exchange completed, with
    adaptive coalescing active. The retry must reuse the completed
    hash exchange, re-derive the SAME coalesce decision from the
    surviving stats, and produce oracle-identical sorted rows."""
    import numpy as np
    from spark_rapids_tpu.expr.aggregates import CountStar
    from spark_rapids_tpu.expr.core import Alias
    from spark_rapids_tpu.parallel.cluster import (ClusterDriver,
                                                   launch_local_workers)
    root = tmp_path_factory.mktemp("adaptive_retry")
    session = TpuSession(SrtConf({}))
    rng = np.random.default_rng(41)
    n = 9_000
    fact = session.create_dataframe({
        "k": rng.integers(0, 40, n).tolist(),
        "v": rng.uniform(0, 10, n).tolist()})
    fact_dir = str(root / "fact")
    fact.write.parquet(fact_dir)
    spec = "seed=3|cluster.barrier:crash@1~attempt=0;workers=1;pos=0;"
    job_conf = {
        "srt.shuffle.partitions": 4,
        "srt.cluster.barrierTimeoutSec": 60,
        # row floor far above any partition -> every reduce stage
        # coalesces into one group on every attempt
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": "100000",
        "srt.test.faultPlan": spec}
    driver = ClusterDriver(num_workers=3, barrier_timeout=60,
                           heartbeat_interval=0.5, heartbeat_timeout=6)
    procs = launch_local_workers(driver, 3)
    try:
        driver.wait_for_workers(timeout=90)
        sess = TpuSession(SrtConf({}))
        plan = sess.read.parquet(fact_dir) \
            .group_by("k").agg(Alias(Sum(col("v")), "s"),
                               Alias(CountStar(), "c")) \
            .sort("k").plan
        rows = driver.run(plan, job_conf)
        expect = TpuSession(SrtConf({})).read.parquet(fact_dir) \
            .group_by("k").agg(Alias(Sum(col("v")), "s"),
                               Alias(CountStar(), "c")) \
            .sort("k").collect()
        assert [r["k"] for r in rows] == [r["k"] for r in expect]
        for got, want in zip(rows, expect):
            assert got["c"] == want["c"]
            assert got["s"] == pytest.approx(want["s"], rel=1e-9)
        stage = [e for e in driver.recovery_events
                 if e["type"] == "stage_retry"]
        assert stage, driver.recovery_events
        assert stage[0]["reused_positions"] == [1], driver.recovery_events
        coalesced = sum(v.get("adaptiveCoalescedPartitions", 0)
                        for wm in driver.last_metrics
                        for v in wm.values())
        assert coalesced >= 1, driver.last_metrics
    finally:
        driver.shutdown()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()


# ----------------------------------------------- NDS differential runs

NDS_AB_QUERIES = ("q3", "q19", "q42")


def _nds_rows(data_dir, qid, scale, adaptive_on):
    from spark_rapids_tpu.models.nds import NDS_QUERIES, register_nds
    s = TpuSession(SrtConf({
        "srt.shuffle.partitions": 8,
        "srt.sql.adaptive.enabled": "true" if adaptive_on else "false",
        # low floor so coalescing actually fires at tiny scale
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": "256"}))
    register_nds(s, data_dir, scale_rows=scale)
    rows = s.sql(NDS_QUERIES[qid]).collect()
    keys = sorted(rows[0]) if rows else []
    return sorted((tuple(r[k] for k in keys) for r in rows), key=repr)


@pytest.fixture(scope="module")
def nds_ab_data(tmp_path_factory):
    return str(tmp_path_factory.mktemp("adaptive_nds") / "data")


# tier-1 keeps one NDS shape (~7-11 s each)
@pytest.mark.parametrize("qid", [
    q if q == "q42" else pytest.param(q, marks=pytest.mark.slow)
    for q in NDS_AB_QUERIES])
def test_nds_adaptive_bit_identical(nds_ab_data, qid):
    """Adaptive on vs off must be BIT-IDENTICAL on NDS queries:
    coalescing only regroups disjoint hash buckets, so every key's
    accumulation order is unchanged."""
    on = _nds_rows(nds_ab_data, qid, 4_000, True)
    off = _nds_rows(nds_ab_data, qid, 4_000, False)
    assert on == off


@pytest.mark.slow
@pytest.mark.parametrize("qid", NDS_AB_QUERIES)
def test_nds_adaptive_bit_identical_100k(tmp_path_factory, qid):
    data = str(tmp_path_factory.mktemp("adaptive_nds_100k") / "data")
    on = _nds_rows(data, qid, 100_000, True)
    off = _nds_rows(data, qid, 100_000, False)
    assert on == off
