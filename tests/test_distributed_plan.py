"""Planner-integrated shuffle: staged plans (partial agg -> exchange ->
final agg; co-partitioned joins; range-partitioned global sort) execute
through ShuffleExchangeExec with results identical to the CPU oracle.

Mirrors the reference's staged execution contract
(GpuShuffleExchangeExecBase.scala:167, GpuHashPartitioningBase.scala:64,
GpuRangePartitioner.scala) — the distributed layer is exercised *by the
product plan*, not hand-assembled."""

import pytest

from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.aggregate import FINAL, PARTIAL, HashAggregateExec
from spark_rapids_tpu.exec.exchange import (BroadcastExchangeExec,
                                            ShuffleExchangeExec)
from spark_rapids_tpu.exec.join import (BroadcastHashJoinExec,
                                        ShuffledHashJoinExec)
from spark_rapids_tpu.exec.sort import SortExec
from spark_rapids_tpu.expr.aggregates import Average, CountStar, Min, Sum
from spark_rapids_tpu.expr.core import Alias, col, lit
from spark_rapids_tpu.plan import overrides
from spark_rapids_tpu.plan.session import TpuSession
from spark_rapids_tpu.testing import assert_tpu_cpu_equal_df


def _collect_nodes(node, out=None):
    out = [] if out is None else out
    out.append(node)
    for c in getattr(node, "children", []):
        _collect_nodes(c, out)
    if hasattr(node, "cpu_child"):
        _collect_nodes(node.cpu_child, out)
    if hasattr(node, "tpu"):
        _collect_nodes(node.tpu, out)
    return out


def _physical(df, conf=None):
    return overrides.apply_overrides(df.plan, conf or df.session.conf)


@pytest.fixture()
def session():
    return TpuSession(SrtConf({"srt.shuffle.partitions": 4}))


def _skewed(session, n=500):
    ks = [(i * 7919) % 13 for i in range(n)]
    vs = [float(i % 97) - 5.0 for i in range(n)]
    tag = ["abcdefgh"[i % 8] * ((i % 3) + 1) for i in range(n)]
    return session.create_dataframe({"k": ks, "v": vs, "tag": tag})


def test_grouped_agg_plans_exchange(session):
    df = _skewed(session).group_by("k").agg(
        Alias(Sum(col("v")), "sv"), Alias(CountStar(), "c"),
        Alias(Average(col("v")), "av"), Alias(Min(col("v")), "mn"))
    nodes = _collect_nodes(_physical(df))
    exchanges = [n for n in nodes if isinstance(n, ShuffleExchangeExec)]
    partials = [n for n in nodes if isinstance(n, HashAggregateExec)
                and n.mode == PARTIAL]
    finals = [n for n in nodes if isinstance(n, HashAggregateExec)
              and n.mode == FINAL]
    assert len(exchanges) == 1 and exchanges[0].num_partitions == 4
    assert len(partials) == 1 and len(finals) == 1
    # final sits above the exchange, which sits above the partial
    assert finals[0].children == [exchanges[0]]
    assert exchanges[0].children == [partials[0]]
    assert_tpu_cpu_equal_df(df)


def test_global_agg_single_partition_exchange(session):
    df = _skewed(session).agg(Alias(Sum(col("v")), "s"),
                              Alias(CountStar(), "c"))
    nodes = _collect_nodes(_physical(df))
    exchanges = [n for n in nodes if isinstance(n, ShuffleExchangeExec)]
    assert len(exchanges) == 1 and exchanges[0].num_partitions == 1
    assert_tpu_cpu_equal_df(df)


def test_small_build_side_broadcasts(session):
    left = _skewed(session)
    right = session.create_dataframe({"k": list(range(13)),
                                      "w": [i * 1.5 for i in range(13)]})
    df = left.join(right, "k")
    nodes = _collect_nodes(_physical(df))
    assert any(isinstance(n, BroadcastExchangeExec) for n in nodes)
    assert any(isinstance(n, BroadcastHashJoinExec) for n in nodes)
    assert not any(isinstance(n, ShuffledHashJoinExec) for n in nodes)
    assert_tpu_cpu_equal_df(df)


def test_large_build_side_shuffles_both_sides():
    conf = SrtConf({"srt.shuffle.partitions": 4,
                    "srt.sql.broadcastRowThreshold": 8})
    session = TpuSession(conf)
    left = _skewed(session)
    right = session.create_dataframe(
        {"k": [i % 13 for i in range(100)],
         "w": [i * 1.5 for i in range(100)]})
    df = left.join(right, "k")
    nodes = _collect_nodes(_physical(df, conf))
    joins = [n for n in nodes if isinstance(n, ShuffledHashJoinExec)]
    exchanges = [n for n in nodes if isinstance(n, ShuffleExchangeExec)]
    assert len(joins) == 1
    assert len(exchanges) == 2, "both join sides must be exchanged"
    assert {e.num_partitions for e in exchanges} == {4}
    assert all(isinstance(c, ShuffleExchangeExec)
               for c in joins[0].children)
    assert_tpu_cpu_equal_df(df)


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_shuffled_join_types_match_oracle(how):
    conf = SrtConf({"srt.shuffle.partitions": 3,
                    "srt.sql.broadcastRowThreshold": 1})
    session = TpuSession(conf)
    left = session.create_dataframe(
        {"k": [i % 11 for i in range(200)] + [None] * 5,
         "v": list(range(205))})
    right = session.create_dataframe(
        {"k": [i % 7 for i in range(60)] + [None] * 3,
         "w": [float(i) for i in range(63)]})
    df = left.join(right, "k", how=how)
    nodes = _collect_nodes(_physical(df, conf))
    assert any(isinstance(n, ShuffledHashJoinExec) for n in nodes)
    assert_tpu_cpu_equal_df(df)


def test_join_key_type_coercion():
    """int32-vs-int64 keys get cast to a common type before hashing —
    partition placement must agree across sides."""
    import numpy as np
    from spark_rapids_tpu.columnar import dtypes as dt
    conf = SrtConf({"srt.shuffle.partitions": 4,
                    "srt.sql.broadcastRowThreshold": 1})
    session = TpuSession(conf)
    left = session.create_dataframe({"k": list(range(50)),
                                     "v": list(range(50))},
                                    schema=[("k", dt.INT32), ("v", dt.INT64)])
    right = session.create_dataframe({"k": [i * 2 for i in range(25)],
                                      "w": list(range(25))},
                                     schema=[("k", dt.INT64),
                                             ("w", dt.INT64)])
    df = left.join(right, on=([col("k")], [col("k")]))
    assert_tpu_cpu_equal_df(df)


def test_distributed_sort_orders(session):
    base = session.create_dataframe(
        {"a": [5, None, 3, 8, 1, None, 9, 2, 7, 0, 4, 6] * 20,
         "s": ["mango", "apple", None, "kiwi", "banana", "peach",
               None, "apricot", "fig", "date", "cherry", "lime"] * 20})
    for asc in (True, False):
        df = base.sort("a", "s", ascending=asc)
        nodes = _collect_nodes(_physical(df))
        ex = [n for n in nodes if isinstance(n, ShuffleExchangeExec)]
        assert any(e.sort_orders for e in ex), "range exchange expected"
        assert_tpu_cpu_equal_df(df, ignore_order=False)


def test_distributed_sort_string_desc(session):
    base = session.create_dataframe(
        {"s": [f"key_{(i * 37) % 101:03d}" for i in range(300)],
         "v": list(range(300))})
    df = base.sort("s", ascending=False)
    assert_tpu_cpu_equal_df(df, ignore_order=False)


def test_distributed_sort_floats_with_nan(session):
    vals = [1.5, float("nan"), -0.0, 0.0, None, 2.5, float("inf"),
            float("-inf"), -3.25] * 15
    base = session.create_dataframe({"v": vals})
    for asc in (True, False):
        df = base.sort("v", ascending=asc)
        assert_tpu_cpu_equal_df(df, ignore_order=False)


def test_exchange_disabled_runs_single_stream(session):
    conf = session.conf.set("srt.shuffle.exchange.enabled", False)
    df = _skewed(session).group_by("k").agg(Alias(Sum(col("v")), "s"))
    nodes = _collect_nodes(overrides.apply_overrides(df.plan, conf))
    assert not any(isinstance(n, ShuffleExchangeExec) for n in nodes)
    # partial+final still compose correctly without the exchange
    assert_tpu_cpu_equal_df(df, conf=conf)


def test_q3_executes_through_exchanges(session, tmp_path):
    """TPC-H q3 via session.read.parquet -> join -> group_by runs as a
    staged plan with shuffle exchanges and matches the oracle
    (VERDICT round-1 item 1's done-criterion)."""
    from spark_rapids_tpu.models import q3, tpch_tables
    # a threshold under every side's estimate: an inner join builds its
    # smaller side, and at 500 that side was broadcast
    conf = SrtConf({"srt.shuffle.partitions": 4,
                    "srt.sql.broadcastRowThreshold": 50})
    sess = TpuSession(conf)
    t = tpch_tables(sess, str(tmp_path), scale_rows=8_000,
                    chunk_rows=4_096)
    df = q3(t["customer"], t["orders"], t["lineitem"])
    nodes = _collect_nodes(_physical(df, conf))
    exchanges = [n for n in nodes if isinstance(n, ShuffleExchangeExec)]
    assert any(isinstance(n, ShuffledHashJoinExec) for n in nodes)
    assert any(isinstance(n, HashAggregateExec) and n.mode == FINAL
               for n in nodes)
    assert len(exchanges) >= 3  # two join sides + agg merge
    assert_tpu_cpu_equal_df(df, approx_float=1e-5, ignore_order=False)


def test_metrics_record_shuffle_rows(session):
    from spark_rapids_tpu.exec.base import ExecContext
    df = _skewed(session, n=300).group_by("k").agg(
        Alias(Sum(col("v")), "s"))
    phys = _physical(df)
    ctx = ExecContext(session.conf)
    rows = sum(int(b.num_rows) for b in phys.execute(ctx))
    assert rows == 13
    written = [m["shuffleWriteRows"].value
               for eid, m in ctx.metrics.items()
               if "shuffleWriteRows" in m]
    assert written and sum(written) > 0
