"""Sub-partition hash join + aggregate re-partition merge fallback.

Reference: GpuSubPartitionHashJoin.scala (build sides over budget are
hash-bucketed and joined pair-wise) and the aggregate merge
re-partition fallback (GpuAggregateExec.scala:711,792). Thresholds are
driven through confs so tiny budgets force the fallback paths; results
must match the CPU oracle and the task metrics must show the split
actually happened.
"""

import pytest

from spark_rapids_tpu.conf import (AGG_MERGE_PARTITION_ROWS,
                                   JOIN_SUB_PARTITION_ROWS, SrtConf)
from spark_rapids_tpu.expr.aggregates import Count, Max, Min, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.testing import (IntGen, StringGen,
                                      assert_tpu_cpu_equal_df, gen_table)

# agg threshold must undercut a post-exchange partition's share of the
# groups (~groups/shuffle.partitions) so the merge fallback fires
TINY = {JOIN_SUB_PARTITION_ROWS.key: "64",
        AGG_MERGE_PARTITION_ROWS.key: "16"}


@pytest.fixture(scope="module")
def session():
    return TpuSession(SrtConf(TINY))


def make_df(session, gens, n, seed=0):
    data, schema = gen_table(gens, n, seed)
    return session.create_dataframe(data, schema)


def _run_with_metrics(df):
    """Execute the physical plan directly, returning (table, metrics)."""
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
    for exec_metrics in ctx.metrics.values():
        for name, metric in exec_metrics.items():
            merged[name] = merged.get(name, 0) + metric.value
    return out, merged


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_subpartition_join_matches_oracle(session, how):
    left = make_df(session, {"k": IntGen(lo=0, hi=80),
                             "v": IntGen(lo=-50, hi=50)}, 400, seed=1)
    right = make_df(session, {"k": IntGen(lo=0, hi=80),
                              "w": IntGen(lo=0, hi=9)}, 300, seed=2)
    df = left.join(right, ([col("k")], [col("k")]), how=how)
    assert_tpu_cpu_equal_df(df)


def test_subpartition_join_metric_fires(session):
    left = make_df(session, {"k": IntGen(lo=0, hi=80),
                             "v": IntGen(lo=-50, hi=50)}, 400, seed=3)
    right = make_df(session, {"k": IntGen(lo=0, hi=80),
                              "w": IntGen(lo=0, hi=9)}, 300, seed=4)
    df = left.join(right, ([col("k")], [col("k")]), how="inner")
    _, metrics = _run_with_metrics(df)
    # 300-row build over a 64-row budget -> ceil(300/64) buckets
    assert metrics.get("joinSubPartitions", 0) >= 5


def test_subpartition_join_string_keys_and_nulls(session):
    left = make_df(session, {"k": StringGen(max_len=4),
                             "v": IntGen()}, 300, seed=5)
    right = make_df(session, {"k": StringGen(max_len=4),
                              "w": IntGen()}, 300, seed=6)
    assert_tpu_cpu_equal_df(
        left.join(right, ([col("k")], [col("k")]), how="left"))


def test_agg_repartition_merge_matches_oracle(session):
    df = make_df(session, {"k": IntGen(lo=0, hi=300),
                           "v": IntGen(lo=-100, hi=100)}, 1000, seed=7)
    out = df.group_by(col("k")).agg(
        Sum(col("v")).alias("s"), Count(col("v")).alias("n"),
        Min(col("v")).alias("mn"), Max(col("v")).alias("mx"))
    assert_tpu_cpu_equal_df(out)


def test_agg_repartition_merge_metric_fires(session):
    df = make_df(session, {"k": IntGen(lo=0, hi=300),
                           "v": IntGen(lo=-100, hi=100)}, 1000, seed=8)
    out = df.group_by(col("k")).agg(Sum(col("v")).alias("s"))
    _, metrics = _run_with_metrics(out)
    assert metrics.get("aggMergePartitions", 0) >= 2


def test_thresholds_off_by_default():
    # defaults are far above test sizes: no sub-partitioning kicks in
    s = TpuSession()
    left = make_df(s, {"k": IntGen(lo=0, hi=20), "v": IntGen()}, 100)
    right = make_df(s, {"k": IntGen(lo=0, hi=20), "w": IntGen()}, 100)
    df = left.join(right, ([col("k")], [col("k")]), how="inner")
    _, metrics = _run_with_metrics(df)
    assert metrics.get("joinSubPartitions", 0) == 0


def test_inner_join_hot_key_skew_chunking(session):
    # one key dominates the build: hash bucketing can't split it, so
    # the inner-join path row-chunks the hot bucket instead (the probe
    # side is the larger: an inner join builds its smaller side)
    left = make_df(session, {"k": IntGen(lo=0, hi=3),
                             "v": IntGen(lo=-50, hi=50)}, 320, seed=9)
    right_data = {"k": [1] * 300, "w": list(range(300))}
    right = session.create_dataframe(right_data)
    df = left.join(right, ([col("k")], [col("k")]), how="inner")
    assert_tpu_cpu_equal_df(df)
    _, metrics = _run_with_metrics(df)
    assert metrics.get("joinSubPartitionSkew", 0) >= 1
