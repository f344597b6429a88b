"""Runtime dynamic partition pruning (GpuSubqueryBroadcastExec:1-299 /
GpuDynamicPruningExpression role): a broadcast join's materialized
build side prunes the probe side's partitioned scan file list before
any probe file opens."""

import glob
import os

import pytest

from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.expr import col, lit
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.plan.session import TpuSession


@pytest.fixture()
def star_schema(tmp_path):
    """Partitioned fact table (8 partitions on k) + small dim table
    where only 2 dim rows survive the filter."""
    session = TpuSession(SrtConf({}))
    fact_root = str(tmp_path / "fact")
    for k in range(8):
        part = session.create_dataframe({
            "v": [float(k * 100 + i) for i in range(50)],
            "x": list(range(50)),
        })
        part.write.parquet(os.path.join(fact_root, f"k={k}"))
    dim = session.create_dataframe({
        "k": list(range(8)),
        "cat": ["keep" if k < 2 else "drop" for k in range(8)],
    })
    dim_dir = str(tmp_path / "dim")
    dim.write.parquet(dim_dir)
    return {"fact": fact_root, "dim": dim_dir}


def _run(star_schema, dpp: bool):
    session = TpuSession(SrtConf({
        "srt.sql.dpp.enabled": dpp,
        # dim is tiny: always a broadcast join
        "srt.sql.broadcastRowThreshold": 1000,
    }))
    fact = session.read.parquet(star_schema["fact"])
    dim = session.read.parquet(star_schema["dim"])
    df = (fact.join(dim.filter(col("cat") == lit("keep")), "k")
          .group_by("k")
          .agg(Alias(Sum(col("v")), "s"), Alias(CountStar(), "c")))
    return df


def test_dpp_prunes_files_same_results(star_schema):
    early = {}
    for dpp in (True, False):
        df = _run(star_schema, dpp)
        rows = {r["k"]: (r["s"], r["c"]) for r in df.collect()}
        early[dpp] = (rows, df.session._last_execution["phases"]
                      ["prefetch_early_starts"])
    on, off = early[True][0], early[False][0]
    assert on == off
    assert set(on) == {0, 1}
    assert all(c == 50 for _, c in on.values())
    # the join starts its build side's producer with itself either way;
    # the fact scan's only where no runtime filter is on its way to it
    assert (early[True][1], early[False][1]) == (1, 2)


def test_dpp_metric_counts_pruned_files(star_schema):
    """The scan must record 6 of 8 files pruned by the runtime filter."""
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.plan import overrides

    session = TpuSession(SrtConf({
        "srt.sql.dpp.enabled": True,
        "srt.sql.broadcastRowThreshold": 1000,
    }))
    fact = session.read.parquet(star_schema["fact"])
    dim = session.read.parquet(star_schema["dim"])
    df = (fact.join(dim.filter(col("cat") == lit("keep")), "k")
          .group_by("k").agg(Alias(CountStar(), "c")))
    physical = overrides.apply_overrides(df.plan, session.conf)
    ctx = ExecContext(session.conf)
    rows = 0
    for batch in physical.execute(ctx):
        rows += int(batch.num_rows)
    assert rows == 2
    dpp_metrics = [m["dppPrunedFiles"].value
                   for m in ctx.metrics.values()
                   if "dppPrunedFiles" in m]
    assert sum(dpp_metrics) == 6, \
        f"expected 6 pruned fact files, metrics: {dpp_metrics}"
    # the probe side waited for the filter: its producer started at its
    # first pull, the build side's when the join started
    early = {exec_id.split("#")[0]: m["prefetchEarlyStarts"].value
             for exec_id, m in ctx.metrics.items()
             if "prefetchEarlyStarts" in m}
    assert early == {"BroadcastExchangeExec": 1}


def test_dpp_not_applied_to_outer_join(star_schema):
    """A left-outer probe side must NOT be pruned (unmatched rows are
    preserved)."""
    session = TpuSession(SrtConf({
        "srt.sql.dpp.enabled": True,
        "srt.sql.broadcastRowThreshold": 1000,
    }))
    fact = session.read.parquet(star_schema["fact"])
    dim = session.read.parquet(star_schema["dim"]) \
        .filter(col("cat") == lit("keep"))
    df = fact.join(dim, "k", how="left_outer") \
        .group_by("k").agg(Alias(CountStar(), "c"))
    got = {r["k"]: r["c"] for r in df.collect()}
    assert set(got) == set(range(8))
    assert all(c == 50 for c in got.values())
