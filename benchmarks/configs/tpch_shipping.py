"""TPC-H shipping family: Q3, the Shipping Priority Query (specification v3, clause 2.4.3), as the deployment
``tpch_sf1_shipping`` runs it.

The builder and the plain pandas reference are this family's own: ``configs/tpch.py::_q3`` groups by
``o_orderkey, o_orderdate`` without ``o_shippriority`` and sorts by revenue alone, and stays as it is. Here the
query is the specification's: ``l_orderkey, revenue, o_orderdate, o_shippriority``, grouped by the three keys,
``ORDER BY revenue DESC, o_orderdate``, ``LIMIT 10``. The reference returns the full answer; ``harness/
compare.py`` applies the order and the cut.

``make_query`` refuses, before any query runs, an engine whose join execs do not count what the cell's metrics
read (``spark_rapids_tpu.exec.join.JOIN_COUNTERS``: the hash join's probe time, probe rows, candidates and
matches): the cell is about the general hash join, and an engine that cannot say what that join did has no
business in it.
"""

from __future__ import annotations

import datetime

import pandas as pd

from benchmarks.harness.lowprec import lower

ENTRIES = ("dataframe",)
QUERIES = ("q3",)

#: the hash join's counters this cell's metrics read (docs/OBSERVABILITY.md, "Joins")
REQUIRED_JOIN_COUNTERS = ("hashJoinProbeTime", "hashJoinProbeRows", "hashJoinCandidates", "hashJoinMatches")

#: clause 2.4.3's substitution parameters, as the specification validates them
SEGMENT = "BUILDING"
DATE = datetime.date(1995, 3, 15)


def missing_counters() -> list[str]:
    """The counters of ``REQUIRED_JOIN_COUNTERS`` that the engine in this checkout cannot report."""
    from spark_rapids_tpu.exec import join
    counters = getattr(join, "JOIN_COUNTERS", {})
    return [name for name in REQUIRED_JOIN_COUNTERS if name not in counters]


def _q3(t):
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col, lit
    cutoff = lit(DATE)
    c = t["customer"].filter(col("c_mktsegment") == SEGMENT)
    o = t["orders"].filter(col("o_orderdate") < cutoff)
    li = t["lineitem"].filter(col("l_shipdate") > cutoff)
    joined = (c.join(o, on=([col("c_custkey")], [col("o_custkey")]))
              .join(li, on=([col("o_orderkey")], [col("l_orderkey")])))
    revenue = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (joined.group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(Sum(revenue).alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
            .sort("revenue", "o_orderdate", ascending=[False, True])
            .limit(10))


def make_query(session, tables: dict, qid: str, entry: str):
    """A callable that runs Q3 through the DataFrame entry and returns its rows. Raises, before any query runs,
    where the engine lacks the counters this family's cell is read through."""
    assert qid in QUERIES and entry in ENTRIES, (qid, entry)
    missing = missing_counters()
    if missing:
        raise RuntimeError("the shipping cell reads the hash join's counters, and this engine reports no "
                           + ", ".join(missing) + " (spark_rapids_tpu.exec.join.JOIN_COUNTERS)")
    return lambda: _q3(tables).collect()


def _ref_q3(paths, precision):
    cust = pd.read_parquet(paths["customer"], columns=["c_custkey", "c_mktsegment"])
    orders = pd.read_parquet(paths["orders"], columns=["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
    li = pd.read_parquet(paths["lineitem"], columns=["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"])
    c = cust[cust["c_mktsegment"] == SEGMENT]
    o = orders[orders["o_orderdate"] < DATE]
    li = li[li["l_shipdate"] > DATE]
    j = c.merge(o, left_on="c_custkey", right_on="o_custkey").merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j["revenue"] = lower(lower(j["l_extendedprice"], precision)
                         * lower(1 - lower(j["l_discount"], precision), precision), precision)
    out = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False)["revenue"].sum()
    return out[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]


def reference(qid: str, paths: dict, precision: str | None = None) -> pd.DataFrame:
    """The full answer of clause 2.4.3 over the parquet files, in pandas: nothing of the engine imported.
    ``precision`` lowers inputs and products to the control's arithmetic (``harness/lowprec.py``)."""
    assert qid in QUERIES, qid
    return _ref_q3(paths, precision)
