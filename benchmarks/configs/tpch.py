"""TPC-H family: DataFrame builders for the queries a cell may name, each with its plain
pandas reference over the same parquet files. The builders are the benchmark's own copies
of ``spark_rapids_tpu/models/tpch.py``: later PRs may change that file, never the yardstick.

Every reference returns the FULL answer (no LIMIT) as a pandas frame whose columns carry the
query's output names; ``harness/compare.py`` applies the order and the cut. ``precision``
lowers the reference to the control's arithmetic ("bfloat16" | "float32"): inputs and
products rounded to it, the adding still exact. That is the gentlest form the lower precision
can take, and a steady one: rounding a finished sum as well adds an error that can cancel
the others by chance (a one-row answer then read 6.8e-6 on one seed of three, 1.2e-3 on the others).
"""

from __future__ import annotations

import datetime

import numpy as np
import pandas as pd

from benchmarks.harness.lowprec import lower

ENTRIES = ("dataframe",)


def _q6(t):
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col, lit
    return (t["lineitem"]
            .filter((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
                    & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
                    & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
                    & (col("l_quantity") < 24.0))
            .agg(Sum(col("l_extendedprice") * col("l_discount")).alias("revenue")))


def _q1(t):
    from spark_rapids_tpu.expr.aggregates import Average, CountStar, Sum
    from spark_rapids_tpu.expr.core import col, lit
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    charge = disc_price * (lit(1.0) + col("l_tax"))
    return (t["lineitem"]
            .filter(col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
            .group_by("l_returnflag", "l_linestatus")
            .agg(Sum(col("l_quantity")).alias("sum_qty"),
                 Sum(col("l_extendedprice")).alias("sum_base_price"),
                 Sum(disc_price).alias("sum_disc_price"), Sum(charge).alias("sum_charge"),
                 Average(col("l_quantity")).alias("avg_qty"),
                 Average(col("l_extendedprice")).alias("avg_price"),
                 Average(col("l_discount")).alias("avg_disc"), CountStar().alias("count_order"))
            .sort("l_returnflag", "l_linestatus"))


def _q3(t):
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import col, lit
    cutoff = lit(datetime.date(1995, 3, 15))
    c = t["customer"].filter(col("c_mktsegment") == "BUILDING")
    o = t["orders"].filter(col("o_orderdate") < cutoff)
    li = t["lineitem"].filter(col("l_shipdate") > cutoff)
    joined = (c.join(o, on=([col("c_custkey")], [col("o_custkey")]))
              .join(li, on=([col("o_orderkey")], [col("l_orderkey")])))
    revenue = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (joined.group_by("o_orderkey", "o_orderdate").agg(Sum(revenue).alias("revenue"))
            .sort("revenue", ascending=False).limit(10))


BUILDERS = {"q6": _q6, "q1": _q1, "q3": _q3}


def make_query(session, tables: dict, qid: str, entry: str):
    """A callable that runs query ``qid`` through the DataFrame entry and returns its rows."""
    assert entry in ENTRIES, entry
    build = BUILDERS[qid]
    return lambda: build(tables).collect()


def _read(paths, table, columns):
    return pd.read_parquet(paths[table], columns=columns)


def _ref_q6(paths, precision):
    li = _read(paths, "lineitem", ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"])
    lo, hi = datetime.date(1994, 1, 1), datetime.date(1995, 1, 1)
    sel = li[(li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) & (li["l_discount"] >= 0.05)
             & (li["l_discount"] <= 0.07) & (li["l_quantity"] < 24.0)]
    product = lower(lower(sel["l_extendedprice"], precision) * lower(sel["l_discount"], precision), precision)
    return pd.DataFrame({"revenue": [float(np.sum(product.to_numpy(np.float64)))]})


def _ref_q1(paths, precision):
    li = _read(paths, "lineitem", ["l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                                   "l_extendedprice", "l_discount", "l_tax"])
    li = li[li["l_shipdate"] <= datetime.date(1998, 9, 2)].copy()
    for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        li[c] = lower(li[c], precision)
    li["disc_price"] = lower(li["l_extendedprice"] * lower(1 - li["l_discount"], precision), precision)
    li["charge"] = lower(li["disc_price"] * lower(1 + li["l_tax"], precision), precision)
    return li.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"), sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), count_order=("l_quantity", "size")).reset_index()


def _ref_q3(paths, precision):
    cutoff = datetime.date(1995, 3, 15)
    cust = _read(paths, "customer", None)
    orders = _read(paths, "orders", ["o_orderkey", "o_custkey", "o_orderdate"])
    li = _read(paths, "lineitem", ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"])
    c = cust[cust["c_mktsegment"] == "BUILDING"]
    o = orders[orders["o_orderdate"] < cutoff]
    li = li[li["l_shipdate"] > cutoff]
    j = c.merge(o, left_on="c_custkey", right_on="o_custkey").merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j["revenue"] = lower(lower(j["l_extendedprice"], precision)
                         * lower(1 - lower(j["l_discount"], precision), precision), precision)
    return j.groupby(["o_orderkey", "o_orderdate"], as_index=False)["revenue"].sum()


REFERENCES = {"q6": _ref_q6, "q1": _ref_q1, "q3": _ref_q3}


def reference(qid: str, paths: dict, precision: str | None = None) -> pd.DataFrame:
    return REFERENCES[qid](paths, precision)
