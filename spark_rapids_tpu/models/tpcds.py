"""TPC-DS-shaped queries (operator breadth).

A representative slice of the NDS suite's operator shapes over
star-schema data (store_sales fact + date_dim/item/customer dims):

  q3   brand revenue for one manufacturer by year (3-way join,
       grouped sum, sort)
  q42  category revenue for one month (dim filters on both sides)
  q55  brand revenue for one (moy, manager) slice
  q68r running/windowed variant: rank categories by revenue inside
       each year (join + aggregate + window), the double-aggregation
       shape q67-family queries use

Each returns a DataFrame over the provided tables; tests check them
differentially against the CPU oracle (tests/test_models.py pattern).
"""

from __future__ import annotations

import os
from typing import Dict

from ..columnar import dtypes as dt
from ..datagen import ColumnSpec, TableSpec, generate_table
from ..expr.aggregates import Sum
from ..expr.core import Alias, col
from ..expr.window import Rank, Window


def store_sales_spec(scale_rows: int) -> TableSpec:
    return TableSpec("store_sales", [
        ColumnSpec("ss_sold_date_sk", dt.INT64, "uniform", lo=1,
                   hi=730),
        ColumnSpec("ss_item_sk", dt.INT64, "uniform", lo=1, hi=2000),
        ColumnSpec("ss_customer_sk", dt.INT64, "zipf",
                   cardinality=5000),
        ColumnSpec("ss_quantity", dt.INT64, "uniform", lo=1, hi=100),
        ColumnSpec("ss_ext_sales_price", dt.FLOAT64, "uniform",
                   lo=1.0, hi=500.0),
        ColumnSpec("ss_net_profit", dt.FLOAT64, "normal", mean=20.0,
                   std=40.0),
    ], scale_rows)


def date_dim_spec() -> TableSpec:
    return TableSpec("date_dim", [
        ColumnSpec("d_date_sk", dt.INT64, "seq"),
        ColumnSpec("d_year", dt.INT64, "choice", choices=[1998, 1999]),
        ColumnSpec("d_moy", dt.INT64, "uniform", lo=1, hi=13),
    ], 730)


def item_spec() -> TableSpec:
    return TableSpec("item", [
        ColumnSpec("i_item_sk", dt.INT64, "seq"),
        ColumnSpec("i_brand_id", dt.INT64, "uniform", lo=1, hi=50),
        ColumnSpec("i_brand", dt.STRING, "uniform", lo=1, hi=50,
                   fmt="brand#{}"),
        ColumnSpec("i_manufact_id", dt.INT64, "uniform", lo=1, hi=20),
        ColumnSpec("i_manager_id", dt.INT64, "uniform", lo=1, hi=10),
        ColumnSpec("i_category", dt.STRING, "choice",
                   choices=["Books", "Electronics", "Home", "Music",
                            "Sports"]),
    ], 2000)


def tpcds_tables(session, data_dir: str,
                 scale_rows: int = 100_000,
                 chunk_rows: int = 1 << 18) -> Dict[str, object]:
    """Generate (once) and open the star-schema subset."""
    tables = {}
    for spec in (store_sales_spec(scale_rows), date_dim_spec(),
                 item_spec()):
        out = os.path.join(data_dir, spec.name)
        if not os.path.isdir(out) or not os.listdir(out):
            generate_table(None, spec, out, chunk_rows=chunk_rows)
        tables[spec.name] = session.read.parquet(out)
    return tables


def _on(l, r):
    return ([col(l)], [col(r)])


def q3(store_sales, date_dim, item, manufact_id: int = 7):
    """Brand revenue by year for one manufacturer (TPC-DS q3 shape)."""
    return (store_sales
            .join(date_dim.filter(col("d_moy") == 11),
                  _on("ss_sold_date_sk", "d_date_sk"))
            .join(item.filter(col("i_manufact_id") == manufact_id),
                  _on("ss_item_sk", "i_item_sk"))
            .group_by("d_year", "i_brand_id", "i_brand")
            .agg(Alias(Sum(col("ss_ext_sales_price")), "sum_agg"))
            .sort("d_year", "i_brand_id"))


def q42(store_sales, date_dim, item, year: int = 1998):
    """Category revenue for one month (TPC-DS q42 shape)."""
    return (store_sales
            .join(date_dim.filter((col("d_moy") == 12) &
                                  (col("d_year") == year)),
                  _on("ss_sold_date_sk", "d_date_sk"))
            .join(item, _on("ss_item_sk", "i_item_sk"))
            .group_by("d_year", "i_category")
            .agg(Alias(Sum(col("ss_ext_sales_price")), "revenue"))
            .sort("i_category"))


def q55(store_sales, date_dim, item, manager_id: int = 4):
    """Brand revenue for one (moy, manager) slice (TPC-DS q55 shape)."""
    return (store_sales
            .join(date_dim.filter((col("d_moy") == 11) &
                                  (col("d_year") == 1999)),
                  _on("ss_sold_date_sk", "d_date_sk"))
            .join(item.filter(col("i_manager_id") == manager_id),
                  _on("ss_item_sk", "i_item_sk"))
            .group_by("i_brand_id", "i_brand")
            .agg(Alias(Sum(col("ss_ext_sales_price")), "ext_price"))
            .sort("i_brand_id"))


def q68r(store_sales, date_dim, item):
    """Rank categories by revenue within each year — the aggregate-
    then-window double pass the q67 family uses."""
    from ..plan.logical import SortField
    agg = (store_sales
           .join(date_dim, _on("ss_sold_date_sk", "d_date_sk"))
           .join(item, _on("ss_item_sk", "i_item_sk"))
           .group_by("d_year", "i_category")
           .agg(Alias(Sum(col("ss_ext_sales_price")), "revenue")))
    w = Window.partition_by("d_year").order_by(
        SortField(col("revenue"), ascending=False))
    return agg.select("d_year", "i_category", "revenue",
                      Rank().over(w).alias("rk")).sort("d_year", "rk")
