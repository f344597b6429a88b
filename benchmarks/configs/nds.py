"""NDS (TPC-DS derived) family: the star-join queries as SQL text, the benchmark's own copies of
``spark_rapids_tpu/models/nds.py::NDS_QUERIES``, each with its plain pandas reference over the
same parquet files. A reference returns the FULL grouped answer (no LIMIT);
``harness/compare.py`` applies ORDER BY and the cut, tolerant of ties. ``precision`` lowers it
to the control's arithmetic: the measure rounded, the adding exact.
"""

from __future__ import annotations

import pandas as pd

from benchmarks.harness.lowprec import lower

ENTRIES = ("sql",)

SQL = {
    "q3": """
        SELECT d_year, i_brand_id AS brand_id, i_brand AS brand,
               SUM(ss_ext_sales_price) AS sum_agg
        FROM store_sales
        JOIN date_dim ON ss_sold_date_sk = d_date_sk
        JOIN item ON ss_item_sk = i_item_sk
        WHERE i_manufact_id = 7 AND d_moy = 11
        GROUP BY d_year, i_brand_id, i_brand
        ORDER BY d_year, sum_agg DESC, brand_id
        LIMIT 100""",
    "q42": """
        SELECT d_year, i_category_id, i_category,
               SUM(ss_ext_sales_price) AS total_sales
        FROM date_dim
        JOIN store_sales ON d_date_sk = ss_sold_date_sk
        JOIN item ON ss_item_sk = i_item_sk
        WHERE d_moy = 12 AND d_year = 1998
        GROUP BY d_year, i_category_id, i_category
        ORDER BY total_sales DESC, d_year, i_category_id, i_category
        LIMIT 100""",
    "q52": """
        SELECT d_year, i_brand_id AS brand_id, i_brand AS brand,
               SUM(ss_ext_sales_price) AS ext_price
        FROM date_dim
        JOIN store_sales ON d_date_sk = ss_sold_date_sk
        JOIN item ON ss_item_sk = i_item_sk
        WHERE i_manager_id = 1 AND d_moy = 11 AND d_year = 1999
        GROUP BY d_year, i_brand_id, i_brand
        ORDER BY d_year, ext_price DESC, brand_id
        LIMIT 100""",
}

#: per query: date_dim filter, item filter, group keys (source column -> output name), sum's output name
_STAR = {
    "q3": ({"d_moy": 11}, {"i_manufact_id": 7},
           {"d_year": "d_year", "i_brand_id": "brand_id", "i_brand": "brand"}, "sum_agg"),
    "q42": ({"d_moy": 12, "d_year": 1998}, {},
            {"d_year": "d_year", "i_category_id": "i_category_id", "i_category": "i_category"}, "total_sales"),
    "q52": ({"d_moy": 11, "d_year": 1999}, {"i_manager_id": 1},
            {"d_year": "d_year", "i_brand_id": "brand_id", "i_brand": "brand"}, "ext_price"),
}


def make_query(session, tables: dict, qid: str, entry: str):
    """A callable that parses the SQL text anew each time, runs it and returns its rows."""
    assert entry in ENTRIES, entry
    text = SQL[qid]
    return lambda: session.sql(text).collect()


def _filtered(frame: pd.DataFrame, equals: dict) -> pd.DataFrame:
    for column, value in equals.items():
        frame = frame[frame[column] == value]
    return frame


def reference(qid: str, paths: dict, precision: str | None = None) -> pd.DataFrame:
    date_filter, item_filter, keys, total = _STAR[qid]
    dates = _filtered(pd.read_parquet(paths["date_dim"], columns=["d_date_sk", "d_year", "d_moy"]), date_filter)
    item_columns = sorted({"i_item_sk", *item_filter, *(k for k in keys if k.startswith("i_"))})
    items = _filtered(pd.read_parquet(paths["item"], columns=item_columns), item_filter)
    sales = pd.read_parquet(paths["store_sales"], columns=["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"])
    # an inner join never matches a NULL key
    sales = sales.dropna(subset=["ss_sold_date_sk", "ss_item_sk"])
    sales = sales.astype({"ss_sold_date_sk": "int64", "ss_item_sk": "int64"})
    joined = sales.merge(dates, left_on="ss_sold_date_sk", right_on="d_date_sk") \
                  .merge(items, left_on="ss_item_sk", right_on="i_item_sk")
    joined[total] = lower(joined["ss_ext_sales_price"], precision)
    # SUM skips NULLs, and is NULL for a group that has nothing else
    out = joined.groupby(list(keys), as_index=False)[total].sum(min_count=1)
    return out.rename(columns=keys)
