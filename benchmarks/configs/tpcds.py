"""TPC-DS family: the star-join reporting queries (3, 42, 52) as SQL text through ``session.sql()``, each with
its plain pandas reference over the same parquet files. A reference returns the FULL grouped answer (no
LIMIT); ``harness/compare.py`` applies ORDER BY and the cut, tolerant of ties. ``precision`` lowers it to the
control's arithmetic: the measure rounded, the adding exact.

The text and the reference follow ``configs/nds.py``'s (the parked ``nds_*`` files), copied so that this
family stands when those go, with one repair: query 42 has the source template's ``i_manager_id = 1``, which
the parked text had dropped (about 490 fact rows pass its joins, not 49,000). What is this family's own is
``make_query``'s check: the cell's per-layer metrics read
the join execs' counters (``spark_rapids_tpu.exec.join.JOIN_COUNTERS`` and the ``phases`` they are summed
into), and an engine that has none of them has no business in the cell — it is also the engine that sits in
the compiler for a quarter of an hour at this scale (PERF.md, PR 23), so it fails here, at once.
"""

from __future__ import annotations

import pandas as pd

from benchmarks.harness.lowprec import lower

ENTRIES = ("sql",)

#: the join execs' counters this cell's metrics read (docs/OBSERVABILITY.md, "Joins")
REQUIRED_JOIN_COUNTERS = ("joinReadbacks", "lookupJoinBatches", "hashJoinBatches", "joinOutCapacity",
                          "joinCapacityRelaunches", "joinBuildTime")

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
        WHERE i_manager_id = 1 AND d_moy = 12 AND d_year = 1998
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
    "q42": ({"d_moy": 12, "d_year": 1998}, {"i_manager_id": 1},
            {"d_year": "d_year", "i_category_id": "i_category_id", "i_category": "i_category"}, "total_sales"),
    "q52": ({"d_moy": 11, "d_year": 1999}, {"i_manager_id": 1},
            {"d_year": "d_year", "i_brand_id": "brand_id", "i_brand": "brand"}, "ext_price"),
}


def missing_join_counters() -> list[str]:
    """The counters of ``REQUIRED_JOIN_COUNTERS`` that the engine in this checkout cannot report."""
    try:
        from spark_rapids_tpu.exec.join import JOIN_COUNTERS
    except ImportError:
        return list(REQUIRED_JOIN_COUNTERS)
    return [name for name in REQUIRED_JOIN_COUNTERS if name not in JOIN_COUNTERS]


def make_query(session, tables: dict, qid: str, entry: str):
    """A callable that parses the SQL text anew each time, runs it and returns its rows. Raises, before any
    query runs, where the engine lacks the counters this family's cells are read through."""
    assert entry in ENTRIES, entry
    missing = missing_join_counters()
    if missing:
        raise RuntimeError("the tpcds cells read the join execs' counters, and this engine reports no "
                           + ", ".join(missing) + " (spark_rapids_tpu.exec.join.JOIN_COUNTERS)")
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
