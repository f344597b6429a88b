"""TPC-H pricing family: Q1, the Pricing Summary Report, as the deployment ``tpch_sf1_pricing`` runs it.

The DataFrame builder and the plain pandas reference are the tpch family's own (``configs/tpch.py``: ``_q1``,
``_ref_q1``), imported and not copied: one query, one reference. What is this family's own is ``make_query``'s
check, after ``configs/tpcds.py``'s: the cell's per-layer metrics read the grouped aggregate's counters
(``spark_rapids_tpu.exec.aggregate.LANE_COUNTERS`` and the ``phases`` they are summed into, with the masked
filter's ``aggMaskedFilterBatches``), and an engine that has none of them has no business in the cell — it is
also the engine that compacts seven columns and hash-claims 2^20 rows a batch for six groups, ten seconds a
query and three queries a window (PERF.md, PR 31), so it fails here, at once.
"""

from __future__ import annotations

import pandas as pd

from benchmarks.configs import tpch

ENTRIES = tpch.ENTRIES
QUERIES = ("q1",)

#: the grouped aggregate's counters this cell's metrics read (docs/OBSERVABILITY.md, "Grouped aggregates")
REQUIRED_LANE_COUNTERS = ("pallasBatches", "groupsResolvedDirect", "groupsHashClaimed")
REQUIRED_PHASE_METRICS = ("aggMaskedFilterBatches",)


def missing_counters() -> list[str]:
    """The counters of the two lists above that the engine in this checkout cannot report."""
    from spark_rapids_tpu.exec import aggregate
    from spark_rapids_tpu.plan import session
    LANE_COUNTERS = getattr(aggregate, "LANE_COUNTERS", {})
    _PHASE_METRICS = getattr(session, "_PHASE_METRICS", {})
    return ([name for name in REQUIRED_LANE_COUNTERS if name not in LANE_COUNTERS]
            + [name for name in REQUIRED_PHASE_METRICS if name not in _PHASE_METRICS])


def make_query(session, tables: dict, qid: str, entry: str):
    """A callable that runs Q1 through the DataFrame entry and returns its rows. Raises, before any query runs,
    where the engine lacks the counters this family's cell is read through."""
    assert qid in QUERIES, qid
    missing = missing_counters()
    if missing:
        raise RuntimeError("the pricing cell reads the grouped aggregate's counters, and this engine reports no "
                           + ", ".join(missing) + " (spark_rapids_tpu.exec.aggregate.LANE_COUNTERS)")
    return tpch.make_query(session, tables, qid, entry)


def reference(qid: str, paths: dict, precision: str | None = None) -> pd.DataFrame:
    assert qid in QUERIES, qid
    return tpch.reference(qid, paths, precision)
