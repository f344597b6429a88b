"""The measured window of a closed-loop cell and the end-to-end arithmetic over it.

One stream runs the cell's queries back to back, round robin in the order the traffic file gives,
until ``seconds`` have passed; the window closes when the query then in flight completes, and its
length is that whole span. So a rate is all the window's work over all its time, a stall lowers it,
and no query is cut off or left out.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class QueryRecord:
    qid: str
    start_s: float
    end_s: float
    fact_rows: int
    result: list
    engine: dict = field(default_factory=dict)  # what the engine's own counters said of this query

    @property
    def wall_ms(self) -> float:
        return (self.end_s - self.start_s) * 1e3


@dataclass
class Window:
    open_s: float
    close_s: float
    records: list

    @property
    def seconds(self) -> float:
        return self.close_s - self.open_s


def run_window(queries: list, seconds: float, fact_rows: dict, probe=None, around=None,
               clock=time.perf_counter) -> Window:
    """``queries``: [(qid, callable returning rows)] in traffic order. ``probe()`` reads the engine's
    counters after a query (outside its timed span). ``around(index, qid)`` is a context manager put
    around each query: the traced run's profiler switch and host span."""
    records = []
    open_s = clock()
    index = 0
    while True:
        qid, call = queries[index % len(queries)]
        if around is None:
            start = clock()
            result = call()
            end = clock()
        else:
            with around(index, qid):
                start = clock()
                result = call()
                end = clock()
        record = QueryRecord(qid, start, end, fact_rows[qid], result)
        if probe is not None:
            record.engine = probe()
        records.append(record)
        index += 1
        if end - open_s >= seconds:
            return Window(open_s, end, records)


def end_to_end(window: Window, setup_s: float) -> dict:
    """Every end-to-end metric the harness knows, as ``{name: (value, unit)}``; ``BENCHMARK.json`` says
    which of them a cell reports."""
    walls = np.array([r.wall_ms for r in window.records])
    return {
        "rows_per_s": (sum(r.fact_rows for r in window.records) / window.seconds, "rows/s"),
        "query_p95_ms": (float(np.percentile(walls, 95)), "ms"),
        "setup_s": (setup_s, "s"),
    }


def spread(values: list) -> dict:
    """Smallest, median and largest of a query's walls in the window: what the tail is made of."""
    if not values:  # a window too short to reach this query
        return {"n": 0}
    return {"n": len(values), "min": float(np.min(values)), "median": float(np.median(values)),
            "max": float(np.max(values))}
