"""The comparison that decides ``correct``: the rows a timed query returned against the plain
reference's full answer.

Exact for row counts, keys, ints, strings and dates; floats by relative error against the
reference. ORDER BY and LIMIT are judged with the reference's values, and two rows whose float
sort keys agree within the query's limit may stand in either order or on either side of the cut:
engines break such ties differently, and that is not a wrong answer.
"""

from __future__ import annotations

import datetime
import math

import numpy as np
import pandas as pd

from benchmarks.harness.lowprec import RANK


def plain(value):
    """One cell as a plain Python value (None for NULL / NaN-as-missing)."""
    if value is None or value is pd.NaT:
        return None
    if isinstance(value, (np.integer,)):
        return int(value)
    if isinstance(value, (float, np.floating)):
        return None if math.isnan(value) else float(value)
    if isinstance(value, pd.Timestamp):
        return value.date()
    if isinstance(value, (np.str_,)):
        return str(value)
    if isinstance(value, datetime.datetime):
        return value.date()
    return value


def rel_err(got: float, want: float) -> float:
    if got == want:
        return 0.0
    return abs(got - want) / max(abs(want), 1e-300)


def _order(a: dict, b: dict, order_by: list, tie: float) -> int:
    """-1 / 0 / 1 as reference row ``a`` sorts before / with / after ``b``. NULLs first ascending,
    last descending (Spark). Two floats that differ, but by no more than ``tie``, leave the order open:
    the engine's own sums may fall either way, and the later columns then decide nothing."""
    for column, direction in order_by:
        x, y = a[column], b[column]
        if x is None or y is None:
            c = 0 if x is y else (-1 if x is None else 1)
        elif isinstance(x, float) or isinstance(y, float):
            if x != y and rel_err(float(x), float(y)) <= tie:
                return 0
            c = (x > y) - (x < y)
        else:
            c = (x > y) - (x < y)
        if c:
            return c if direction == "asc" else -c
    return 0


class Comparison:
    """What one run compared: counts of exact faults and the widest float error per precision."""

    def __init__(self):
        self.rows = 0
        self.wrong = 0
        self.lower_lane = 0
        self.max_rel_err: dict[str, float] = {}
        self.notes: list[str] = []
        self._reference_rows: dict = {}  # id(reference frame) -> its rows by key

    def fault(self, note: str) -> None:
        self.wrong += 1
        if len(self.notes) < 20:
            self.notes.append(note)

    def add(self, label: str, rows: list[dict], ref: pd.DataFrame, spec: dict, float_limit: float,
            lane: str | None = None) -> None:
        """``rows``: what the query returned. ``ref``: the reference's full answer. ``spec``: the
        configuration's entry for the query (keys, order_by, limit, and the ``precision`` it states).
        ``float_limit``: that precision's limit, which also says when two float sort keys tie. ``lane``:
        the precision of the lane the program says it answered in; a lower one than stated is a departure
        from the configuration, whatever the floats read."""
        keys, order_by, limit = spec.get("keys", []), spec.get("order_by", []), spec.get("limit")
        precision = spec["precision"]
        self.max_rel_err.setdefault(precision, 0.0)
        if lane is not None and RANK[lane] < RANK[precision]:
            self.lower_lane += 1
            if len(self.notes) < 20:
                self.notes.append(f"{label}: answered in the {lane} lane, the configuration states {precision}")
        columns = list(ref.columns)
        ref_rows = self._reference_rows.get(id(ref))
        if ref_rows is None:
            ref_rows = self._reference_rows[id(ref)] = {}
            for record in ref.to_dict("records"):
                record = {c: plain(v) for c, v in record.items()}
                ref_rows[tuple(record[k] for k in keys)] = record
        expected = min(limit, len(ref_rows)) if limit else len(ref_rows)
        if len(rows) != expected:
            self.fault(f"{label}: {len(rows)} rows, the reference has {expected}")
        seen, matched = set(), []
        for i, row in enumerate(rows):
            self.rows += 1
            row = {c: plain(v) for c, v in row.items()}
            if list(row) != columns:
                self.fault(f"{label} row {i}: columns {list(row)}, the reference has {columns}")
                continue
            key = tuple(row[k] for k in keys)
            want = ref_rows.get(key)
            if want is None or key in seen:
                self.fault(f"{label} row {i}: key {key} " + ("twice" if key in seen else "not in the reference"))
                continue
            seen.add(key)
            matched.append(want)
            for c in columns:
                a, b = row[c], want[c]
                if isinstance(b, float) and isinstance(a, (int, float)) and not isinstance(a, bool):
                    err = rel_err(float(a), b)
                    self.max_rel_err[precision] = max(self.max_rel_err[precision], err)
                elif a != b:
                    self.fault(f"{label} row {i} {c}: {a!r}, the reference has {b!r}")
        tie = 2 * float_limit
        for i in range(1, len(matched)):
            if order_by and _order(matched[i - 1], matched[i], order_by, tie) > 0:
                self.fault(f"{label}: rows {i - 1} and {i} are out of order")
        if limit and matched and len(ref_rows) > limit:
            for key, record in ref_rows.items():
                if key not in seen and _order(record, matched[-1], order_by, tie) < 0:
                    self.fault(f"{label}: group {key} belongs inside the first {limit} and is missing")

    def numbers(self, limits: dict[str, float]) -> dict:
        """Each number compared beside its limit: ``{name: {"value": v, "limit": l}}``."""
        out = {"rows_compared": {"value": self.rows, "limit": None},
               "wrong_rows": {"value": self.wrong, "limit": 0},
               "lower_lane_answers": {"value": self.lower_lane, "limit": 0}}
        for precision, err in sorted(self.max_rel_err.items()):
            out[f"max_rel_err_{precision}"] = {"value": err, "limit": limits[precision]}
        return out

    def correct(self, limits: dict[str, float]) -> bool:
        return (self.rows > 0 and self.wrong == 0 and self.lower_lane == 0
                and all(err <= limits[p] for p, err in self.max_rel_err.items()))
