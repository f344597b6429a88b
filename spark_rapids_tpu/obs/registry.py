"""Metrics registry: per-query summaries + Prometheus-style snapshot.

The exec layer already accumulates leveled ``Metric``s per operator
(``ExecContext.metrics: {exec_id: {name: Metric}}``); this module
aggregates them the way the reference accelerator's SQL UI does —
filtered by ``srt.metrics.level`` (ESSENTIAL < MODERATE < DEBUG),
rolled up per query, and kept in a bounded process-wide registry that
the benchmark's readers and tests can snapshot or export as Prometheus
text.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional

LEVEL_ORDER = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}

#: quantiles reported for every histogram (summaries + Prometheus)
QUANTILES = (0.50, 0.90, 0.99)


def _escape_label(v: Any) -> str:
    """Prometheus text-format label-value escaping: backslash, double
    quote, and newline must be escaped inside the quotes."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


class Histogram:
    """Bounded log-bucketed histogram over non-negative integers.

    Bucket ``i`` holds values whose ``bit_length()`` is ``i`` — i.e.
    ``{0}`` for bucket 0 and ``[2^(i-1), 2^i - 1]`` for ``i >= 1`` —
    so at most ~65 buckets cover the full 64-bit range and the counts
    list grows lazily to the highest bucket actually hit. Quantile
    estimates take the containing bucket's upper bound clamped to the
    observed min/max, which is tight enough for p50/p90/p99 skew
    detection without per-value storage."""

    __slots__ = ("name", "unit", "_counts", "_count", "_sum",
                 "_min", "_max", "_lock")

    def __init__(self, name: str, unit: str = ""):
        self.name = name
        self.unit = unit
        self._counts: List[int] = []   # lazily grown, index = bit_length
        self._count = 0
        self._sum = 0
        self._min: Optional[int] = None
        self._max: Optional[int] = None
        self._lock = threading.Lock()

    def observe(self, value) -> None:
        v = int(value)
        if v < 0:
            v = 0
        i = v.bit_length()
        with self._lock:
            if i >= len(self._counts):
                self._counts.extend([0] * (i + 1 - len(self._counts)))
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> int:
        return self._sum

    def buckets(self) -> List[tuple]:
        """``[(le, cumulative_count), ...]`` with le the inclusive
        upper bound of each allocated bucket — already cumulative, as
        Prometheus histogram buckets require."""
        with self._lock:
            counts = list(self._counts)
        out: List[tuple] = []
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            le = 0 if i == 0 else (1 << i) - 1
            out.append((le, cum))
        return out

    def quantile(self, q: float) -> int:
        """Estimated q-quantile (0 < q <= 1)."""
        with self._lock:
            if self._count == 0:
                return 0
            rank = q * self._count
            cum = 0
            for i, c in enumerate(self._counts):
                cum += c
                if cum >= rank and c:
                    le = 0 if i == 0 else (1 << i) - 1
                    hi = min(le, self._max)
                    return max(hi, self._min)
            return self._max or 0

    def percentiles(self) -> Dict[str, int]:
        return {f"p{int(q * 100)}": self.quantile(q) for q in QUANTILES}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            count, total = self._count, self._sum
            mn, mx = self._min, self._max
        d: Dict[str, Any] = {"count": count, "sum": total,
                             "min": mn or 0, "max": mx or 0}
        if self.unit:
            d["unit"] = self.unit
        d.update(self.percentiles())
        return d


def level_allows(conf_level: str, metric_level: str) -> bool:
    """True when a metric at ``metric_level`` should be reported under
    the configured ``conf_level`` (ESSENTIAL shows the least)."""
    want = LEVEL_ORDER.get(str(conf_level).upper(), 1)
    have = LEVEL_ORDER.get(str(metric_level).upper(), 1)
    return have <= want


def summarize_metrics(ctx_metrics: Dict[str, Dict[str, Any]],
                      level: str = "MODERATE") -> Dict[str, Dict[str, dict]]:
    """Flatten ``{exec_id: {name: Metric}}`` into plain dicts, keeping
    only metrics at or below the configured level."""
    out: Dict[str, Dict[str, dict]] = {}
    for exec_id, metrics in ctx_metrics.items():
        kept: Dict[str, dict] = {}
        for name, m in metrics.items():
            m_level = getattr(m, "level", "MODERATE")
            if not level_allows(level, m_level):
                continue
            kept[name] = {"value": getattr(m, "value", m),
                          "level": m_level,
                          "unit": getattr(m, "unit", "")}
        if kept:
            out[str(exec_id)] = kept
    return out


def query_totals(summary: Dict[str, Dict[str, dict]]) -> Dict[str, Any]:
    """Cross-operator totals for the headline numbers."""
    totals: Dict[str, Any] = {"opTimeNs": 0, "numOutputRows": 0,
                              "numOutputBatches": 0, "spilledBytes": 0,
                              "shuffleBytesWritten": 0}
    for metrics in summary.values():
        for name, rec in metrics.items():
            v = rec.get("value", 0)
            if not isinstance(v, (int, float)):
                continue
            if name == "opTime":
                totals["opTimeNs"] += v
            elif name in totals:
                totals[name] += v
    return totals


class MetricsRegistry:
    """Bounded process-wide record of completed queries plus running
    totals. Cheap enough to leave always-on: recording happens once
    per query, never per batch."""

    def __init__(self, max_queries: int = 64, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._queries: deque = deque(maxlen=max_queries)
        self._hists: Dict[str, Histogram] = {}
        self._counters: Dict[str, float] = {
            "queries_total": 0,
            "queries_failed_total": 0,
            "op_time_ns_total": 0,
            "output_rows_total": 0,
            "output_batches_total": 0,
            "wall_time_ns_total": 0,
        }

    def observe(self, name: str, value, unit: str = "") -> None:
        """Record one sample into the named histogram (created on
        first use). A disabled registry drops the sample without
        allocating anything."""
        if not self.enabled:
            return
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name, Histogram(name, unit))
        h.observe(value)

    def histogram(self, name: str) -> Optional[Histogram]:
        with self._lock:
            return self._hists.get(name)

    def histograms(self) -> Dict[str, Histogram]:
        with self._lock:
            return dict(self._hists)

    def record_query(self, query_id: str,
                     summary: Dict[str, Dict[str, dict]],
                     wall_ns: int = 0, status: str = "ok",
                     **extra: Any) -> Dict[str, Any]:
        totals = query_totals(summary)
        rec = {"query_id": query_id, "status": status,
               "wall_ns": wall_ns, "totals": totals,
               "operators": summary}
        rec.update(extra)
        with self._lock:
            hists = dict(self._hists)
        if hists:
            rec["quantiles"] = {n: h.snapshot() for n, h in hists.items()}
        with self._lock:
            self._queries.append(rec)
            self._counters["queries_total"] += 1
            if status != "ok":
                self._counters["queries_failed_total"] += 1
            self._counters["op_time_ns_total"] += totals["opTimeNs"]
            self._counters["output_rows_total"] += totals["numOutputRows"]
            self._counters["output_batches_total"] += \
                totals["numOutputBatches"]
            self._counters["wall_time_ns_total"] += wall_ns
        return rec

    def queries(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._queries)

    def last_query(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._queries[-1] if self._queries else None

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            hists = dict(self._hists)
            out = {"counters": dict(self._counters),
                   "queries": list(self._queries)}
        if hists:
            out["histograms"] = {n: h.snapshot()
                                 for n, h in hists.items()}
        return out

    def prometheus_text(self) -> str:
        """Prometheus text exposition format: running counters,
        histograms (cumulative buckets, _sum/_count, and p50/p90/p99
        quantile gauges), and per-operator op-time of the most recent
        query. A disabled registry exposes nothing."""
        if not self.enabled:
            return ""
        lines: List[str] = []
        with self._lock:
            counters = dict(self._counters)
            hists = dict(self._hists)
            last = self._queries[-1] if self._queries else None
        for name, value in sorted(counters.items()):
            metric = f"srt_{name}"
            lines.append(f"# TYPE {metric} counter")
            lines.append(f"{metric} {value:g}")
        for name in sorted(hists):
            h = hists[name]
            metric = f"srt_{name}"
            lines.append(f"# TYPE {metric} histogram")
            for le, cum in h.buckets():
                lines.append(f'{metric}_bucket{{le="{le}"}} {cum}')
            lines.append(f'{metric}_bucket{{le="+Inf"}} {h.count}')
            lines.append(f"{metric}_sum {h.sum}")
            lines.append(f"{metric}_count {h.count}")
            lines.append(f"# TYPE {metric}_quantile gauge")
            for q in QUANTILES:
                lines.append(
                    f'{metric}_quantile{{quantile="{q:g}"}} '
                    f'{h.quantile(q)}')
        if last is not None:
            metric = "srt_last_query_op_time_ns"
            lines.append(f"# TYPE {metric} gauge")
            for exec_id, metrics in sorted(last["operators"].items()):
                rec = metrics.get("opTime")
                if rec is None:
                    continue
                lines.append(
                    f'{metric}{{exec_id="{_escape_label(exec_id)}"}} '
                    f'{rec.get("value", 0):g}')
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._queries.clear()
            self._hists.clear()
            for k in self._counters:
                self._counters[k] = 0


_REGISTRY: Optional[MetricsRegistry] = None
_REG_LOCK = threading.Lock()


def registry() -> MetricsRegistry:
    global _REGISTRY
    with _REG_LOCK:
        if _REGISTRY is None:
            _REGISTRY = MetricsRegistry()
        return _REGISTRY


def reset_registry() -> None:
    global _REGISTRY
    with _REG_LOCK:
        _REGISTRY = None


def observe(name: str, value, unit: str = "") -> None:
    """Module-level shortcut for histogram observation sites
    (task times, shuffle block sizes, fetch latencies...)."""
    registry().observe(name, value, unit)
