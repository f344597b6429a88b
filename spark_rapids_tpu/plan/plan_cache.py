"""Physical-plan cache: structural keys for logical plans.

Every ``collect()`` used to re-run apply_overrides and build fresh exec
instances, so each exec's ``jax.jit`` wrappers were new objects and the
in-memory pjit cache never carried across collects — a warm TPC-H query
spent more wall-clock re-tracing jaxprs than computing (the persistent
XLA compile cache only removes the *compile*, not the trace). The
reference has no analogue because Spark caches compiled RDD DAGs per
Dataset; here the session memoizes ``logical plan -> physical plan`` on
a STRUCTURAL key so re-built-but-identical DataFrames (bench loops, SQL
re-parses) reuse the exec tree and its traced jits.

Key rules (conservative by construction):
- encodes node/expression class names + full ``__dict__`` contents
  recursively; children positionally,
- file scans fold in (path, mtime, size) per file so data edits
  invalidate,
- ANY value the encoder does not recognize raises Uncachable and the
  query simply runs uncached (never a wrong reuse: unknown values can
  not silently alias),
- re-execution of a cached tree calls ``reset_for_rerun`` on every exec
  so one-shot state (shuffle writes, broadcast materialization) is
  rebuilt.
"""

from __future__ import annotations

import datetime
import decimal
import os

from ..columnar import dtypes as dt


class Uncachable(Exception):
    """Plan contains state the structural key cannot encode safely."""


_PRIMS = (str, int, float, bool, bytes, type(None), complex,
          datetime.date, datetime.datetime, datetime.timedelta,
          decimal.Decimal)

_MAX_ITEMS = 4096  # bail on huge embedded literals (LocalRelation data)


def _enc(v, depth: int = 0):
    if depth > 64:
        raise Uncachable("nesting too deep")
    if isinstance(v, _PRIMS):
        return (type(v).__name__, repr(v))
    if isinstance(v, dt.DType):
        return ("dtype", type(v).__name__,
                tuple(sorted((k, _enc(x, depth + 1))
                             for k, x in vars(v).items())))
    if isinstance(v, (list, tuple)):
        if len(v) > _MAX_ITEMS:
            raise Uncachable("sequence too large")
        return (type(v).__name__,) + tuple(_enc(x, depth + 1) for x in v)
    if isinstance(v, dict):
        if len(v) > _MAX_ITEMS:
            raise Uncachable("dict too large")
        return ("dict",) + tuple(
            sorted((_enc(k, depth + 1), _enc(x, depth + 1))
                   for k, x in v.items()))
    if isinstance(v, (set, frozenset)):
        if len(v) > _MAX_ITEMS:
            raise Uncachable("set too large")
        return ("set",) + tuple(sorted(_enc(x, depth + 1) for x in v))
    from ..exec.sort import SortOrder
    from ..expr.core import Expression
    from ..expr.window import WindowFrame, WindowSpec
    from .logical import LogicalPlan, SortField
    if isinstance(v, (LogicalPlan, Expression, SortField, SortOrder,
                      WindowSpec, WindowFrame)):
        return _enc_node(v, depth + 1)
    raise Uncachable(f"unencodable {type(v).__name__}")


def _enc_node(node, depth: int):
    from .logical import LogicalPlan
    items = []
    for k, val in sorted(vars(node).items()):
        if k == "children":
            continue
        items.append((k, _enc(val, depth)))
    key = (type(node).__module__, type(node).__name__, tuple(items),
           tuple(_enc(c, depth) for c in getattr(node, "children", ())))
    if isinstance(node, LogicalPlan) and hasattr(node, "paths"):
        # file scan: fold file identity in so on-disk edits invalidate
        stats = []
        for p in node.paths:
            try:
                st = os.stat(p)
                stats.append((p, int(st.st_mtime_ns), st.st_size))
            except OSError:
                raise Uncachable("unstatable scan path")
        key = key + (tuple(stats),)
    return key


def plan_cache_key(plan, conf):
    """Hashable structural key for (logical plan, conf), or None when
    the plan is not safely cachable."""
    try:
        conf_key = tuple(sorted(
            (k, _enc(v)) for k, v in conf._settings.items()))
        return (_enc(plan), conf_key)
    except Uncachable:
        return None
    except Exception:
        return None


#: trees one key keeps: twice srt.sql.concurrentQueryTasks' default, so
#: every admitted query that sends one text finds a tree of its own
_MAX_TREES_PER_KEY = 8


class PhysicalPlanCache:
    """Small FIFO memo of structural key -> physical plans.

    Cached exec trees hold one-shot execution state (shuffle ids,
    write flags, metrics), so a tree may be EXECUTING on at most one
    thread at a time. Serial callers reuse via ``reset_for_rerun``;
    concurrent callers (the serving front door runs many sessions over
    one shared cache) take an execution *lease* on a tree. A key holds
    up to ``_MAX_TREES_PER_KEY`` trees: a caller that finds every one of
    them leased plans a fresh tree, which joins them, so N streams that
    send one text plan it N times in all and not once a collision
    (planning a star query is tens of milliseconds of Python)."""

    def __init__(self, max_entries: int = 32):
        import threading
        self.max_entries = max_entries
        #: key -> [(physical, lease lock)], newest last
        self._entries: dict = {}
        self._mu = threading.Lock()
        # lifetime counters, reported as hit rates by the serving
        # bench (tools/serve_bench.py) alongside the jit-registry's
        self.hits = 0
        self.misses = 0
        self.busy_bypasses = 0

    def lease(self, key):
        """(physical, release_fn) with the execution lease held, or
        (None, None). A key whose trees are all busy — mid-execution on
        other threads — counts as a miss (the caller plans another)."""
        with self._mu:
            instances = self._entries.get(key)
            if not instances:
                self.misses += 1
                return None, None
            for physical, lock in instances:
                if lock.acquire(blocking=False):
                    self.hits += 1
                    return physical, lock.release
            self.misses += 1
            self.busy_bypasses += 1
            return None, None

    def stats(self) -> dict:
        with self._mu:
            return {"hits": self.hits, "misses": self.misses,
                    "busy_bypasses": self.busy_bypasses,
                    "entries": len(self._entries)}

    def put_leased(self, key, physical):
        """Insert with the execution lease pre-acquired: the builder
        is about to execute the very instance it cached, so no other
        thread may lease it until that run releases."""
        import threading
        lock = threading.Lock()
        lock.acquire()
        with self._mu:
            instances = self._entries.get(key)
            if instances is None:
                if len(self._entries) >= self.max_entries:
                    self._entries.pop(next(iter(self._entries)))
                instances = self._entries[key] = []
            if len(instances) >= _MAX_TREES_PER_KEY:
                instances.pop(0)  # still leased where it runs; then dropped
            instances.append((physical, lock))
        return lock.release

    def clear(self) -> None:
        with self._mu:
            self._entries.clear()
