"""Span tracer: query → stage → task → operator spans.

A minimal Dapper-style tracer over ``time.perf_counter_ns``. Spans
carry a kind (``query``/``stage``/``task``/``operator``), a parent
link, and free-form attributes; a finished tracer exports the whole
tree as Chrome-trace (catapult) JSON — loadable in ``chrome://tracing``
/ Perfetto, and parseable by ``tools/profile_report.py``.

Tracers are created per query by the session (``srt.eventLog.trace.
enabled``) and handed to operators through ``ExecContext.tracer``; the
disabled path is ``ctx.tracer is None`` — no span allocation, no
clock reads beyond what the metrics layer already pays.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

# Resolved once: the timers and the launch path enter a range on every
# operator pull and every program dispatch, so a per-enter import would
# be measurable there. Importing jax.profiler touches no backend.
try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except ImportError:  # pragma: no cover - jax always present in-tree
    _TraceAnnotation = None


_NULL_RANGE = contextlib.nullcontext()


def annotate(name: str):
    """A host range on the profiler's clock (``jax.profiler.
    TraceAnnotation``): it lands in the same ``.xplane.pb`` as the
    device's operations, on the calling thread's line, so a device idle
    gap can be laid against what the host was doing. With no trace
    running it costs one context manager and a ``TraceMe`` level check;
    where jax has no profiler it is a null context. The names in use are
    listed in docs/OBSERVABILITY.md ("Host ranges and query phases")."""
    if _TraceAnnotation is None:
        return _NULL_RANGE
    return _TraceAnnotation(name)


class Span:
    """One finished (or in-flight) span. Timestamps are monotonic
    ``perf_counter_ns`` values, so durations are exact and spans from
    one process share a timeline; wall-clock anchoring lives in the
    tracer's anchor pair (exported as trace metadata), not per span."""

    __slots__ = ("name", "kind", "span_id", "parent_id", "t0_ns",
                 "t1_ns", "attrs", "tid")

    def __init__(self, name: str, kind: str, span_id: int,
                 parent_id: Optional[int], t0_ns: int,
                 attrs: Optional[dict], tid: int):
        self.name = name
        self.kind = kind
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_ns = t0_ns
        self.t1_ns: Optional[int] = None
        self.attrs = attrs
        self.tid = tid

    @property
    def duration_ns(self) -> int:
        return 0 if self.t1_ns is None else self.t1_ns - self.t0_ns

    def __repr__(self):
        return (f"Span({self.kind}:{self.name} id={self.span_id} "
                f"parent={self.parent_id} dur={self.duration_ns}ns)")


class _SpanScope:
    """Context manager returned by :meth:`Tracer.span`."""

    __slots__ = ("tracer", "span")

    def __init__(self, tracer: "Tracer", span: Span):
        self.tracer = tracer
        self.span = span

    def __enter__(self) -> Span:
        self.tracer._push(self.span)
        return self.span

    def __exit__(self, *exc):
        try:
            self.tracer._pop(self.span)
        finally:
            self.tracer.end(self.span)
        return False


class Tracer:
    """Thread-safe span collector. One per traced query.

    Two usage styles:
    - ``with tracer.span("q", kind="query"): ...`` — pushes onto a
      thread-local stack so nested spans parent automatically;
    - ``s = tracer.begin(name, kind, parent=...); ...; tracer.end(s)``
      — explicit parentage for callers that already maintain their own
      stack (the exec layer's exclusive-time timer stack).

    Cross-process: the driver ships ``tracer.context()`` with each
    cluster job; a worker rebuilds a child tracer from it with
    :meth:`from_context`, so worker spans (a) share the driver's
    ``trace_id``, (b) default-parent under the driver's job span
    (``_remote_parent``), and (c) allocate span ids in a
    pid-namespaced range that cannot collide with other processes.
    Every tracer stamps a monotonic↔wall-clock anchor pair at
    construction; :func:`merge_chrome_traces` uses the anchors to
    clock-align per-process trace files onto one timeline.
    """

    def __init__(self, trace_id: Optional[str] = None,
                 remote_parent: Optional[int] = None):
        self.trace_id = trace_id or os.urandom(8).hex()
        self._remote_parent = remote_parent
        # paired clock reads: anchor_unix_s is the wall-clock time at
        # monotonic instant anchor_mono_ns (per-process alignment key)
        self.anchor_mono_ns = time.perf_counter_ns()
        self.anchor_unix_s = time.time()
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        # span ids are namespaced by pid so ids minted on different
        # processes of one trace never collide when merged
        self._id_base = (os.getpid() & 0x3FFFFF) << 32
        self._next_id = 1
        self._tls = threading.local()

    # --- cross-process context ---
    def context(self, span: Optional[Span] = None) -> dict:
        """Serializable trace context to ship with a remote job: the
        given span (or the calling thread's innermost open scope)
        becomes the remote side's default parent."""
        sid = span.span_id if span is not None else self.current_id()
        return {"trace_id": self.trace_id, "span_id": sid,
                "pid": os.getpid()}

    @classmethod
    def from_context(cls, ctx: Optional[dict]) -> "Tracer":
        """Child tracer parented under a remote span context."""
        if not ctx:
            return cls()
        return cls(trace_id=ctx.get("trace_id"),
                   remote_parent=ctx.get("span_id"))

    # --- explicit API ---
    def begin(self, name: str, kind: str = "span",
              parent: Optional[int] = None,
              attrs: Optional[dict] = None) -> Span:
        """Start a span. ``parent=None`` links to the calling thread's
        innermost open ``span()`` scope (the query span, usually), or
        to the remote parent on a worker-side tracer."""
        if parent is None:
            stack = getattr(self._tls, "stack", None)
            if stack:
                parent = stack[-1].span_id
            else:
                parent = self._remote_parent
        with self._lock:
            sid = self._id_base + self._next_id
            self._next_id += 1
        return Span(name, kind, sid, parent, time.perf_counter_ns(),
                    attrs, threading.get_ident())

    def end(self, span: Span) -> None:
        span.t1_ns = time.perf_counter_ns()
        with self._lock:
            self._spans.append(span)

    # --- scoped API ---
    def span(self, name: str, kind: str = "span",
             parent: Optional[int] = None,
             attrs: Optional[dict] = None) -> _SpanScope:
        return _SpanScope(self, self.begin(name, kind, parent, attrs))

    def _push(self, span: Span) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(span)

    def _pop(self, span: Span) -> None:
        stack = getattr(self._tls, "stack", None)
        if stack and stack[-1] is span:
            stack.pop()
        elif stack and span in stack:  # exception-skewed exit order
            stack.remove(span)

    def current_id(self) -> Optional[int]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1].span_id if stack else None

    def instant(self, name: str, attrs: Optional[dict] = None) -> None:
        """Zero-duration marker (Chrome-trace ``ph: i``)."""
        s = self.begin(name, kind="instant", attrs=attrs)
        s.t1_ns = s.t0_ns
        with self._lock:
            self._spans.append(s)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    # --- export ---
    def export_chrome_trace(self) -> str:
        """Chrome-trace (catapult) JSON object format. Every event
        carries the required ``ph``/``ts``/``pid`` fields; ``ts`` is
        microseconds (float) on the monotonic timeline."""
        pid = os.getpid()
        events: List[dict] = []
        for s in self.spans():
            args: Dict = {"span_id": s.span_id}
            if s.parent_id is not None:
                args["parent_id"] = s.parent_id
            if s.attrs:
                args.update(s.attrs)
            if s.kind == "instant":
                events.append({"name": s.name, "cat": s.kind, "ph": "i",
                               "ts": s.t0_ns / 1e3, "pid": pid,
                               "tid": s.tid, "s": "t", "args": args})
                continue
            events.append({"name": s.name, "cat": s.kind, "ph": "X",
                           "ts": s.t0_ns / 1e3,
                           "dur": (s.t1_ns or s.t0_ns) / 1e3
                                  - s.t0_ns / 1e3,
                           "pid": pid, "tid": s.tid, "args": args})
        return json.dumps({"traceEvents": events,
                           "displayTimeUnit": "ms",
                           "metadata": {
                               "trace_id": self.trace_id,
                               "pid": pid,
                               "anchor_mono_ns": self.anchor_mono_ns,
                               "anchor_unix_s": self.anchor_unix_s,
                               "remote_parent": self._remote_parent,
                           }})

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.export_chrome_trace())
        return path


def merge_chrome_traces(paths) -> dict:
    """Clock-align and merge per-process Chrome-trace files into one.

    Each file's events sit on that process's private monotonic
    timeline; its metadata anchor pair (``anchor_mono_ns`` at wall
    clock ``anchor_unix_s``) converts them to a shared wall-clock
    timeline: ``ts_wall_us = ts_us + anchor_unix_s*1e6 -
    anchor_mono_ns/1e3``. Events keep their originating ``pid`` so the
    merged view shows one lane per process. Returns the merged
    catapult object (``traceEvents`` sorted by aligned ts)."""
    events: List[dict] = []
    sources: List[dict] = []
    trace_ids = set()
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        meta = doc.get("metadata") or {}
        if meta.get("trace_id"):
            trace_ids.add(meta["trace_id"])
        offset_us = 0.0
        if "anchor_mono_ns" in meta and "anchor_unix_s" in meta:
            offset_us = (meta["anchor_unix_s"] * 1e6
                         - meta["anchor_mono_ns"] / 1e3)
        sources.append({"path": os.path.basename(str(path)),
                        "pid": meta.get("pid"),
                        "offset_us": offset_us,
                        "trace_id": meta.get("trace_id")})
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = ev["ts"] + offset_us
            events.append(ev)
    events.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"trace_id": (sorted(trace_ids)[0]
                                      if len(trace_ids) == 1 else
                                      sorted(trace_ids)),
                         "sources": sources}}


def maybe_tracer(conf) -> Optional[Tracer]:
    """A fresh per-query tracer when ``srt.eventLog.trace.enabled`` is
    on, else None (the zero-overhead disabled path)."""
    from ..conf import TRACE_ENABLED
    if not conf.get(TRACE_ENABLED):
        return None
    return Tracer()
