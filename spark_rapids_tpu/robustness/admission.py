"""Query admission control and the cancellation/deadline contract.

The reference serializes device access with ``GpuSemaphore`` — a
1000-permit semaphore carved into ``spark.rapids.sql.concurrentGpuTasks``
shares so the config can over/under-subscribe (GpuSemaphore.scala:106).
``exec/base.py``'s ``TpuSemaphore`` already plays that role at *task*
granularity; this module lifts the same idea to *query* granularity for
the serving tier (ROADMAP item 1):

  * ``QuerySemaphore`` — ``srt.sql.concurrentQueryTasks`` queries run;
    up to ``srt.sql.admission.maxQueueDepth`` more wait FIFO with
    exponential backoff + jitter between re-checks; arrivals beyond the
    queue are load-shed with a retryable ``AdmissionRejected`` so an
    overloaded server degrades by refusing work, not by queueing
    unboundedly.
  * ``QueryContext`` — the cancel token threaded through the session,
    operator pull loops, prefetch producers, and transport fetch
    workers. ``cancel()`` and deadlines both funnel into ``check()``,
    which raises the typed ``QueryCancelled`` / ``DeadlineExceeded``
    that the session surfaces (and cluster drivers broadcast).

Admission states (each transition emits a JSONL event):

    submit -> ADMITTED                       (QueryAdmitted)
    submit -> QUEUED -> ADMITTED             (AdmissionQueued, QueryAdmitted)
    submit -> QUEUED -> cancel/deadline      (AdmissionAbandoned)
    submit -> REJECTED (queue full)          (AdmissionRejected)

The thread-local "current query" mirrors ``active_conf``: worker
threads spawned on a query's behalf (prefetch producers, fetch pool
workers) enter ``query_scope(token)`` so deep code — budget slices,
spill victim selection, retry backoff sleeps — can find the owning
query without threading a parameter through every signature.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Optional

from ..conf import (ADMISSION_BACKOFF_BASE_S, ADMISSION_MAX_QUEUE_DEPTH,
                    CONCURRENT_QUERY_TASKS, active_conf)
from ..obs import events as _events
from ..obs.trace import annotate

__all__ = ["AdmissionRejected", "QueryInterrupted", "QueryCancelled",
           "DeadlineExceeded", "QueryContext", "QuerySemaphore",
           "current_query", "set_current_query", "query_scope",
           "query_semaphore", "reset_query_semaphore"]


class AdmissionRejected(RuntimeError):
    """Load-shed: the admission queue is full. Retryable — the query
    did no work and held no resources; resubmit after backoff."""


class QueryInterrupted(RuntimeError):
    """Base for clean query teardown (cancel or deadline). NOT a bug:
    the engine unwinds through every thread and stays serviceable."""


class QueryCancelled(QueryInterrupted):
    """The query's cancel token fired (user abort, driver broadcast)."""


class DeadlineExceeded(QueryInterrupted):
    """srt.sql.queryTimeout / collect(timeout=...) expired."""


class QueryContext:
    """Cancel token + deadline for one query, shared across every
    thread working on its behalf (consumer, prefetch producers, fetch
    pool workers, cluster worker job threads).

    ``check()`` is the single choke point: cheap enough for per-batch
    pull loops (one Event.is_set + one clock read when a deadline is
    armed), and every blocking wait in the engine either polls it or
    waits on ``_cancelled`` directly (``sleep``)."""

    __slots__ = ("query_id", "deadline", "cancel_reason", "_cancelled",
                 "admission_wait_ns", "dispatch_ns", "launches",
                 "semaphore_wait_ns", "_launch_lock")

    def __init__(self, query_id: str = "",
                 deadline: Optional[float] = None):
        self.query_id = query_id
        #: absolute time.monotonic() deadline; None = no deadline
        self.deadline = deadline
        self.cancel_reason = ""
        self._cancelled = threading.Event()
        #: ns spent queued for admission, stamped by
        #: QuerySemaphore.acquire: None = never admitted, 0 = admitted
        #: on the fast path, >0 = waited in the FIFO. The serving tier
        #: reads this to bucket latency per admission tier.
        self.admission_wait_ns: Optional[int] = None
        #: host ns spent dispatching device programs on this query's
        #: behalf, and how many dispatches, summed over its threads
        #: (jit_registry ``launch.*`` ranges; ``phases`` of the record)
        self.dispatch_ns = 0
        self.launches = 0
        #: ns its threads stood blocked for a device permit
        #: (exec/base.py TpuSemaphore, ``semaphore.wait`` ranges)
        self.semaphore_wait_ns = 0
        self._launch_lock = threading.Lock()

    def count_launch(self, ns: int) -> None:
        with self._launch_lock:
            self.dispatch_ns += ns
            self.launches += 1

    def count_semaphore_wait(self, ns: int) -> None:
        with self._launch_lock:
            self.semaphore_wait_ns += ns

    @property
    def admission_tier(self) -> str:
        """'immediate' | 'queued' | 'unadmitted' — which admission
        path this query took (serving-tier latency bucketing)."""
        w = self.admission_wait_ns
        if w is None:
            return "unadmitted"
        return "queued" if w > 0 else "immediate"

    def set_timeout(self, seconds: Optional[float]) -> None:
        if seconds is not None and seconds > 0:
            self.deadline = time.monotonic() + float(seconds)

    def cancel(self, reason: str = "cancelled") -> None:
        if not self._cancelled.is_set():
            self.cancel_reason = reason
            self._cancelled.set()

    def is_cancelled(self) -> bool:
        return self._cancelled.is_set()

    def expired(self) -> bool:
        return self.deadline is not None and \
            time.monotonic() > self.deadline

    def remaining(self) -> Optional[float]:
        if self.deadline is None:
            return None
        return self.deadline - time.monotonic()

    def check(self) -> None:
        """Raise the typed teardown error if this query should stop."""
        if self._cancelled.is_set():
            raise QueryCancelled(
                f"query {self.query_id or '?'} cancelled"
                + (f": {self.cancel_reason}" if self.cancel_reason
                   else ""))
        if self.expired():
            raise DeadlineExceeded(
                f"query {self.query_id or '?'} exceeded its deadline")

    def sleep(self, seconds: float) -> None:
        """Cancel-aware sleep: wake early on cancel() and never sleep
        past the deadline; raises via check() if either fired."""
        t = seconds
        r = self.remaining()
        if r is not None:
            t = min(t, max(r, 0.0))
        if t > 0:
            self._cancelled.wait(t)
        self.check()


# --- thread-local current query (mirrors conf.set_active_conf) -------------
_TL = threading.local()


def current_query() -> Optional[QueryContext]:
    return getattr(_TL, "query", None)


def set_current_query(q: Optional[QueryContext]) -> None:
    _TL.query = q


class query_scope:
    """Bind ``token`` as this thread's current query for the duration;
    restores the previous binding on exit (nested queries, reused pool
    threads)."""

    def __init__(self, token: Optional[QueryContext]):
        self._token = token
        self._prev: Optional[QueryContext] = None

    def __enter__(self) -> Optional[QueryContext]:
        self._prev = current_query()
        set_current_query(self._token)
        return self._token

    def __exit__(self, *exc) -> bool:
        set_current_query(self._prev)
        return False


def check_current_query() -> None:
    """Convenience for deep call sites: check the thread's current
    query token, if any. Zero-cost shape when no query is bound."""
    q = current_query()
    if q is not None:
        q.check()


class QuerySemaphore:
    """Bounded query admission (GpuSemaphore at query granularity).

    Like the reference's 1000-permit pool split ``concurrentGpuTasks``
    ways, ``TOTAL_PERMITS`` is carved into ``permits`` equal shares so
    a future weighted-admission tier (big queries take several shares)
    slots in without changing the protocol. Re-entrant per thread, like
    ``TpuSemaphore``: a nested ``session.execute`` on an admitted
    thread (cache materialization, explain(metrics=True)) must not
    deadlock behind itself.
    """

    TOTAL_PERMITS = 1000

    def __init__(self, permits: int, max_queue_depth: int = 16,
                 backoff_base_s: float = 0.05):
        self.permits = max(int(permits), 1)
        self.share = self.TOTAL_PERMITS // self.permits
        self.max_queue_depth = max(int(max_queue_depth), 0)
        self.backoff_base_s = float(backoff_base_s)
        self._cv = threading.Condition()
        self._active = 0
        self._queue: deque = deque()  # FIFO tickets (opaque objects)
        self._holders = {}  # tid -> depth (re-entrancy)
        # counters for tests/chaos: lifetime admitted/queued/rejected
        self.admitted = 0
        self.queued = 0
        self.rejected = 0

    # --- introspection ---
    def active(self) -> int:
        with self._cv:
            return self._active

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def acquire(self, token: Optional[QueryContext] = None) -> None:
        """Admit one query, waiting FIFO if the running set is full.

        Raises ``AdmissionRejected`` when the wait queue is at
        capacity, and ``QueryCancelled`` / ``DeadlineExceeded`` if the
        token fires while queued (the query never ran; it abandons its
        queue slot)."""
        tid = threading.get_ident()
        qid = token.query_id if token is not None else ""
        with self._cv:
            if self._holders.get(tid, 0) > 0:
                self._holders[tid] += 1
                return
            if self._active < self.permits and not self._queue:
                self._active += 1
                self._holders[tid] = 1
                self.admitted += 1
                if token is not None:
                    token.admission_wait_ns = 0
                _events.emit("QueryAdmitted", query_id=qid,
                             active=self._active, queued_ns=0)
                return
            if len(self._queue) >= self.max_queue_depth:
                self.rejected += 1
                _events.emit("AdmissionRejected", query_id=qid,
                             queue_depth=len(self._queue))
                raise AdmissionRejected(
                    f"admission queue full "
                    f"({len(self._queue)}/{self.max_queue_depth} "
                    f"queued, {self._active} running); retry later")
            ticket = object()
            self._queue.append(ticket)
            self.queued += 1
            _events.emit("AdmissionQueued", query_id=qid,
                         queue_depth=len(self._queue))
            t0 = time.perf_counter_ns()
            attempt = 0
            try:
                with annotate("admission.wait"):
                    while not (self._queue[0] is ticket
                               and self._active < self.permits):
                        if token is not None:
                            token.check()  # cancel/deadline while queued
                        # backoff + jitter bounds how stale a deadline
                        # check can get; release() notifies so an open
                        # slot is claimed immediately, not at backoff
                        attempt += 1
                        backoff = (self.backoff_base_s
                                   * min(2 ** (attempt - 1), 64)
                                   * (1.0 + random.random() * 0.25))
                        self._cv.wait(timeout=backoff)
                self._queue.popleft()
                self._active += 1
                self._holders[tid] = 1
                self.admitted += 1
                wait_ns = time.perf_counter_ns() - t0
                if token is not None:
                    token.admission_wait_ns = wait_ns
                _events.emit("QueryAdmitted", query_id=qid,
                             active=self._active, queued_ns=wait_ns)
            except BaseException:
                try:
                    self._queue.remove(ticket)
                except ValueError:
                    pass
                _events.emit("AdmissionAbandoned", query_id=qid)
                self._cv.notify_all()
                raise

    def release(self) -> None:
        tid = threading.get_ident()
        with self._cv:
            n = self._holders.get(tid, 0)
            if n == 0:
                return
            if n > 1:
                self._holders[tid] = n - 1
                return
            del self._holders[tid]
            self._active = max(0, self._active - 1)
            self._cv.notify_all()

    def __enter__(self) -> "QuerySemaphore":
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False


_QUERY_SEM: Optional[QuerySemaphore] = None
_QS_LOCK = threading.Lock()


def query_semaphore(conf=None) -> QuerySemaphore:
    """Process-wide admission semaphore, sized from config on first
    use (device_semaphore idiom — one pool per device pool)."""
    global _QUERY_SEM
    with _QS_LOCK:
        if _QUERY_SEM is None:
            c = conf or active_conf()
            _QUERY_SEM = QuerySemaphore(
                c.get(CONCURRENT_QUERY_TASKS),
                max_queue_depth=c.get(ADMISSION_MAX_QUEUE_DEPTH),
                backoff_base_s=c.get(ADMISSION_BACKOFF_BASE_S))
        return _QUERY_SEM


def reset_query_semaphore(conf=None) -> QuerySemaphore:
    """Test hook: drop the singleton (resized from conf on next use,
    or immediately when a conf is given)."""
    global _QUERY_SEM
    with _QS_LOCK:
        _QUERY_SEM = None
    return query_semaphore(conf) if conf is not None else None
