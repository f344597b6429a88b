"""Asynchronous pipelined execution: overlap I/O with device compute.

The engine is a pull-based iterator chain, so every scan decode,
shuffle fetch, checksum verify, and host->device transfer stalls the
consumer (and therefore the TPU) for its full duration. The reference
plugin hides these latencies with a multithreaded reader and the
RapidsShuffleIterator's fetch-ahead window; this module is the common
primitive behind both: ``PrefetchIterator`` runs a producer iterator on
a background thread behind a bounded queue with byte-budget
backpressure, and ``prefetch_batches`` specializes it for
ColumnarBatch streams — every in-flight batch registers with the spill
catalog as ACTIVE_ON_DECK so memory pressure can reclaim it, and with
``srt.exec.pipeline.depth`` >= 2 the producer's upload of batch N+1
overlaps the consumer's compute on batch N (double buffering; JAX's
async dispatch makes the device transfer itself non-blocking on the
producer).

Insertion points (see plan/overrides.py ``_insert_pipeline``):
  * ``PrefetchExec`` wraps ``FileSourceScanExec`` output — decode
    overlaps compute,
  * the read side of ``ShuffleExchangeExec`` wraps each reduce
    partition's block stream — fetch/verify/deserialize overlap reduce
    compute,
  * ``BroadcastExchangeExec.materialize`` drains its child through a
    prefetcher while concat-staging runs on the consumer.

When a producer starts: at its consumer's first pull, or, under a
broadcast hash join, when the join starts (``TpuExec.start_sources``,
``sources_started``): a join drains its build side before it pulls its
probe side once, so producers that start at the first pull start one
after another down a chain of joins, each behind the scan, filter and
table build of the one before. Started with the join they run side by
side; the producer waits on the execution's context
(``ExecContext.early_sources``) for the node's own execution to
continue it, and the join that started it closes it if nothing does.

Correctness contract:
  * items arrive in producer order (single producer, FIFO deque);
  * a producer-side exception is re-raised on the CONSUMING thread —
    the original exception object, after all items produced before it
    have been drained — so ``FetchFailed`` / ``DataCorruption`` /
    injected faults surface at the same plan node and with the same
    type as in synchronous mode, and stage-retry / whole-job-retry
    isinstance checks keep firing;
  * the producer thread inherits the query conf (``set_active_conf``)
    and, when a fault plan is armed, the wrapping operator's fault
    scope, so ``~op=`` site matches behave as if the work ran inline;
  * ``close()`` is idempotent, joins the producer, and discards (via
    ``on_discard``) anything still queued, so an abandoned consumer
    (LocalLimit, error unwind) leaks neither threads nor spill-catalog
    registrations.

Producer threads are kept (``_ProducerPool``): an iterator borrows one
for its run and hands it back, and an iterator that names an
``affinity`` gets the thread that served that affinity last. The reason
is the host allocator, not the cost of starting a thread: a scan makes
and frees a few hundred MB of host buffers, glibc ties a thread to one
of its arenas when the thread first allocates, and in a process whose
runtime threads already hold every arena a NEW thread is dealt the
next arena of the ring, so a scan run on a fresh thread each query
walks its buffers through a hundred arenas (cold memory every time,
a quarter slower end to end) while the same scan on the thread it had
before finds its heap where it left it. ``RunAhead`` borrows from the
same pool: a multi-file scan's decode threads are kept threads too, by
the table and their index among its workers.

The SelfTimer disjointness invariant (obs: exclusive op-times on one
thread never overlap) holds because each thread pulls through its own
timer stack (ExecContext.timer_stack is thread-local): producer-side
operators attribute their op-time on the producer's stack, the
``PrefetchExec`` / exchange frames attribute only wait time on the
consumer's. tools/profile_report.py folds the two by treating
sum(op-time) > wall as pipeline overlap, not double-charging.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from collections import deque
from typing import Callable, Iterable, Iterator, Optional

from ..conf import (PIPELINE_DEPTH, PIPELINE_ENABLED, PIPELINE_MAX_BYTES,
                    SrtConf, set_active_conf)
from ..obs.trace import annotate
from .base import ExecContext, Metric, Schema, TpuExec

__all__ = ["PrefetchIterator", "PrefetchExec", "RunAhead",
           "prefetch_batches", "start_early", "sources_started",
           "pipeline_enabled", "prefetch_buffer_bytes",
           "prefetch_thread_leaks", "close_live_iterators"]

# Live iterators, for the resource sampler's prefetch-occupancy gauge.
# Weak so an abandoned iterator never outlives its consumer.
_LIVE: "weakref.WeakSet[PrefetchIterator]" = weakref.WeakSet()
_LIVE_LOCK = threading.Lock()

#: producer threads that outlived close()'s join timeout — a stuck
#: source (hung socket, wedged decode). Chaos runs and the leak gate
#: fail loudly on a nonzero count instead of silently shipping a
#: daemon thread per wedged query.
_THREAD_LEAKS = [0]


def prefetch_thread_leaks() -> int:
    return _THREAD_LEAKS[0]


def _await_parked(parked: threading.Event, thread_name: str,
                  join_timeout: float, queued: int,
                  leak_metric: Optional[Metric] = None) -> None:
    """Wait until a borrowed producer has left its job and parked. One
    that outlives the timeout is wedged inside its source (hung socket,
    stuck decode): it leaks as a daemon thread (the pool starts another
    when it needs one), and that must fail loudly, not silently: a
    warning event, the process-wide ``prefetch_thread_leaks`` counter
    and the node's ``prefetchThreadLeaks`` metric all record it, so
    chaos runs and the serving tier's health checks trip."""
    if parked.wait(timeout=join_timeout):
        return
    _THREAD_LEAKS[0] += 1
    if leak_metric is not None:
        leak_metric.add(1)
    from ..obs import events as _events
    _events.emit("PrefetchThreadLeak", thread=thread_name,
                 join_timeout_s=join_timeout, queued=queued)
    import logging
    logging.getLogger("spark_rapids_tpu.exec").warning(
        "prefetch producer %s leaked: still in its source %.0fs "
        "after close()", thread_name, join_timeout)


#: a parked producer's thread name; while it runs an iterator it is
#: ``srt-prefetch-<name>``, so a thread under that name after close()
#: is a producer still inside its source
_PARKED = "srt-producer-parked"
#: parked producers kept per process; the least recently used goes first
_MAX_PARKED = 64


class _Producer(threading.Thread):
    """One kept producer thread: runs the job it is handed, parks."""

    def __init__(self, pool: "_ProducerPool"):
        super().__init__(name=_PARKED, daemon=True)
        self._pool = pool
        self._cv = threading.Condition()
        self._job = None
        self.affinity: Optional[str] = None

    def hand(self, job) -> None:
        """``job``: (run, thread name, parked event), or None to end."""
        with self._cv:
            self._job = (job,)
            self._cv.notify()

    def run(self) -> None:
        while True:
            with self._cv:
                while self._job is None:
                    self._cv.wait()
                (job,) = self._job
                self._job = None
            if job is None:
                return
            run, name, parked = job
            self.name = name
            try:
                run()
            except BaseException:  # noqa: BLE001
                # PrefetchIterator._run relays its errors to the consumer;
                # whatever else a job raises must not end a thread the pool
                # still lists
                pass
            finally:
                # this thread's view of conf and query is the job's
                from ..robustness.admission import set_current_query
                set_active_conf(None)
                set_current_query(None)
                self.name = _PARKED
                self._pool.park(self)
                parked.set()


class _ProducerPool:
    """Parked producer threads, by the affinity they served last."""

    def __init__(self):
        self._lock = threading.Lock()
        #: parked producers, least recently parked first
        self._parked: "deque[_Producer]" = deque()

    def start(self, run: Callable[[], None], name: str,
              affinity: str) -> threading.Event:
        """Run ``run`` on the parked thread that served ``affinity``
        last, else on a new one (on the longest parked, once the pool
        is full). Returns the event set once the thread is parked
        again."""
        with self._lock:
            worker = None
            for w in reversed(self._parked):
                if w.affinity == affinity:
                    worker = w
                    break
            if worker is not None:
                self._parked.remove(worker)
            elif len(self._parked) >= _MAX_PARKED:
                worker = self._parked.popleft()
        if worker is None:
            worker = _Producer(self)
            worker.start()
        worker.affinity = affinity
        parked = threading.Event()
        worker.hand((run, name, parked))
        return parked

    def park(self, worker: _Producer) -> None:
        with self._lock:
            self._parked.append(worker)
            extra = [self._parked.popleft()
                     for _ in range(len(self._parked) - _MAX_PARKED)]
        for w in extra:
            w.hand(None)


_PRODUCERS = _ProducerPool()


class _ReaderBounds:
    """What the reader pool holds over the whole process, under the one
    condition every ``RunAhead`` waits on: ``live`` reader threads
    inside a task, ``bytes`` admitted and not yet given back. A stream
    admits and starts against these, first come: the reference's
    ``multiThreadedRead.numThreads`` is one pool an executor, shared by
    its tasks, not one a scan."""

    def __init__(self):
        self.cv = threading.Condition()
        self.live = 0
        self.bytes = 0


_READERS = _ReaderBounds()


def prefetch_buffer_bytes() -> int:
    """Total bytes queued across all live prefetchers in this process
    (obs/resource.py sampler probe; racy reads are fine for a gauge)."""
    with _LIVE_LOCK:
        its = list(_LIVE)
    return sum(it._bytes for it in its)


def close_live_iterators(query=None, join_timeout: float = 10.0) -> int:
    """Close every live PrefetchIterator owned by ``query`` (a
    QueryContext, or a query-id string; None closes all).

    The serving tier's per-session teardown calls this after a client
    disconnect: a consumer abandoned mid-stream never reaches the
    iterator's normal close, and without this the producer thread
    would count as a leak once its queue backpressure wedged. Returns
    the number of iterators closed."""
    qid = getattr(query, "query_id", query)
    with _LIVE_LOCK:
        its = list(_LIVE)
    closed = 0
    for it in its:
        owner = it._query
        if qid is not None and (owner is None or owner.query_id != qid):
            continue
        it.close(join_timeout=join_timeout)
        closed += 1
    return closed


class PrefetchIterator:
    """Run ``source_factory()`` on a background thread; consume here.

    The factory (not a live iterator) crosses the thread boundary so
    the source generator is CREATED on the producer thread — generator
    bodies that capture thread-local state at first-next (conf, fault
    scopes, task context) see the producer's, which this class sets up
    to mirror the consumer's.

    Backpressure: the producer blocks while ``depth`` items are queued
    or queued bytes would exceed ``max_bytes``; an oversized single
    item is admitted only into an EMPTY queue (progress guarantee, the
    ByteBudget convention). ``nbytes`` sizes items; None = count-only.
    """

    def __init__(self, source_factory: Callable[[], Iterable],
                 depth: int = 2,
                 max_bytes: int = 0,
                 nbytes: Optional[Callable] = None,
                 conf: Optional[SrtConf] = None,
                 fault_tag: str = "",
                 on_discard: Optional[Callable] = None,
                 name: str = "prefetch",
                 wait_metric: Optional[Metric] = None,
                 depth_peak_metric: Optional[Metric] = None,
                 bytes_peak_metric: Optional[Metric] = None,
                 tracer=None,
                 parent_span_id: Optional[int] = None,
                 query=None,
                 leak_metric: Optional[Metric] = None,
                 affinity: Optional[str] = None):
        self._factory = source_factory
        #: cancellation token (robustness/admission.py QueryContext):
        #: the producer observes it between items and while blocked on
        #: backpressure, the consumer while blocked on an empty queue —
        #: a cancelled query drains and joins instead of wedging
        self._query = query
        self._leak_metric = leak_metric
        self._depth = max(int(depth), 1)
        self._max_bytes = max(int(max_bytes), 0)
        self._nbytes = nbytes
        self._conf = conf
        self._fault_tag = fault_tag
        self._on_discard = on_discard
        self._wait_metric = wait_metric
        self._depth_peak_metric = depth_peak_metric
        self._bytes_peak_metric = bytes_peak_metric
        self._name = name
        # span parenting across the thread boundary: the producer
        # thread's tracer stack starts empty, so without an explicit
        # parent captured at construction (on the CONSUMER thread,
        # where the enclosing operator span is live) every
        # producer-side span would orphan
        self._tracer = tracer
        self._parent_span_id = parent_span_id
        self._cv = threading.Condition()
        self._buf: deque = deque()  # (item, nbytes)
        self._bytes = 0
        self._depth_peak = 0
        self._bytes_peak = 0
        self._done = False
        self._stopped = False
        self._error: Optional[BaseException] = None
        self._closed = False
        self._thread_name = f"srt-prefetch-{name}"
        with _LIVE_LOCK:
            _LIVE.add(self)
        #: set once the producer has left the source and parked again
        self._parked = _PRODUCERS.start(self._run, self._thread_name,
                                        affinity or name)

    # --- producer side ---------------------------------------------------
    def _run(self) -> None:
        from ..robustness import faults
        from ..robustness.admission import set_current_query
        if self._conf is not None:
            set_active_conf(self._conf)
        # producer thread inherits the query identity the same way it
        # inherits the conf: spillable registrations it creates carry
        # the owning query's budget-slice tag, and retry/backoff sleeps
        # deep in the source (transport) become cancel-aware
        set_current_query(self._query)
        scope = (faults.op_scope(self._fault_tag)
                 if self._fault_tag and faults.armed() else None)
        # scoped producer span: pushed onto THIS thread's tracer stack,
        # so operator spans opened by the source (SelfTimer falls back
        # to tracer.current_id()) parent here instead of orphaning
        span_scope = (self._tracer.span(f"prefetch-{self._name}",
                                        kind="producer",
                                        parent=self._parent_span_id)
                      if self._tracer is not None else None)
        src = None
        try:
            if span_scope is not None:
                span_scope.__enter__()
            if scope is not None:
                scope.__enter__()
            try:
                src = iter(self._factory())
                for item in src:
                    if self._query is not None and (
                            self._query.is_cancelled()
                            or self._query.expired()):
                        # observe-and-drain: no error relay — the
                        # consumer raises the typed teardown itself
                        self._discard(item)
                        break
                    n = int(self._nbytes(item)) if self._nbytes else 0
                    if not self._admit(item, n):
                        break
            finally:
                if scope is not None:
                    scope.__exit__(None, None, None)
                if span_scope is not None:
                    span_scope.__exit__(None, None, None)
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            with self._cv:
                self._error = e
                self._cv.notify_all()
        finally:
            # tear the source down on ITS OWN thread (generator finally
            # blocks may release locks/sockets owned by this thread)
            if src is not None and hasattr(src, "close"):
                try:
                    src.close()
                except Exception:
                    pass
            with self._cv:
                self._done = True
                self._cv.notify_all()

    def _admit(self, item, n: int) -> bool:
        """Queue one item, honoring depth + byte backpressure. False =
        stopped: the item was discarded and the producer should quit."""
        with self._cv:
            while not self._stopped and self._buf and (
                    len(self._buf) >= self._depth
                    or (self._max_bytes
                        and self._bytes + n > self._max_bytes)):
                if self._query is not None:
                    if self._query.is_cancelled() or \
                            self._query.expired():
                        self._discard(item)
                        return False
                    # bounded wait so a cancel with a wedged consumer
                    # still unblocks the producer
                    self._cv.wait(timeout=0.25)
                else:
                    self._cv.wait()
            if self._stopped:
                self._discard(item)
                return False
            self._buf.append((item, n))
            self._bytes += n
            if len(self._buf) > self._depth_peak:
                self._depth_peak = len(self._buf)
            if self._bytes > self._bytes_peak:
                self._bytes_peak = self._bytes
            self._cv.notify_all()
            return True

    def _discard(self, item) -> None:
        if self._on_discard is not None:
            try:
                self._on_discard(item)
            except Exception:
                pass

    # --- consumer side ---------------------------------------------------
    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        with self._cv:
            waited = 0
            while True:
                if self._buf:
                    item, n = self._buf.popleft()
                    self._bytes -= n
                    self._cv.notify_all()
                    if waited:
                        if self._wait_metric is not None:
                            self._wait_metric.add(waited)
                        if self._query is not None:
                            self._query.count_scan_wait(waited)
                    return item
                # buffered items drain before an error surfaces: the
                # consumer sees exactly the prefix the producer emitted
                # before failing, same as synchronous execution
                if self._error is not None:
                    err = self._error
                    self._stopped = True
                    self._cv.notify_all()
                    self._flush_peaks()
                    raise err
                if self._done:
                    # a producer that DRAINED on cancel/deadline looks
                    # exactly like clean end-of-stream — re-check the
                    # token before reporting exhaustion, or the query
                    # would return a silently truncated prefix
                    if self._query is not None:
                        self._query.check()
                    self._flush_peaks()
                    raise StopIteration
                t0 = time.perf_counter_ns()
                # the consumer stands blocked on the producer: the same
                # interval prefetchWaitTime counts, on the profiler's
                # clock (one range per wake-up)
                with annotate("prefetch.wait"):
                    if self._query is not None:
                        # typed teardown even when the producer is
                        # wedged in a hung source: poll the token
                        # while waiting
                        self._query.check()
                        self._cv.wait(timeout=0.25)
                    else:
                        self._cv.wait()
                waited += time.perf_counter_ns() - t0

    def _flush_peaks(self) -> None:
        # peaks fold across partitions sharing one metrics dict: keep
        # the query-wide max (single consuming thread, no set() race)
        if self._depth_peak_metric is not None:
            self._depth_peak_metric.set(
                max(self._depth_peak_metric.value, self._depth_peak))
        if self._bytes_peak_metric is not None:
            self._bytes_peak_metric.set(
                max(self._bytes_peak_metric.value, self._bytes_peak))

    def close(self, join_timeout: float = 30.0) -> None:
        """Stop the producer, wait until it has left the source and
        parked (a wedged one is counted as a leak: ``_await_parked``),
        and discard queued items."""
        if self._closed:
            return
        self._closed = True
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        _await_parked(self._parked, self._thread_name, join_timeout,
                      len(self._buf), self._leak_metric)
        with self._cv:
            while self._buf:
                item, _ = self._buf.popleft()
                self._discard(item)
            self._bytes = 0
            self._flush_peaks()

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class RunAhead:
    """Ordered results of ``tasks``, computed ahead of the consumer on
    kept threads.

    ``tasks`` is a list of ``(cost, make)``: ``make()`` returns a
    generator, ``cost`` is an estimate of the bytes its items will hold.
    Up to ``threads`` producers borrowed from the pool drain tasks into
    lists, in submission order, while the cost of what is queued, running
    or done and not yet taken stays within ``max_bytes``. Both bounds
    are the process's (``_ReaderBounds``): a producer starts a task only
    while fewer than ``threads`` reader threads are inside one, this
    stream's or another's, and a stream that already holds something
    starts on its next run of tasks (below; a task alone is a run) only
    while all live streams together stay within ``max_bytes``. What a
    stream's consumer can come to wait for is never held to another
    stream's bytes: a stream that holds nothing always admits, and a
    run whose first task is admitted is admitted to its end against the
    stream's own bytes alone. So no stream waits on another's consumer,
    and the streams together hold at most the budget and one run each
    of the others. One live stream is bounded exactly as if the bounds
    were its own. Iterating
    yields ``(task index, item)`` in submission order. A task that is
    over the budget alone never goes to the pool: the consumer runs its
    generator itself, item by item, when it gets there, so such a task
    is never materialised whole.

    A task's error is re-raised on the consuming thread (the original
    exception object) after the items the task produced before it. The
    borrowed threads carry ``conf``, ``query`` and, when a fault plan is
    armed, the fault scope of the thread that built the stream. Worker
    ``k`` names the affinity ``<affinity>#k``, so a table is decoded by
    the threads, and into the heaps, that decoded it last. ``close()``
    drops what is queued, waits for what runs, and leaves every
    borrowed thread parked. ``pooled`` counts the tasks taken from the
    pool, ``ahead`` those that were done when the consumer asked,
    ``threads_peak`` the most reader threads that were inside a task at
    once, this stream's and every other live one's, when one of its own
    started.

    ``hold_until[i] >= i`` names the task whose taking gives task
    ``i``'s cost back to the budget (default: its own): tasks that fill
    one buffer between them (a scan batch's files) stay on the budget
    until the consumer has the last of them. Such a run of tasks must
    fit the budget together, or the consumer would wait for one the
    budget never admits.
    """

    def __init__(self, tasks: "list[tuple[int, Callable[[], Iterator]]]",
                 threads: int, max_bytes: int,
                 conf: Optional[SrtConf] = None, query=None,
                 name: str = "ahead", affinity: Optional[str] = None,
                 hold_until: "Optional[list[int]]" = None):
        from ..robustness import faults
        self._tasks = tasks
        self._max_bytes = max(int(max_bytes), 0)
        self._conf = conf
        self._query = query
        self._fault_tag = faults.current_op() if faults.armed() else ""
        self._cv = _READERS.cv
        self._threads = max(int(threads), 1)
        self._next = 0  # first task not admitted yet
        self._queue: deque = deque()  # admitted, not yet claimed
        self._done: dict = {}  # task index -> (items, error)
        self._bytes = 0
        self._bytes_peak = 0
        self._hold_until = hold_until or list(range(len(tasks)))
        self._held = 0  # taken, still on the budget
        self._stopped = False
        self.pooled = 0
        self.ahead = 0
        self.threads_peak = 0
        self._thread_name = f"srt-prefetch-{name}"
        n = min(self._threads, sum(
            1 for cost, _ in tasks if cost <= self._max_bytes))
        with self._cv:
            self._admit()
        self._parked = [
            _PRODUCERS.start(self._work, f"{self._thread_name}-{k}",
                             f"{affinity or name}#{k}")
            for k in range(n)]

    def _admit(self) -> None:
        """Hand the pool every next task the budget has room for; stop
        at one the consumer has to run itself. The process's bytes are
        asked only where a run starts and the stream holds something;
        inside a run the stream's own decide, as if it were alone.
        Callers hold ``_cv``."""
        admitted = False
        while self._next < len(self._tasks) and not self._stopped:
            i = self._next
            cost = self._tasks[i][0]
            if self._bytes + cost > self._max_bytes:
                break
            starts_run = i == 0 or self._hold_until[i - 1] < i
            if starts_run and self._bytes and \
                    _READERS.bytes + cost > self._max_bytes:
                break
            self._queue.append(i)
            self._bytes += cost
            _READERS.bytes += cost
            self._bytes_peak = max(self._bytes_peak, self._bytes)
            self._next += 1
            admitted = True
        if admitted:
            self._cv.notify_all()

    def _work(self) -> None:
        from ..robustness import faults
        from ..robustness.admission import set_current_query
        set_active_conf(self._conf)
        set_current_query(self._query)
        scope = (faults.op_scope(self._fault_tag) if self._fault_tag
                 else contextlib.nullcontext())
        with scope:
            while True:
                with self._cv:
                    while not (self._queue
                               and _READERS.live < self._threads):
                        if self._stopped or not self._queue and \
                                self._next >= len(self._tasks):
                            return
                        self._cv.wait()
                    if self._stopped:
                        return
                    i = self._queue.popleft()
                    make = self._tasks[i][1]
                    _READERS.live += 1
                    self.threads_peak = max(self.threads_peak,
                                            _READERS.live)
                items, error = [], None
                try:
                    items.extend(make())
                except BaseException as e:  # noqa: BLE001 — relayed
                    error = e
                with self._cv:
                    _READERS.live -= 1
                    if not self._stopped:
                        self._done[i] = (items, error)
                    self._cv.notify_all()

    def __iter__(self) -> Iterator:
        for i, (cost, make) in enumerate(self._tasks):
            if cost > self._max_bytes:
                with contextlib.closing(make()) as items:
                    for item in items:
                        yield i, item
                with self._cv:
                    self._next = i + 1
                    self._admit()
                    self._cv.notify_all()  # the last task: workers leave
                continue
            with self._cv:
                self.pooled += 1
                if i in self._done:
                    self.ahead += 1
                while i not in self._done:
                    # another stream may have given bytes back
                    self._admit()
                    self._cv.wait()
                items, error = self._done.pop(i)
                self._held += cost
                if self._hold_until[i] <= i:
                    self._bytes -= self._held
                    _READERS.bytes -= self._held
                    self._held = 0
                    self._cv.notify_all()
                self._admit()
            for item in items:
                yield i, item
            if error is not None:
                raise error

    def close(self, join_timeout: float = 30.0) -> None:
        with self._cv:
            _READERS.bytes -= self._bytes  # what was never taken
            self._bytes = 0
            self._stopped = True
            queued = len(self._queue)
            self._queue.clear()
            self._done.clear()
            self._cv.notify_all()
        for k, parked in enumerate(self._parked):
            _await_parked(parked, f"{self._thread_name}-{k}", join_timeout,
                          queued)


def pipeline_enabled(ctx: ExecContext, node=None) -> bool:
    """Runtime gate: the conf switch AND (for exchanges) the planner's
    safety tag. The planner withholds ``_pipeline_ok`` from plans with
    partition-context expressions (spark_partition_id() et al) whose
    values would race against a producer advancing ``ctx.partition_id``.
    """
    if not ctx.conf.get(PIPELINE_ENABLED):
        return False
    if node is not None and not getattr(node, "_pipeline_ok", False):
        return False
    return True


class _Unstaged:
    """Queue-slot shim matching SpillableBatch's get/close/nbytes
    surface WITHOUT taking a spill-catalog registration or ownership.
    Used for zero-copy shuffle-bypass streams: those batches are live
    objects the shuffle manager still owns (already spill-registered in
    its device catalog), so re-wrapping would double-account the bytes
    and a queue discard would close a batch other readers may replay.
    """

    __slots__ = ("_batch", "nbytes")

    def __init__(self, batch):
        self._batch = batch
        self.nbytes = int(getattr(batch, "nbytes", 0))

    def get(self):
        return self._batch

    def close(self) -> None:
        pass


def _start_producer(ctx: ExecContext, node: TpuExec,
                    source_factory: Callable[[], Iterable],
                    name: str, stage: bool,
                    affinity: Optional[str]) -> PrefetchIterator:
    """The producer half of ``prefetch_batches``: a running
    PrefetchIterator of staged batches, its metrics on ``node``."""
    from ..memory.spill import SpillableBatch, SpillPriority
    m = ctx.metrics_for(node.exec_id)
    wait = m.setdefault("prefetchWaitTime",
                        Metric("prefetchWaitTime", Metric.MODERATE, "ns"))
    dpk = m.setdefault("prefetchQueueDepthPeak",
                       Metric("prefetchQueueDepthPeak", Metric.DEBUG))
    bpk = m.setdefault("prefetchBytesPeak",
                       Metric("prefetchBytesPeak", Metric.DEBUG))
    leaks = m.setdefault("prefetchThreadLeaks",
                         Metric("prefetchThreadLeaks", Metric.ESSENTIAL))

    def staged() -> Iterator:
        for batch in source_factory():
            yield SpillableBatch(batch, SpillPriority.ACTIVE_ON_DECK) \
                if stage else _Unstaged(batch)

    # capture the enclosing operator span NOW, on the consumer thread:
    # the nearest timed frame with a live span, else the thread's open
    # scope (query/task span) — the producer thread can't see either
    parent_span_id = None
    if ctx.tracer is not None:
        for frame in reversed(ctx.timer_stack):
            sp = getattr(frame, "_span", None)
            if sp is not None:
                parent_span_id = sp.span_id
                break
        if parent_span_id is None:
            parent_span_id = ctx.tracer.current_id()

    return PrefetchIterator(
        staged,
        depth=ctx.conf.get(PIPELINE_DEPTH),
        max_bytes=ctx.conf.get(PIPELINE_MAX_BYTES),
        nbytes=lambda sb: sb.nbytes,
        conf=ctx.conf,
        fault_tag=node.exec_id,
        on_discard=lambda sb: sb.close(),
        name=name or node.exec_id,
        wait_metric=wait,
        depth_peak_metric=dpk,
        bytes_peak_metric=bpk,
        tracer=ctx.tracer,
        parent_span_id=parent_span_id,
        query=ctx.query,
        leak_metric=leaks,
        affinity=affinity)


def prefetch_batches(ctx: ExecContext, node: TpuExec,
                     source_factory: Callable[[], Iterable],
                     name: str = "", stage: bool = True,
                     affinity: Optional[str] = None) -> Iterator:
    """Pull a ColumnarBatch stream through a background prefetcher.

    Each produced batch registers with the spill catalog as an
    ACTIVE_ON_DECK SpillableBatch while it waits in the queue (memory
    pressure can push queued batches to host/disk instead of OOMing);
    the consumer re-materializes (usually a no-op: still on device) and
    releases the registration before yielding. Metrics land on
    ``node``: prefetchWaitTime (consumer blocked on an empty queue),
    prefetchQueueDepthPeak, prefetchBytesPeak.

    The producer starts here, unless a join above ``node`` started it
    when the join itself started (``start_early``): then the stream
    continues the producer that waits on ``ctx.early_sources``.

    ``affinity`` names what the producer works on, where that outlives
    the plan node (a scan's files): the same thread, and so the same
    host heap, serves it from query to query. Default: the name.

    ``stage=False`` skips the SpillableBatch wrap — for streams that
    may hand through ALREADY-owned live batches (the shuffle locality
    bypass), where a second registration would double-count memory and
    discard-on-close would free somebody else's batch.
    """
    pf = ctx.early_sources.pop(node.exec_id, None) or _start_producer(
        ctx, node, source_factory, name, stage, affinity)

    def consume() -> Iterator:
        try:
            for sb in pf:
                try:
                    batch = sb.get()
                finally:
                    sb.close()
                yield batch
        finally:
            pf.close()
    return consume()


def start_early(ctx: ExecContext, node: TpuExec,
                source_factory: Callable[[], Iterable],
                name: str = "", affinity: Optional[str] = None
                ) -> "list[str]":
    """``TpuExec.start_sources`` of a node that pulls through
    ``prefetch_batches``: start that producer now and leave it on
    ``ctx.early_sources`` for the node's own execution to continue.
    Depth and ``srt.exec.pipeline.maxBytesInFlight`` bound what it
    queues meanwhile, as in steady state; an error it meets waits for
    the first pull. Returns ``[node.exec_id]``, or nothing where the
    node's producer already waits there. Counted in the node's
    ``prefetchEarlyStarts``."""
    if node.exec_id in ctx.early_sources:
        return []
    ctx.early_sources[node.exec_id] = _start_producer(
        ctx, node, source_factory, name, True, affinity)
    ctx.metrics_for(node.exec_id).setdefault(
        "prefetchEarlyStarts",
        Metric("prefetchEarlyStarts", Metric.MODERATE)).add(1)
    return [node.exec_id]


@contextlib.contextmanager
def sources_started(ctx: ExecContext, node: TpuExec):
    """``node`` (a join) executes inside this: its sources start at
    entry (``node.start_sources``), and whichever of them its execution
    never pulled — an inner join over an empty build, an error or a
    cancel while the build drains, a LIMIT satisfied above — is closed
    at exit: its thread parks, its queued batches are discarded."""
    started = node.start_sources(ctx)
    try:
        yield
    finally:
        for exec_id in started:
            pf = ctx.early_sources.pop(exec_id, None)
            if pf is not None:
                pf.close()


class PrefetchExec(TpuExec):
    """Transparent pipelining node: runs its child on a background
    thread (prefetch_batches) and re-yields. Inserted by the planner
    above blocking sources (today: FileSourceScanExec); schema and
    partitioning pass through. When ``srt.exec.pipeline.enabled`` is
    off at run time (a cached plan re-run under a different conf) it
    degrades to a synchronous pass-through."""

    _streams_child = True

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._pipeline_ok = True

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    @property
    def output_partitioning(self):
        return self.children[0].output_partitioning

    def do_execute(self, ctx: ExecContext) -> Iterator:
        child = self.children[0]
        if not pipeline_enabled(ctx, self):
            yield from child.execute(ctx)
            return
        # the child's description (a scan: format and files), not this
        # node's id: every plan over the same table shares the producer
        yield from prefetch_batches(ctx, self, lambda: child.execute(ctx),
                                    affinity=child.node_description())

    def start_sources(self, ctx: ExecContext) -> "list[str]":
        if not pipeline_enabled(ctx, self):
            return []
        child = self.children[0]
        return start_early(ctx, self, lambda: child.execute(ctx),
                           affinity=child.node_description())

    def node_description(self) -> str:
        return "Prefetch"
