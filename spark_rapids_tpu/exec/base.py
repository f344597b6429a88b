"""Exec base: operator protocol, metrics, device semaphore.

Reference counterparts: GpuExec.scala:197 (base trait + metrics
GpuExec.scala:36-188), GpuSemaphore.scala (N tasks share the device,
computeNumPermits :106), GpuMetric ESSENTIAL/MODERATE/DEBUG levels.
"""

from __future__ import annotations

import os

import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence

from ..columnar import dtypes as dt
from ..columnar.vector import ColumnarBatch
from ..conf import CONCURRENT_TASKS, SrtConf, active_conf
from ..jit_registry import read_int
from ..obs.trace import annotate

Schema = List  # [(name, DType), ...]


class Metric:
    """One operator metric (GpuMetric). Thread-safe accumulator."""

    ESSENTIAL = "ESSENTIAL"
    MODERATE = "MODERATE"
    DEBUG = "DEBUG"

    def __init__(self, name: str, level: str = MODERATE, unit: str = ""):
        self.name = name
        self.level = level
        self.unit = unit
        self.value = 0
        self._lock = threading.Lock()

    def add(self, v) -> None:
        with self._lock:
            self.value += int(v)

    def set(self, v) -> None:
        with self._lock:
            self.value = int(v)

    def __repr__(self):
        return f"{self.name}={self.value}{self.unit}"


#: operator Metric -> key of the query record's ``phases``: batches a
#: filter compacted or an exchange cut with their fixed-width columns
#: carried through the sort that orders the rows, and batches whose every
#: plane was gathered (``columnar/vector.py::sort_carries``)
COMPACT_COUNTERS = {"compactSortedBatches": "compact_sorted_batches",
                    "compactGatheredBatches": "compact_gathered_batches"}


def count_compaction(metrics: Dict[str, "Metric"], carried: bool) -> None:
    """One batch compacted or cut, the way ``sort_carries`` said."""
    name = "compactSortedBatches" if carried else "compactGatheredBatches"
    metrics.setdefault(name, Metric(name, Metric.MODERATE)).add(1)


class NvtxTimer:
    """Scoped op-time accumulation (NvtxWithMetrics.scala:21-48).

    On TPU there is no NVTX; ranges surface through jax.profiler traces.
    """

    def __init__(self, metric: Optional[Metric], name: str = ""):
        self.metric = metric
        self.name = name
        self._t0 = 0.0
        self._trace = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        self._trace = annotate(self.name or "op")
        self._trace.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            self._trace.__exit__(*exc)
        finally:
            self._trace = None
            if self.metric is not None:
                self.metric.add(time.perf_counter_ns() - self._t0)
        return False


class SelfTimer:
    """Self-time accumulation for nested operator pulls.

    Operators pull their children inside ``next()``, so a naive scoped
    timer would charge the whole subtree to every ancestor (the reference
    explicitly excludes child time from op time). A per-context timer
    stack pauses the enclosing operator's clock while a nested one runs:
    each metric receives only the time its own operator spent. Each
    pulling thread has its own stack (ExecContext.timer_stack is
    thread-local): frames on different threads run genuinely in
    parallel — pipelined producers (exec/pipeline.py) — and must not
    pause each other; I/O thread pools do their timing elsewhere.
    """

    def __init__(self, stack: list, metric: Optional[Metric], name: str = "",
                 tracer=None):
        self.stack = stack
        self.metric = metric
        self.name = name
        self.tracer = tracer
        self._t0 = 0
        self._span = None
        self._trace = None

    def __enter__(self):
        t = time.perf_counter_ns()
        if self.stack:
            parent = self.stack[-1]
            if parent.metric is not None:
                parent.metric.add(t - parent._t0)
        self._t0 = t
        self.stack.append(self)
        if self.tracer is not None:
            # Inclusive operator span: parent is the nearest enclosing
            # timed frame's span, else the thread's open scope (the
            # query/task span).
            parent_id = None
            for frame in reversed(self.stack[:-1]):
                sp = getattr(frame, "_span", None)
                if sp is not None:
                    parent_id = sp.span_id
                    break
            if parent_id is None:
                parent_id = self.tracer.current_id()
            self._span = self.tracer.begin(self.name or "op",
                                           kind="operator",
                                           parent=parent_id)
        self._trace = annotate(self.name or "op")
        self._trace.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            self._trace.__exit__(*exc)
        finally:
            self._trace = None
            t = time.perf_counter_ns()
            if self in self.stack:
                # An exception below us may have abandoned deeper frames
                # (a suspended generator torn down without its __exit__
                # in stack order). Discard them so the stack stays
                # consistent: the deepest one was the frame actually
                # running, so it gets the elapsed time; the others (and
                # we) were already paused at their child's enter and
                # accrue nothing more.
                dangled = False
                while self.stack[-1] is not self:
                    frame = self.stack.pop()
                    if not dangled and frame.metric is not None:
                        frame.metric.add(t - frame._t0)
                    dangled = True
                self.stack.pop()
                if self.metric is not None and not dangled:
                    self.metric.add(t - self._t0)
                if self.stack:
                    self.stack[-1]._t0 = t
            if self._span is not None and self.tracer is not None:
                self.tracer.end(self._span)
                self._span = None
        return False


class TpuSemaphore:
    """Limits concurrent device-work submitters (GpuSemaphore.scala).

    The reference grants 1000/N permits per task so configuration can
    over/under-subscribe; here a plain counting semaphore over host
    threads suffices because XLA serializes execution per device stream.
    """

    def __init__(self, permits: int):
        self._sem = threading.Semaphore(permits)
        self.permits = permits
        self._holders: Dict[int, int] = {}
        self._lock = threading.Lock()

    def acquire_if_necessary(self) -> None:
        tid = threading.get_ident()
        with self._lock:
            if self._holders.get(tid, 0) > 0:
                self._holders[tid] += 1
                return
        # uncontended fast path: only actual blocking counts as wait
        # (GpuTaskMetrics semaphore-wait accumulator), charged to the
        # thread's current query, as a launch is
        if not self._sem.acquire(blocking=False):
            t0 = time.perf_counter_ns()
            with annotate("semaphore.wait"):
                self._sem.acquire()
            wait_ns = time.perf_counter_ns() - t0
            from ..robustness.admission import current_query
            query = current_query()
            if query is not None:
                query.count_semaphore_wait(wait_ns)
        with self._lock:
            self._holders[tid] = 1

    def release_if_held(self) -> None:
        tid = threading.get_ident()
        with self._lock:
            n = self._holders.get(tid, 0)
            if n == 0:
                return
            if n > 1:
                self._holders[tid] = n - 1
                return
            del self._holders[tid]
        self._sem.release()

    def __enter__(self):
        self.acquire_if_necessary()
        return self

    def __exit__(self, *exc):
        self.release_if_held()
        return False


_GLOBAL_SEM: Optional[TpuSemaphore] = None
_SEM_LOCK = threading.Lock()


def device_semaphore(conf: Optional[SrtConf] = None) -> TpuSemaphore:
    """Process-wide device semaphore, sized from config on first use:
    ``srt.sql.concurrentTpuTasks`` of the first session that executes
    (the ``query_semaphore`` idiom, robustness/admission.py)."""
    global _GLOBAL_SEM
    with _SEM_LOCK:
        if _GLOBAL_SEM is None:
            _GLOBAL_SEM = TpuSemaphore(
                (conf or active_conf()).get(CONCURRENT_TASKS))
        return _GLOBAL_SEM


def reset_device_semaphore(conf: Optional[SrtConf] = None
                           ) -> Optional[TpuSemaphore]:
    """Test hook: drop the singleton (resized from conf on next use, or
    immediately when a conf is given), as ``reset_query_semaphore``."""
    global _GLOBAL_SEM
    with _SEM_LOCK:
        _GLOBAL_SEM = None
    return device_semaphore(conf) if conf is not None else None


class ExecContext:
    """Per-query execution context: conf, metrics sink, semaphore."""

    def __init__(self, conf: Optional[SrtConf] = None, query=None):
        self.conf = conf or active_conf()
        #: cancellation/deadline token (robustness/admission.py
        #: QueryContext); None = non-cancellable run. Checked once per
        #: batch in ``TpuExec.execute`` — the universal teardown point
        #: covering every operator — and shipped to producer/fetch
        #: threads spawned on the query's behalf.
        self.query = query
        self.semaphore = device_semaphore(self.conf)
        self.metrics: Dict[str, Dict[str, Metric]] = {}
        #: SelfTimer stacks, one per pulling thread (see timer_stack)
        self._timer_stacks = threading.local()
        #: current reduce-partition index for context expressions
        #: (spark_partition_id / monotonically_increasing_id); operators
        #: that stream one partition at a time set this while iterating
        self.partition_id = 0
        #: multi-host execution context (parallel/cluster.py
        #: ClusterTaskContext); None = single-process run
        self.cluster = None
        #: crash-dump ring (srt.debug.dumpPath): exec_id -> last batch
        self.last_batches: Dict[str, tuple] = {}
        self._dumped = False
        #: per-query span tracer (obs/trace.py) when
        #: srt.eventLog.trace.enabled; None = no span allocation
        self.tracer = None
        #: join exec_id -> (build batch, what the join computed from it:
        #: exec/join.py BuildSide): built once per build batch, dropped
        #: with the query's context
        self.join_builds: Dict[str, tuple] = {}
        #: exec_id -> the prefetch producer (exec/pipeline.py
        #: PrefetchIterator) a join started for that node before its
        #: first pull (``TpuExec.start_sources``); the node's execution
        #: takes it from here, the join that started it closes what is
        #: never taken. On the context, not the node: plans are cached
        #: and re-run
        self.early_sources: Dict[str, object] = {}

    def dump_crash(self, failing_exec, error: BaseException,
                   dump_dir: str) -> Optional[str]:
        """Write every operator's last output batch + the plan tree +
        the error under dump_dir (once per query) so the failure
        replays offline (DumpUtils crash-dump role). Returns the dump
        directory."""
        if self._dumped:
            return None
        self._dumped = True
        import time as _time

        from ..utils.dump import dump_batch
        out = os.path.join(dump_dir,
                           f"crash-{int(_time.time() * 1e3)}")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "plan.txt"), "w") as f:
            f.write(failing_exec.tree_string() + "\n\n")
            f.write(f"failing operator: "
                    f"{failing_exec.node_description()}\n")
            f.write(f"error: {type(error).__name__}: {error}\n")
        for exec_id, (desc, batch) in list(self.last_batches.items()):
            safe = exec_id.replace("#", "_")
            try:
                dump_batch(batch, out, prefix=safe)
            except Exception:
                pass  # best-effort: a corrupt batch may be the cause
        return out

    @property
    def timer_stack(self) -> list:
        """This thread's SelfTimer stack. Per-thread so pipelined
        producer threads (exec/pipeline.py) attribute their operators'
        exclusive time on their own stack — frames on different threads
        genuinely run concurrently and must not pause each other."""
        st = getattr(self._timer_stacks, "stack", None)
        if st is None:
            st = self._timer_stacks.stack = []
        return st

    def metrics_for(self, exec_id: str) -> Dict[str, Metric]:
        return self.metrics.setdefault(exec_id, {})


class TpuExec:
    """Base physical operator.

    Children in ``children``; ``output_schema`` is the produced schema;
    ``execute(ctx)`` yields ColumnarBatches. Subclasses implement
    ``do_execute``.
    """

    _counter = [0]

    #: set by the planner when a partition-wise parent consumes this
    #: node's advertised partitioning without a re-exchange: AQE
    #: transforms that change the partition count must stand down
    preserve_partitioning = False

    #: True on operators that pull their one child batch after batch,
    #: from their first pull to their last (filter, project, coalesce,
    #: prefetch, the fused wrappers): ``start_sources`` reaches the
    #: scans beneath them
    _streams_child = False

    def __init__(self, *children: "TpuExec"):
        self.children: List[TpuExec] = list(children)
        TpuExec._counter[0] += 1
        self.exec_id = f"{type(self).__name__}#{TpuExec._counter[0]}"

    @property
    def output_schema(self) -> Schema:
        raise NotImplementedError

    # --- distribution protocol (plan/distribution.py; EnsureRequirements) ---
    @property
    def output_partitioning(self):
        """How this node's output rows are spread across partitions.
        Default: unknown (forces an exchange wherever a parent needs a
        specific distribution)."""
        from ..plan.distribution import UnknownPartitioning
        return UnknownPartitioning(1)

    def required_child_distributions(self):
        """Per-child Distribution requirements; the planner inserts
        exchanges for children that do not satisfy them."""
        from ..plan.distribution import UnspecifiedDistribution
        return [UnspecifiedDistribution() for _ in self.children]

    def execute_partitioned(self, ctx: "ExecContext"):
        """Yield one batch-iterator per output partition.

        Exchange nodes yield their reduce partitions; everything else is
        a single stream. Partition-wise consumers (final aggregate,
        shuffled join, partition sort) pull through this instead of
        ``execute`` so partition boundaries survive the operator.
        """
        yield self.execute(ctx)

    def _measure_stream(self, ctx: "ExecContext", stream):
        """Output accounting for partition-wise consumption paths that
        bypass ``execute()`` (which does this for the plain path)."""
        m = ctx.metrics_for(self.exec_id)
        rows = m.setdefault("numOutputRows",
                            Metric("numOutputRows", Metric.ESSENTIAL))
        batches = m.setdefault(
            "numOutputBatches", Metric("numOutputBatches",
                                       Metric.MODERATE))
        for b in stream:
            rows.add(read_int(b.num_rows))
            batches.add(1)
            yield b

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        m = ctx.metrics_for(self.exec_id)
        rows = m.setdefault("numOutputRows", Metric("numOutputRows",
                                                    Metric.ESSENTIAL))
        batches = m.setdefault("numOutputBatches",
                               Metric("numOutputBatches", Metric.MODERATE))
        optime = m.setdefault("opTime", Metric("opTime", Metric.ESSENTIAL,
                                               "ns"))
        from ..conf import DEBUG_DUMP_PATH
        dump_dir = ctx.conf.get(DEBUG_DUMP_PATH)
        # fault injection at operator granularity: tag the pulling
        # thread with this operator's exec_id so memory.reserve fault
        # sites can ~match on it. Only when a plan is armed — the
        # production path never touches the scope TLS.
        from ..robustness import faults
        scope = faults.op_scope(self.exec_id) if faults.armed() else None
        qctx = ctx.query
        it = iter(self.do_execute(ctx))
        while True:
            # per-batch cancellation/deadline point: every operator's
            # pull loop funnels through here, so one check covers scans,
            # fused programs, joins, and exchanges alike (None check
            # only when cancellation is unused)
            if qctx is not None:
                qctx.check()
            with SelfTimer(ctx.timer_stack, optime, self.exec_id,
                           ctx.tracer):
                try:
                    if scope is None:
                        batch = next(it)
                    else:
                        with scope:
                            batch = next(it)
                except StopIteration:
                    return
                except BaseException as e:
                    if dump_dir:
                        ctx.dump_crash(self, e, dump_dir)
                    raise
            rows.add(read_int(batch.num_rows))
            batches.add(1)
            if dump_dir:
                ctx.last_batches[self.exec_id] = \
                    (self.node_description(), batch)
            yield batch

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    def start_sources(self, ctx: ExecContext) -> List[str]:
        """Start now, ahead of the first pull, the prefetch producers
        of the sources this operator will stream in this execution; a
        join calls it on its children when it starts, so the scans
        beneath it run beside its build (exec/pipeline.py
        ``sources_started``). Returns the exec_ids whose producers it
        started and left on ``ctx.early_sources``. Default: none — an
        operator that may never pull its child, or not in plan order
        (exchanges' map sides, sort, limit, union, window), starts
        nothing the lazy order would not have started."""
        if self._streams_child:
            return self.children[0].start_sources(ctx)
        return []

    def reset_for_rerun(self) -> None:
        """Clear one-shot per-run state before a cached physical tree is
        re-executed (plan/plan_cache.py). Compile caches (jit wrappers)
        must survive — they are the point of caching the tree; stateful
        nodes (shuffle writes, broadcast materialization) override."""
        # adaptive decisions are derived from ONE run's measured sizes;
        # the next run measures afresh (plan/adaptive.py caches)
        self.__dict__.pop("_adaptive_decision", None)
        self.__dict__.pop("_adaptive_groups_cache", None)
        for c in self.children:
            if isinstance(c, TpuExec):
                c.reset_for_rerun()

    # --- plan tree utilities ---
    def tree_string(self, indent: int = 0) -> str:
        line = "  " * indent + "* " + self.node_description()
        return "\n".join([line] + [c.tree_string(indent + 1)
                                   for c in self.children])

    def node_description(self) -> str:
        return type(self).__name__

    def __repr__(self):
        return self.tree_string()


def schema_names(schema: Schema) -> List[str]:
    return [n for n, _ in schema]


def schema_types(schema: Schema) -> List[dt.DType]:
    return [t for _, t in schema]
