"""User-facing session + DataFrame API.

The frontend that plays Spark's role above the plan-rewrite layer: users
build DataFrames (logical plans), and ``collect`` runs them through the
overrides driver (overrides.py) onto the TPU, with CPU fallback for
anything tagged unsupported — the full tag-then-convert architecture of
the reference (Plugin.scala ColumnarOverrideRules) with our own engine
underneath instead of Spark's.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union as TUnion

import numpy as np

from ..columnar import dtypes as dt
from ..conf import SrtConf, active_conf, set_active_conf
from ..exec.base import COMPACT_COUNTERS as _COMPACT_COUNTERS
from ..exec.base import ExecContext, TpuExec
from ..exec.aggregate import LANE_COUNTERS as _LANE_COUNTERS
from ..exec.exchange import EXCHANGE_COUNTERS as _EXCHANGE_COUNTERS
from ..exec.join import JOIN_COUNTERS as _JOIN_COUNTERS
from ..expr.aggregates import (Average, Count, CountStar, First, Last, Max,
                               Min, StddevSamp, Sum)
from ..expr.core import Alias, ColumnRef, Expression, col, lit, output_name
from ..jit_registry import read_int
from ..obs.trace import annotate
from . import logical as L
from . import overrides
from .host_table import HostTable, batch_to_table, concat_tables, empty_like, to_pydict
from .transitions import CpuPhysical, DeviceToHostBridge

#: re-check the map count only every N executes (reading
#: /proc/self/maps is O(mappings) — cheap, but not free per query).
#: 1-2 NDS-scale queries can add several thousand mappings when the
#: persistent cache is warm (deserialization is fast), so the window
#: must stay small.
import os as _os
import sys as _sys
import threading as _threading
import time as _time

try:
    _MMAP_CHECK_EVERY = max(
        1, int(_os.environ.get("SRT_MMAP_CHECK_EVERY", 2)))
except ValueError:
    _MMAP_CHECK_EVERY = 2
_mmap_counter = [0]


def mmap_pressure() -> bool:
    """True when this process's memory mappings near the kernel's
    vm.max_map_count (65530 default): every compiled XLA executable
    holds mmap'd code pages, the engine mints fresh jit wrappers per
    plan, and long many-query processes (the 99-query NDS suite, the
    test suite) accumulate mappings monotonically until the limit is hit
    — at which point jaxlib SIGSEGVs inside whatever allocation crosses
    the line (compile, cache write OR cache load)."""
    try:
        with open("/proc/self/maps", "rb") as f:
            used = sum(1 for _ in f)
        with open("/proc/sys/vm/max_map_count", "rb") as f:
            limit = int(f.read())
    except OSError:  # non-Linux: nothing to defend against
        return False
    try:
        frac = float(_os.environ.get("SRT_MMAP_GUARD_FRACTION", 0.5))
    except ValueError:
        frac = 0.5
    if _os.environ.get("SRT_MMAP_GUARD_DEBUG"):
        print(f"[mmap_guard] used={used} limit={limit}",
              file=_sys.stderr, flush=True)
    return used >= frac * limit


def release_compiled_programs() -> None:
    """Drop every in-memory executable (the persistent disk cache keeps
    recompiles cheap), returning their mappings to the kernel."""
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


def _mmap_guard(session) -> None:
    """Self-defense against memory-mapping exhaustion (SURVEY §5
    failure-detection role; observed live in round 4, and again under
    jax 0.9.0 in PR 21): when usage nears the limit, drop the session's
    plan cache (its exec trees pin traced jits) and every in-memory
    executable."""
    _mmap_counter[0] += 1
    if _mmap_counter[0] % _MMAP_CHECK_EVERY:
        return
    if mmap_pressure():
        session._plan_cache.clear()
        release_compiled_programs()


class TpuSession:
    """Entry point (SparkSession analogue). Holds the active conf and
    the temp-view catalog backing ``sql()``."""

    #: process-wide query sequence — query ids stay unique across
    #: sessions within one process (event-log files are per process)
    _query_seq = [0]

    def __init__(self, conf: Optional[SrtConf] = None):
        self.conf = conf or active_conf()
        self._catalog: Dict[str, "DataFrame"] = {}
        from .plan_cache import PhysicalPlanCache
        self._plan_cache = PhysicalPlanCache()
        #: (physical, ctx, query_id, wall_ns) of the most recent
        #: execute — explain(metrics=True) renders from this
        self._last_execution = None
        #: QueryContext of the query this session is currently
        #: executing (None when idle): the cancel handle for other
        #: threads — ``session.cancel()`` / serving-tier aborts
        self._active_query = None
        #: serving-tier identity: when set (serve/server.py stamps
        #: them per client session) QueryStart/QueryEnd events carry
        #: session_id/tenant fields so per-pid event logs from a
        #: multi-session server group by tenant in profile_report /
        #: history_report instead of interleaving anonymously
        self.session_id: Optional[str] = None
        self.tenant: Optional[str] = None

    def cancel(self, reason: str = "session.cancel()") -> bool:
        """Cancel the in-flight query, if any (thread-safe; callable
        from any thread). Returns True if a query was signalled."""
        q = self._active_query
        if q is None:
            return False
        q.cancel(reason)
        return True

    # --- constructors ---
    def create_dataframe(self, data: Dict[str, list],
                         schema: Optional[List] = None) -> "DataFrame":
        if schema is None:
            schema = _infer_schema(data)
        return DataFrame(self, L.LocalRelation(data, schema))

    # --- SQL frontend (sql/parser.py; the Catalyst seam analogue) ---
    def create_or_replace_temp_view(self, name: str, df: "DataFrame"
                                    ) -> None:
        self._catalog[name.lower()] = df

    def table(self, name: str) -> "DataFrame":
        try:
            return self._catalog[name.lower()]
        except KeyError:
            raise KeyError(f"table or view {name!r} not found; register "
                           "with create_or_replace_temp_view")

    def sql(self, text: str) -> "DataFrame":
        """Run a SQL SELECT over registered temp views."""
        from ..sql import parse_sql
        return parse_sql(self, text)

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.Range(start, end, step))

    @property
    def read(self) -> "DataFrameReader":
        from ..io.reader import DataFrameReader
        return DataFrameReader(self)

    # --- execution ---
    def execute(self, plan: L.LogicalPlan,
                timeout: Optional[float] = None,
                query=None, parse_ns: int = 0) -> HostTable:
        """Run a logical plan to a host table.

        Physical plans are memoized on a structural key (plan_cache.py)
        so repeated collects of an identical query — even through fresh
        DataFrame objects — reuse the exec tree and its traced jits;
        without this every collect re-traced every jaxpr (the dominant
        warm-query cost). ``parse_ns`` is what building ``plan`` from
        SQL text cost (``DataFrame.parse_ns``): it is reported with the
        query's other phases."""
        return self._execute_recorded(plan, timeout, query, parse_ns)[0]

    def _execute_recorded(self, plan: L.LogicalPlan,
                          timeout: Optional[float] = None, query=None,
                          parse_ns: int = 0) -> Tuple[HostTable, dict]:
        """``execute`` returning the query's registry record beside the
        table, so the caller that builds rows out of the table can add
        its share to ``record["phases"]["fetch_ns"]``."""
        t0 = _time.perf_counter_ns()
        with annotate("plan.physical"):
            _mmap_guard(self)
            if self.conf.ansi:
                # srt.sql.ansi.enabled: clone the plan with every Cast /
                # arithmetic / sum node ansi-marked so overflow and
                # invalid casts raise (expr/ansi.py; the conf is part of
                # the plan cache key, so ANSI and non-ANSI plans never
                # alias)
                from ..expr.ansi import rewrite_plan
                plan = rewrite_plan(plan)
            from .plan_cache import plan_cache_key
            key = plan_cache_key(plan, self.conf)
            physical, release = (None, None)
            if key is not None:
                # execution lease: a cached tree may run on one thread
                # at a time (its shuffle ids / write flags are instance
                # state); a busy entry makes this caller plan fresh
                physical, release = self._plan_cache.lease(key)
            if physical is None:
                physical = overrides.apply_overrides(plan, self.conf)
                # only fully-device plans cache: CPU/bridge nodes hold
                # no reset protocol for their one-shot state
                if key is not None and isinstance(physical, TpuExec):
                    release = self._plan_cache.put_leased(key, physical)
            elif isinstance(physical, TpuExec):
                physical.reset_for_rerun()
        plan_ns = _time.perf_counter_ns() - t0
        try:
            return self._execute_physical(physical, plan,
                                          timeout=timeout, query=query,
                                          parse_ns=parse_ns,
                                          plan_ns=plan_ns)
        finally:
            if release is not None:
                release()

    def _execute_physical(self, physical, plan: L.LogicalPlan,
                          timeout: Optional[float] = None,
                          query=None, parse_ns: int = 0,
                          plan_ns: int = 0) -> Tuple[HostTable, dict]:
        """Run a planned physical tree with the query-level
        observability wrapper: QueryStart/QueryEnd events, optional
        per-query span tracer (written out as a Chrome trace), and a
        per-query metrics summary recorded in the process registry,
        with the query's ``phases`` (docs/OBSERVABILITY.md). When
        observability is off this adds one conf check and one
        per-query summary — nothing per batch. Returns the result and
        that record.

        Concurrency contract (robustness/admission.py): the query
        first passes admission (``srt.sql.concurrentQueryTasks``
        running, bounded queue, load-shed with AdmissionRejected),
        claims a per-query budget slice, and executes under a
        QueryContext cancel token armed from ``timeout`` (collect) or
        ``srt.sql.queryTimeout`` — cancellation/deadline surface as
        QueryCancelled / DeadlineExceeded after a clean teardown
        through every producer and fetch thread."""
        from ..conf import METRICS_LEVEL, QUERY_TIMEOUT_S
        from ..obs import events as _events
        from ..obs import resource as _resource
        from ..obs.registry import registry as _registry
        from ..obs.registry import summarize_metrics
        from ..obs.trace import maybe_tracer
        from ..memory.budget import device_budget, task_context
        from ..robustness.admission import (DeadlineExceeded,
                                            QueryContext,
                                            QueryInterrupted,
                                            query_scope, query_semaphore)
        _events.configure_from_conf(self.conf)
        _resource.configure_from_conf(self.conf)
        if query is not None:
            # externally-supplied cancel token (serve/server.py): the
            # caller holds the handle before admission, so a client
            # disconnect cancels a query even while it is still queued
            qctx = query
            qid = qctx.query_id
            if timeout is not None:
                qctx.set_timeout(timeout)
            elif qctx.deadline is None:
                qctx.set_timeout(self.conf.get(QUERY_TIMEOUT_S))
        else:
            TpuSession._query_seq[0] += 1
            qid = f"q{_os.getpid()}-{TpuSession._query_seq[0]}"
            qctx = QueryContext(query_id=qid)
            qctx.set_timeout(timeout if timeout is not None
                             else self.conf.get(QUERY_TIMEOUT_S))
        # admission before any work: may park this thread in the
        # bounded queue, load-shed (AdmissionRejected — retryable, no
        # resources held), or give up on cancel/deadline while queued
        sem = query_semaphore(self.conf)
        sem.acquire(qctx)
        budget = None
        try:
            budget = device_budget()
            budget.register_query(qid, slots=sem.permits)
            self._active_query = qctx
            qscope = query_scope(qctx)
            qscope.__enter__()
            ctx = ExecContext(self.conf, query=qctx)
            ctx.tracer = maybe_tracer(self.conf)
        except BaseException:
            # a failed setup must not leak the admission permit —
            # that would wedge every later query behind a ghost
            if budget is not None:
                budget.unregister_query(qid)
            sem.release()
            raise
        tc = task_context()
        tc0 = (tc.spilled_bytes, tc.retry_count, tc.split_count)
        # a caller's token may have run queries before this one
        launch0 = (qctx.dispatch_ns, qctx.launches,
                   qctx.semaphore_wait_ns, qctx.dispatch_cpu_ns,
                   qctx.readback_ns, qctx.readbacks)
        # this thread pulls the plan: its waits, launches and reads are
        # split out of the execution's wall (``consumer_*`` phases)
        consumer0 = qctx.consumer
        qctx.consumer = _threading.get_ident()
        mine0 = (qctx.consumer_scan_wait_ns,
                 qctx.consumer_semaphore_wait_ns,
                 qctx.consumer_dispatch_ns, qctx.consumer_readback_ns)
        fetch_ns = 0
        is_tpu = isinstance(physical, TpuExec)
        # serving identity fields ride on QueryStart/QueryEnd (only
        # when set: single-session logs stay byte-identical)
        ident: Dict = {}
        if self.session_id is not None:
            ident["session_id"] = self.session_id
        if self.tenant is not None:
            ident["tenant"] = self.tenant
        if _events.enabled():
            _events.emit("QueryStart", query_id=qid, device=is_tpu,
                         plan=physical.tree_string() if is_tpu
                         else type(physical).__name__, **ident)
        qspan = ctx.tracer.span(qid, kind="query") \
            if ctx.tracer is not None else None
        t0 = _time.perf_counter_ns()
        status = "ok"
        error = None
        try:
            if qspan is not None:
                qspan.__enter__()
            try:
                if is_tpu:
                    from ..memory.spill import batch_nbytes
                    from .adaptive import adaptive_execute
                    reg = _registry()
                    tables = []
                    for b in adaptive_execute(physical, ctx):
                        n = read_int(b.num_rows)
                        if n == 0:
                            continue
                        # output-batch shape distributions (once per
                        # OUTPUT batch, not per operator pull)
                        reg.observe("batch_rows", n, "rows")
                        reg.observe("batch_bytes", batch_nbytes(b),
                                    "bytes")
                        tf = _time.perf_counter_ns()
                        with annotate("result.fetch"):
                            tables.append(batch_to_table(b))
                        fetch_ns += _time.perf_counter_ns() - tf
                    result = concat_tables(tables) if tables \
                        else empty_like(plan.schema)
                else:
                    result = physical.evaluate(ctx)
                # final token check: a cancel/deadline that flipped as
                # the last producer drained must never surface as a
                # silently truncated "successful" result — a cancelled
                # query's caller gets the typed error even if the race
                # finished the pull loop first
                qctx.check()
            finally:
                if qspan is not None:
                    qspan.__exit__(None, None, None)
        except QueryInterrupted as e:
            status = "deadline_exceeded" \
                if isinstance(e, DeadlineExceeded) else "cancelled"
            error = f"{type(e).__name__}: {e}"
            _events.emit(type(e).__name__, query_id=qid,
                         reason=qctx.cancel_reason)
            raise
        except BaseException as e:
            status = "error"
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            qscope.__exit__(None, None, None)
            budget.unregister_query(qid)
            sem.release()
            if self._active_query is qctx:
                self._active_query = None
            wall_ns = _time.perf_counter_ns() - t0
            qctx.consumer = consumer0
            mine = dict(zip(CONSUMER_PHASES, (
                qctx.consumer_scan_wait_ns - mine0[0],
                qctx.consumer_semaphore_wait_ns - mine0[1],
                qctx.consumer_dispatch_ns - mine0[2],
                qctx.consumer_readback_ns - mine0[3])))
            _registry().observe("task_time_ns", wall_ns, "ns")
            summary = summarize_metrics(ctx.metrics,
                                        self.conf.get(METRICS_LEVEL))
            extra = {"spilled_bytes": tc.spilled_bytes - tc0[0],
                     "oom_retries": tc.retry_count - tc0[1],
                     "oom_splits": tc.split_count - tc0[2],
                     "phases": _query_phases(
                         ctx.metrics, parse_ns=parse_ns, plan_ns=plan_ns,
                         admission_wait_ns=qctx.admission_wait_ns or 0,
                         execute_ns=wall_ns, fetch_ns=fetch_ns,
                         dispatch_ns=qctx.dispatch_ns - launch0[0],
                         launches=qctx.launches - launch0[1],
                         semaphore_wait_ns=qctx.semaphore_wait_ns
                         - launch0[2],
                         dispatch_cpu_ns=qctx.dispatch_cpu_ns
                         - launch0[3],
                         readback_ns=qctx.readback_ns - launch0[4],
                         readbacks=qctx.readbacks - launch0[5],
                         consumer_host_ns=wall_ns - sum(mine.values()),
                         **mine)}
            rec = _registry().record_query(qid, summary, wall_ns,
                                           status, **extra)
            self._last_execution = {"physical": physical, "ctx": ctx,
                                    "query_id": qid, "wall_ns": wall_ns,
                                    "record": rec,
                                    "phases": rec["phases"]}
            if _events.enabled():
                end: Dict = {"query_id": qid, "status": status,
                             "wall_ns": wall_ns, "metrics": summary}
                end.update(ident)
                end.update(extra)
                if error is not None:
                    end["error"] = error
                _events.emit("QueryEnd", **end)
                if ctx.tracer is not None and \
                        _events.log_dir() is not None:
                    try:
                        ctx.tracer.write_chrome_trace(_os.path.join(
                            _events.log_dir(), f"trace-{qid}.json"))
                    except OSError:
                        pass
        return result, rec


#: operator Metric (summed over the plan, whatever srt.metrics.level
#: shows) -> key of the query record's ``phases``
_PHASE_METRICS = {"scanDecodeTime": "scan_decode_ns",
                  "scanPooledFiles": "scan_pooled_files",
                  "scanDecodeAheadFiles": "scan_ahead_files",
                  "scanBatches": "scan_batches",
                  "scanInPlaceBatches": "scan_inplace_batches",
                  "scanWaitTime": "scan_wait_ns",
                  "scanTime": "scan_upload_ns",
                  "prefetchWaitTime": "prefetch_wait_ns",
                  "prefetchEarlyStarts": "prefetch_early_starts"}
# the join execs' counters (exec/join.py JOIN_COUNTERS): build time, which
# path answered each pair, capacity relaunches, host reads of device scalars
_PHASE_METRICS.update((name, key) for name, (_, _, key)
                      in _JOIN_COUNTERS.items() if key)
# the grouped aggregate: batches the Pallas lane took, how each batch's
# groups were found (exec/aggregate.py LANE_COUNTERS), and the batches
# whose filter a fused chain handed to the aggregate as its mask
_PHASE_METRICS.update(_LANE_COUNTERS)
_PHASE_METRICS["aggMaskedFilterBatches"] = "agg_masked_filter_batches"
# the shuffle exchange's map batches read whole and cut into partitions
_PHASE_METRICS.update(_EXCHANGE_COUNTERS)
# the batches a filter compacted or an exchange cut with their fixed-width
# columns carried through the sort, and with every plane gathered
_PHASE_METRICS.update(_COMPACT_COUNTERS)


#: a gauge's peak, not a sum: the largest over the plan's operators
_PHASE_PEAKS = {"scanReaderThreadsPeak": "reader_threads_peak"}

#: what the thread that pulls the plan spent of ``execute_ns`` standing
#: in a prefetch queue, at the device gate, in launches and in device
#: reads; ``consumer_host_ns`` is the rest of ``execute_ns``, its own
#: work, so that these five add up to ``execute_ns`` exactly
CONSUMER_PHASES = ("consumer_scan_wait_ns", "consumer_semaphore_wait_ns",
                   "consumer_dispatch_ns", "consumer_readback_ns")

#: the phases a query's session clocks itself, in the order a request
#: meets them; ``serve_ns`` is the server's (serve/server.py), 0 for a
#: query no server ran. With ``_PHASE_METRICS``' and ``_PHASE_PEAKS``'
#: values, every key a record's ``phases`` holds
TIMED_PHASES = ("parse_ns", "plan_ns", "admission_wait_ns", "execute_ns",
                "fetch_ns", "dispatch_ns", "dispatch_cpu_ns", "launches",
                "semaphore_wait_ns", "readback_ns", "readbacks",
                *CONSUMER_PHASES, "consumer_host_ns", "serve_ns")


def _query_phases(ctx_metrics: Dict, **timed) -> Dict[str, int]:
    """The ``phases`` of one query's record: where its wall went, each
    number measured where the work happens (docs/OBSERVABILITY.md,
    "Host ranges and query phases"). ``timed`` are the phases the
    session clocks itself; the scan's and the pipeline's come from the
    operators' metrics."""
    phases = dict.fromkeys(TIMED_PHASES, 0)
    phases.update(timed)
    phases.update((key, 0) for key in _PHASE_METRICS.values())
    phases.update((key, 0) for key in _PHASE_PEAKS.values())
    for metrics in ctx_metrics.values():
        for name, key in _PHASE_METRICS.items():
            metric = metrics.get(name)
            if metric is not None:
                phases[key] += int(metric.value)
        for name, key in _PHASE_PEAKS.items():
            metric = metrics.get(name)
            if metric is not None:
                phases[key] = max(phases[key], int(metric.value))
    return phases


def _infer_value_type(sample, values=()):
    import datetime
    import decimal
    if sample is None:
        return dt.INT32
    if isinstance(sample, bool):
        return dt.BOOL
    if isinstance(sample, int):
        return dt.INT64
    if isinstance(sample, float):
        return dt.FLOAT64
    if isinstance(sample, str):
        return dt.STRING
    if isinstance(sample, datetime.datetime):
        return dt.TIMESTAMP
    if isinstance(sample, datetime.date):
        return dt.DATE
    if isinstance(sample, decimal.Decimal):
        exp = -sample.as_tuple().exponent
        return dt.DecimalType(18, max(exp, 0))
    if isinstance(sample, (list, tuple)):
        elems = [e for v in values if v is not None for e in v
                 if e is not None] or \
            [e for e in sample if e is not None]
        et = _infer_value_type(elems[0], elems) if elems else dt.INT64
        return dt.ArrayType(et)
    if isinstance(sample, dict):
        return dt.StructType(tuple(
            (k, _infer_value_type(v)) for k, v in sample.items()))
    raise TypeError(f"cannot infer dtype for value {sample!r}")


def _infer_schema(data: Dict[str, list]) -> List:
    schema = []
    for name, values in data.items():
        sample = next((v for v in values if v is not None), None)
        schema.append((name, _infer_value_type(sample, values)))
    return schema


def _to_expr(c) -> Expression:
    if isinstance(c, Expression):
        return c
    if isinstance(c, str):
        return col(c)
    return lit(c)


class DeviceColumns(dict):
    """Mapping of {name: (data, validity)} device arrays with the live
    row count — arrays are capacity-padded past ``num_rows``."""

    def __init__(self, cols: dict, num_rows: int):
        super().__init__(cols)
        self.num_rows = num_rows


def _extract_windows(plan: L.LogicalPlan, exprs):
    """Pull WindowExpressions out of a projection list into Window nodes
    (the analyzer step Spark performs for window functions in select):
    one Window node per distinct (partition_by, order_by) spec, chained;
    the projection then references the produced columns by name. Window
    expressions NESTED inside larger expressions (the TPC-DS
    ``sum(x)*100/sum(sum(x)) over (...)`` ratio shape) extract the same
    way — the surrounding arithmetic stays in the projection and reads
    the generated column."""
    from ..expr import conditional as Cond
    from ..expr.window import WindowExpression
    groups = {}  # spec signature -> [(WindowExpression, gen_name)]
    counter = [0]

    def pull(e):
        if isinstance(e, WindowExpression):
            # always a fresh internal name: a user alias may collide
            # with an input column, and name lookup resolves
            # first-match
            gen = f"__w{counter[0]}"
            counter[0] += 1
            sig = (repr(e.spec.partition_by),
                   repr([(repr(o.expr), o.ascending, o.nulls_first)
                         for o in e.spec.order_fields]))
            groups.setdefault(sig, []).append((e, gen))
            return col(gen)
        if isinstance(e, Cond.CaseWhen):
            return Cond.CaseWhen(
                [(pull(c), pull(v)) for c, v in e.branches],
                pull(e.otherwise) if e.otherwise is not None else None)
        if not e.children:
            return e
        out = e.__class__.__new__(e.__class__)
        out.__dict__.update(e.__dict__)
        out.children = [pull(c) for c in e.children]
        return out

    out_exprs = []
    for i, e in enumerate(exprs):
        if isinstance(e, Alias):
            out_exprs.append(Alias(pull(e.children[0]), e.name))
        elif isinstance(e, WindowExpression):
            out_exprs.append(Alias(pull(e), f"_w{i}"))
        else:
            out_exprs.append(pull(e))
    for _, wexprs in groups.items():
        plan = L.Window(plan, wexprs)
    return plan, out_exprs


def _extract_generators(plan: L.LogicalPlan, exprs):
    """Pull Explode generators out of a projection into a Generate node
    (the analyzer step Spark performs for explode() in select): at most
    one generator per projection, like Spark."""
    from ..expr.collections import Explode
    out_exprs = []
    gen_count = 0
    for i, e in enumerate(exprs):
        inner = e.children[0] if isinstance(e, Alias) else e
        if isinstance(inner, Explode):
            gen_count += 1
            if gen_count > 1:
                raise ValueError("only one generator allowed per select")
            user = e.name if isinstance(e, Alias) else "col"
            if inner.with_position:
                pos_name = f"__gpos{i}"
                plan = L.Generate(plan, inner, f"__gen{i}", pos_name)
                out_exprs.append(Alias(col(pos_name), "pos"))
            else:
                plan = L.Generate(plan, inner, f"__gen{i}")
            out_exprs.append(Alias(col(f"__gen{i}"), user))
        else:
            out_exprs.append(e)
    return plan, out_exprs


class DataFrame:
    """Lazy logical-plan builder (Spark DataFrame analogue)."""

    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self.session = session
        self.plan = plan
        #: ns ``session.sql()`` spent parsing and analyzing the text
        #: this frame came from; its first execution reports and
        #: clears it
        self.parse_ns = 0

    def _run(self, timeout: Optional[float] = None
             ) -> Tuple[HostTable, dict]:
        parse_ns, self.parse_ns = self.parse_ns, 0
        return self.session._execute_recorded(self.plan, timeout,
                                              parse_ns=parse_ns)

    def _fetch(self, build, timeout: Optional[float] = None):
        """Run the query and hand its table to ``build``; the time
        ``build`` takes is the caller's share of ``result.fetch``."""
        table, rec = self._run(timeout)
        t0 = _time.perf_counter_ns()
        with annotate("result.fetch"):
            out = build(table)
        rec["phases"]["fetch_ns"] += _time.perf_counter_ns() - t0
        return out

    # --- transformations ---
    def select(self, *cols) -> "DataFrame":
        exprs = [_to_expr(c) for c in cols]
        plan, exprs = _extract_generators(self.plan, exprs)
        plan, exprs = _extract_windows(plan, exprs)
        return DataFrame(self.session, L.Project(plan, exprs))

    def with_column(self, name: str, expr) -> "DataFrame":
        existing = [col(n) for n, _ in self.plan.schema if n != name]
        exprs = existing + [Alias(_to_expr(expr), name)]
        plan, exprs = _extract_windows(self.plan, exprs)
        return DataFrame(self.session, L.Project(plan, exprs))

    def filter(self, condition) -> "DataFrame":
        return DataFrame(self.session,
                         L.Filter(self.plan, _to_expr(condition)))

    where = filter

    def group_by(self, *cols) -> "GroupedData":
        return GroupedData(self, [_to_expr(c) for c in cols])

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on, how: str = "inner"
             ) -> "DataFrame":
        how = {"inner": "inner", "left": "left_outer",
               "left_outer": "left_outer", "right": "right_outer",
               "right_outer": "right_outer", "full": "full_outer",
               "full_outer": "full_outer", "outer": "full_outer",
               "semi": "left_semi", "left_semi": "left_semi",
               "anti": "left_anti", "left_anti": "left_anti",
               "cross": "cross"}[how]
        if isinstance(on, str):
            on = [on]
        using: List[str] = []
        if isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            using = list(on)
            lk = [col(n) for n in on]
            rk = [col(n) for n in on]
        elif isinstance(on, tuple) and len(on) == 2:
            lk, rk = [_to_expr(e) for e in on[0]], \
                [_to_expr(e) for e in on[1]]
        else:
            raise TypeError("join `on`: column name(s) or (left_exprs, "
                            "right_exprs)")
        joined = L.Join(self.plan, other.plan, lk, rk, how)
        # USING semantics: emit the key once. left's copy is the correct
        # survivor for inner/left/semi/anti; other types keep both.
        if using and how in ("inner", "left_outer", "left_semi",
                             "left_anti"):
            keep = [col(n) for n in self.columns]
            if how in ("inner", "left_outer"):
                keep += [col(n) for n in other.columns if n not in using]
                # name-based refs resolve to the first (left) occurrence;
                # right non-key columns are unique by assumption
            joined = L.Project(joined, keep)
        return DataFrame(self.session, joined)

    def cross_join(self, other: "DataFrame",
                   condition: Optional[Expression] = None) -> "DataFrame":
        """Cartesian product, optionally with a non-equi condition
        (nested-loop join on device)."""
        how = "cross" if condition is None else "inner"
        return DataFrame(self.session,
                         L.Join(self.plan, other.plan, [], [], how,
                                condition=condition))

    def sort(self, *cols, ascending: TUnion[bool, Sequence[bool]] = True
             ) -> "DataFrame":
        exprs = [_to_expr(c) for c in cols]
        if isinstance(ascending, bool):
            ascending = [ascending] * len(exprs)
        order = [L.SortField(e, a) for e, a in zip(exprs, ascending)]
        return DataFrame(self.session, L.Sort(self.plan, order))

    order_by = sort

    def sort_desc(self, *cols) -> "DataFrame":
        return self.sort(*cols, ascending=False)

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, L.Limit(self.plan, n))

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        return DataFrame(self.session, L.Sample(self.plan, fraction,
                                                seed))

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session, L.Union(self.plan, other.plan))

    def distinct(self) -> "DataFrame":
        return DataFrame(self.session, L.Distinct(self.plan))

    # --- metadata ---
    @property
    def schema(self) -> List:
        return self.plan.schema

    @property
    def columns(self) -> List[str]:
        return [n for n, _ in self.plan.schema]

    def __getitem__(self, name: str) -> ColumnRef:
        if name not in self.columns:
            raise KeyError(name)
        return col(name)

    # --- actions ---
    def collect(self, timeout: Optional[float] = None) -> List[dict]:
        """Run the query and return rows. ``timeout`` (seconds) arms a
        per-call deadline — the query tears down cleanly and raises
        DeadlineExceeded on expiry; overrides ``srt.sql.queryTimeout``."""
        def rows(table: HostTable) -> List[dict]:
            data = to_pydict(table)
            names = list(data.keys())
            return [{k: data[k][i] for k in names}
                    for i in range(table.num_rows)]
        return self._fetch(rows, timeout)

    def to_pydict(self) -> dict:
        return self._fetch(to_pydict)

    def to_pandas(self):
        import pandas as pd
        return pd.DataFrame(self.to_pydict())

    def count(self) -> int:
        return self._run()[0].num_rows

    @property
    def write(self):
        from ..io.writer import DataFrameWriter
        return DataFrameWriter(self)

    def cache(self) -> "DataFrame":
        """Materialize once into compressed host blocks; further use
        re-reads the cache (ParquetCachedBatchSerializer role)."""
        from ..cache import cache_dataframe
        return cache_dataframe(self)

    def unpersist(self) -> "DataFrame":
        """Release a cached DataFrame's blocks (memory + disk) and
        unregister it from the session cache registry."""
        from ..cache import CachedRelation
        if isinstance(self.plan, CachedRelation):
            self.plan.unpersist()
        return self

    def to_device_arrays(self) -> "DeviceColumns":
        """Zero-copy ML export (ColumnarRdd.scala:42 role — the
        reference hands cuDF tables to XGBoost; here downstream jax ML
        code consumes the columns directly). Returns a DeviceColumns:
        mapping of {name: (data jax.Array, validity)} plus ``num_rows``
        — arrays are capacity-padded, so consumers MUST slice to
        num_rows (padding rows are indistinguishable from nulls by
        validity alone)."""
        from .. import ops  # noqa: F401
        from ..exec.base import ExecContext, TpuExec
        from ..ops import kernels as K
        from ..columnar.vector import choose_capacity
        from . import overrides as O
        physical = O.apply_overrides(self.plan, self.session.conf)
        ctx = ExecContext(self.session.conf)
        if isinstance(physical, TpuExec):
            batches = [b for b in physical.execute(ctx)
                       if int(b.num_rows) > 0]
        else:
            from .host_table import table_to_batch
            batches = [table_to_batch(physical.evaluate(ctx))]
        if not batches:
            return DeviceColumns({}, 0)
        total = sum(int(b.num_rows) for b in batches)
        merged = batches[0] if len(batches) == 1 else \
            K.concat_batches(batches, choose_capacity(total))
        cols = {name: (c.data if not hasattr(c, "chars") else
                       (c.offsets, c.chars), c.validity)
                for name, c in zip(merged.names, merged.columns)}
        return DeviceColumns(cols, int(merged.num_rows))

    def explain(self, mode: str = "ALL", metrics: bool = False) -> str:
        if metrics:
            return self._explain_metrics()
        meta = overrides.tag_only(self.plan)
        out = "\n".join(meta.explain_lines(
            only_not_on_tpu=(mode == "NOT_ON_TPU")))
        print(out)
        return out

    def _explain_metrics(self) -> str:
        """Execute the query, then render the physical tree with each
        operator's accumulated metrics (rows / batches / op-time /
        shuffle bytes; the reference SQL-UI annotation role) plus a
        query-level footer with wall time and spill totals."""
        from ..conf import METRICS_LEVEL
        self._run()
        last = self.session._last_execution
        physical, ctx = last["physical"], last["ctx"]
        level = self.session.conf.get(METRICS_LEVEL)
        if isinstance(physical, TpuExec):
            body = _metrics_tree_lines(physical, ctx.metrics, level)
        else:
            body = [f"* {type(physical).__name__} (CPU fallback path)"]
        rec = last["record"]
        totals = rec["totals"]
        footer = (f"query {last['query_id']}: "
                  f"wall={last['wall_ns'] / 1e6:.1f}ms "
                  f"opTime={totals['opTimeNs'] / 1e6:.1f}ms "
                  f"rows={totals['numOutputRows']} "
                  f"shuffleBytes={totals['shuffleBytesWritten']} "
                  f"spilledBytes={rec.get('spilled_bytes', 0)} "
                  f"oomRetries={rec.get('oom_retries', 0)}")
        out = "\n".join(body + [footer])
        print(out)
        return out

    def __repr__(self):
        cols = ", ".join(f"{n}: {t}" for n, t in self.plan.schema)
        return f"DataFrame[{cols}]"


def _metrics_tree_lines(node: TpuExec, metrics: Dict, level: str,
                        indent: int = 0) -> List[str]:
    """Physical tree lines with per-operator metric annotations,
    filtered by the configured metrics level."""
    from ..obs.registry import level_allows
    line = "  " * indent + "* " + node.node_description()
    m = metrics.get(node.exec_id, {})
    parts = []
    for name in sorted(m):
        met = m[name]
        if not level_allows(level, met.level):
            continue
        if met.unit == "ns":
            parts.append(f"{name}={met.value / 1e6:.1f}ms")
        else:
            parts.append(f"{name}={met.value}{met.unit}")
    if parts:
        line += "  [" + ", ".join(parts) + "]"
    lines = [line]
    for c in node.children:
        if isinstance(c, TpuExec):
            lines.extend(_metrics_tree_lines(c, metrics, level,
                                             indent + 1))
    return lines


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Expression]):
        self.df = df
        self.keys = keys

    def agg(self, *aggs) -> DataFrame:
        pairs = []
        for i, a in enumerate(aggs):
            if isinstance(a, Alias):
                pairs.append((a.children[0], a.name))
            else:
                pairs.append((a, output_name(a, len(self.keys) + i)))
        return DataFrame(self.df.session,
                         L.Aggregate(self.df.plan, self.keys, pairs))

    def count(self) -> DataFrame:
        return self.agg(Alias(CountStar(), "count"))

    def _simple(self, fn_cls, cols) -> DataFrame:
        return self.agg(*[Alias(fn_cls(_to_expr(c)), f"{fn_cls.name}({c})")
                          for c in cols])

    def sum(self, *cols) -> DataFrame:
        return self._simple(Sum, cols)

    def min(self, *cols) -> DataFrame:
        return self._simple(Min, cols)

    def max(self, *cols) -> DataFrame:
        return self._simple(Max, cols)

    def avg(self, *cols) -> DataFrame:
        return self._simple(Average, cols)

    mean = avg
