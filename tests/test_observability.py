"""Observability subsystem tests (spark_rapids_tpu/obs/):

- span tracer: nesting/parentage, Chrome-trace export validity;
- event log: JSONL round-trip through a real query and the offline
  ``tools/profile_report.py`` analyzer;
- metrics registry: level gating (ESSENTIAL < MODERATE < DEBUG),
  per-query summaries, Prometheus text;
- the zero-overhead contract: a session with observability disabled
  installs no sink and hands operators no tracer;
- SelfTimer exception-path hardening: abandoned frames are torn down
  with no double-charged parent time;
- NDS profile smoke: one NDS query end-to-end with the event log on,
  profiled offline — summed exclusive ESSENTIAL op-times must fit
  inside the measured wall clock;
- program names, host ranges and query phases: every jitted program's
  HLO module is ``jit_<registry label>``; a parquet query's record
  carries its ``phases``; the ranges land in a ``jax.profiler`` trace on
  the threads that feed the device, decode does not.
"""

import json
import os
import sys
import time

import pytest

from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.base import ExecContext, Metric, SelfTimer
from spark_rapids_tpu.obs import events
from spark_rapids_tpu.obs.registry import (MetricsRegistry, level_allows,
                                           query_totals, summarize_metrics)
from spark_rapids_tpu.obs.trace import Tracer, maybe_tracer
from spark_rapids_tpu.plan.session import TpuSession
from spark_rapids_tpu.testing.asserts import assert_consumer_partition

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
import profile_report  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_sink():
    """Every test starts and ends with no process-wide event sink, so
    sink state never leaks between tests (or into other test files —
    the sink is module-global by design)."""
    events.install(None)
    yield
    events.install(None)


def _session(tmp_path=None, trace=False):
    settings = {"srt.shuffle.partitions": 2}
    if tmp_path is not None:
        settings["srt.eventLog.enabled"] = "true"
        settings["srt.eventLog.dir"] = str(tmp_path)
        if trace:
            settings["srt.eventLog.trace.enabled"] = "true"
    return TpuSession(SrtConf(settings))


def _run_small_query(session):
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import Alias
    df = session.create_dataframe(
        {"k": [i % 5 for i in range(200)],
         "v": [float(i) for i in range(200)]})
    return df.group_by("k").agg(Alias(Sum(col("v")), "s")).sort("k") \
        .collect()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_scoped():
    tr = Tracer()
    with tr.span("query", kind="query") as q:
        with tr.span("stage", kind="stage") as st:
            with tr.span("task", kind="task") as tk:
                assert tr.current_id() == tk.span_id
        assert tr.current_id() == q.span_id
    spans = {s.name: s for s in tr.spans()}
    assert spans["query"].parent_id is None
    assert spans["stage"].parent_id == spans["query"].span_id
    assert spans["task"].parent_id == spans["stage"].span_id
    for s in spans.values():
        assert s.t1_ns is not None and s.t1_ns >= s.t0_ns


def test_span_explicit_parent_defaults_to_open_scope():
    tr = Tracer()
    with tr.span("query", kind="query") as q:
        op = tr.begin("HashAggregateExec", kind="operator")
        tr.end(op)
    assert op.parent_id == q.span_id
    explicit = tr.begin("child", parent=op.span_id)
    tr.end(explicit)
    assert explicit.parent_id == op.span_id


def test_span_scope_survives_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("query", kind="query"):
            with tr.span("op", kind="operator"):
                raise RuntimeError("boom")
    assert tr.current_id() is None  # stack fully unwound
    assert all(s.t1_ns is not None for s in tr.spans())


def test_chrome_trace_export_valid(tmp_path):
    tr = Tracer()
    with tr.span("q1", kind="query", attrs={"rows": 10}):
        with tr.span("FilterExec", kind="operator"):
            pass
        tr.instant("SpillToHost", attrs={"bytes": 4096})
    path = tr.write_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())  # must be valid JSON
    evs = doc["traceEvents"]
    assert len(evs) == 3
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float))
        assert e["pid"] == os.getpid()
    by_name = {e["name"]: e for e in evs}
    assert by_name["q1"]["ph"] == "X"
    assert by_name["q1"]["args"]["rows"] == 10
    assert by_name["SpillToHost"]["ph"] == "i"
    assert by_name["FilterExec"]["args"]["parent_id"] == \
        by_name["q1"]["args"]["span_id"]
    assert by_name["q1"]["dur"] >= by_name["FilterExec"]["dur"] >= 0


def test_maybe_tracer_gated_by_conf():
    assert maybe_tracer(SrtConf({})) is None
    assert maybe_tracer(
        SrtConf({"srt.eventLog.trace.enabled": "true"})) is not None


# ---------------------------------------------------------------------------
# event log round-trip
# ---------------------------------------------------------------------------

def test_event_log_roundtrip_through_query(tmp_path):
    session = _session(tmp_path)
    rows = _run_small_query(session)
    assert [r["k"] for r in rows] == [0, 1, 2, 3, 4]
    files = list(events.iter_log_files(str(tmp_path)))
    assert files, "enabled event log wrote no events-*.jsonl"
    recs = events.read_all_events(str(tmp_path))
    kinds = [r["event"] for r in recs]
    assert "QueryStart" in kinds and "QueryEnd" in kinds
    start = next(r for r in recs if r["event"] == "QueryStart")
    end = next(r for r in recs if r["event"] == "QueryEnd")
    assert start["query_id"] == end["query_id"]
    # tree_string of the physical plan rides along on QueryStart
    assert "HashAggregate" in start["plan"]
    assert end["status"] == "ok" and end["wall_ns"] > 0
    # every record carries the envelope fields
    for r in recs:
        assert r["event"] in events.EVENT_TYPES
        assert isinstance(r["ts"], float) and r["pid"] == os.getpid()


def test_event_log_torn_line_skipped(tmp_path):
    w = events.EventLogWriter(str(tmp_path))
    w.emit("QueryStart", query_id="q1")
    w.emit("QueryEnd", query_id="q1", status="ok")
    w.close()
    with open(w.path, "a") as f:
        f.write('{"event": "QueryEnd", "truncat')  # crash-torn tail
    recs = events.read_events(w.path)
    assert [r["event"] for r in recs] == ["QueryStart", "QueryEnd"]


def test_profile_report_roundtrip(tmp_path):
    session = _session(tmp_path)
    _run_small_query(session)
    reports = profile_report.report(str(tmp_path))
    assert len(reports) == 1
    rep = reports[0]
    assert rep["status"] == "ok"
    assert rep["wall_ns"] > 0
    assert rep["operators"], "no per-operator breakdown"
    assert any(o["op_time_ns"] > 0 for o in rep["operators"])
    # exclusive op-times are disjoint PER THREAD: pipelined producer
    # threads (exec/pipeline.py) may push the raw sum past the wall;
    # the busy/wait/overlap decomposition must stay consistent
    assert rep["op_time_ns"] > 0
    cp = rep["critical_path"]
    assert 0 < cp["busy_ns"] <= rep["op_time_ns"]
    assert cp["wait_ns"] == max(rep["wall_ns"] - cp["busy_ns"], 0)
    assert cp["overlap_ns"] == max(cp["busy_ns"] - rep["wall_ns"], 0)
    # the rendered report and the CLI agree on content
    text = profile_report.render(rep)
    assert rep["query_id"] in text and "op-time breakdown" in text
    assert profile_report.main([str(tmp_path)]) == 0
    assert profile_report.main([str(tmp_path / "nope")]) == 2


def test_profile_report_attributes_windowed_events(tmp_path):
    w = events.EventLogWriter(str(tmp_path))
    w.emit("QueryStart", query_id="qA")
    w.emit("SpillToHost", bytes=1024)
    w.emit("RetryAttempt", scope="oom", kind="retry")
    w.emit("QueryEnd", query_id="qA", status="ok", wall_ns=10,
           metrics={}, spilled_bytes=1024, oom_retries=1)
    w.close()
    time.sleep(0.01)
    w2 = events.EventLogWriter(str(tmp_path))
    w2.emit("SpillToHost", bytes=999)  # after qA ended: unattributed
    w2.close()
    rep = profile_report.report(str(tmp_path), query_id="qA")[0]
    assert rep["spill"]["to_host"] == 1
    assert rep["spill"]["bytes"] == 1024
    assert rep["retries"] == {"oom": 1, "by_scope": {"oom": 1}}


# ---------------------------------------------------------------------------
# metrics levels + registry
# ---------------------------------------------------------------------------

def test_level_gating():
    assert level_allows("DEBUG", "ESSENTIAL")
    assert level_allows("MODERATE", "MODERATE")
    assert not level_allows("ESSENTIAL", "MODERATE")
    assert not level_allows("MODERATE", "DEBUG")
    ctx_metrics = {"FilterExec#1": {
        "opTime": Metric("opTime", Metric.ESSENTIAL, "ns"),
        "numOutputRows": Metric("numOutputRows", Metric.MODERATE),
        "peakDeviceMemory": Metric("peakDeviceMemory", Metric.DEBUG, "B"),
    }}
    for m in ctx_metrics["FilterExec#1"].values():
        m.add(7)
    essential = summarize_metrics(ctx_metrics, "ESSENTIAL")
    assert set(essential["FilterExec#1"]) == {"opTime"}
    moderate = summarize_metrics(ctx_metrics, "MODERATE")
    assert set(moderate["FilterExec#1"]) == {"opTime", "numOutputRows"}
    debug = summarize_metrics(ctx_metrics, "DEBUG")
    assert len(debug["FilterExec#1"]) == 3
    assert debug["FilterExec#1"]["opTime"] == \
        {"value": 7, "level": "ESSENTIAL", "unit": "ns"}


def test_registry_records_and_exports():
    reg = MetricsRegistry(max_queries=2)
    summary = {"ScanExec#0": {"opTime": {"value": 100,
                                         "level": "ESSENTIAL",
                                         "unit": "ns"},
                              "numOutputRows": {"value": 42,
                                                "level": "ESSENTIAL",
                                                "unit": ""}}}
    reg.record_query("q1", summary, wall_ns=250, status="ok")
    reg.record_query("q2", {}, wall_ns=50, status="error")
    snap = reg.snapshot()
    assert snap["counters"]["queries_total"] == 2
    assert snap["counters"]["queries_failed_total"] == 1
    assert snap["counters"]["op_time_ns_total"] == 100
    assert snap["counters"]["output_rows_total"] == 42
    assert query_totals(summary)["opTimeNs"] == 100
    reg.record_query("q3", summary, wall_ns=10)  # bounded deque
    assert [q["query_id"] for q in reg.queries()] == ["q2", "q3"]
    assert reg.snapshot()["counters"]["queries_total"] == 3
    prom = reg.prometheus_text()
    assert "srt_queries_total 3" in prom
    assert 'srt_last_query_op_time_ns{exec_id="ScanExec#0"} 100' in prom


def test_session_records_query_in_registry():
    from spark_rapids_tpu.obs.registry import registry
    before = registry().snapshot()["counters"]["queries_total"]
    session = _session()
    _run_small_query(session)
    snap = registry().snapshot()
    assert snap["counters"]["queries_total"] == before + 1
    last = snap["queries"][-1]
    assert last["status"] == "ok" and last["wall_ns"] > 0
    assert_consumer_partition(last["phases"])
    assert last["totals"]["opTimeNs"] > 0
    assert session._last_execution["record"] is last or \
        session._last_execution["record"] == last


def test_explain_metrics_renders_annotated_tree():
    session = _session()
    df = session.create_dataframe({"k": [1, 2, 2], "v": [1.0, 2.0, 3.0]})
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import Alias
    out = df.group_by("k").agg(Alias(Sum(col("v")), "s")) \
        .explain(metrics=True)
    assert "opTime=" in out and "numOutputRows=" in out
    assert "wall=" in out and "rows=" in out  # footer totals


# ---------------------------------------------------------------------------
# zero-overhead disabled path
# ---------------------------------------------------------------------------

def test_disabled_session_installs_nothing():
    session = _session()  # no eventLog confs
    _run_small_query(session)
    assert not events.enabled()
    assert events._SINK is None  # no sink object was ever created
    assert session._last_execution["ctx"].tracer is None


def test_conf_managed_sink_torn_down_by_disabled_conf(tmp_path):
    enabled = _session(tmp_path)
    _run_small_query(enabled)
    assert events.enabled()
    disabled = _session()
    _run_small_query(disabled)
    assert not events.enabled()  # conf-managed sink removed


def test_emit_disabled_is_cheap():
    # the contract is "one global is-None check"; guard against a
    # regression that starts allocating/formatting on the disabled path
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        events.emit("TaskEnd", rows=1, metrics={"a": 1})
    dt = time.perf_counter() - t0
    assert dt < 0.5, f"disabled emit too slow: {dt:.3f}s for {n} calls"


# ---------------------------------------------------------------------------
# SelfTimer exception-path hardening
# ---------------------------------------------------------------------------

def test_selftimer_exception_unwinds_stack():
    stack = []
    m = Metric("opTime", Metric.ESSENTIAL, "ns")
    with pytest.raises(RuntimeError):
        with SelfTimer(stack, m, "op"):
            raise RuntimeError("boom")
    assert stack == []
    assert m.value > 0


def test_selftimer_abandoned_frames_no_double_count():
    """A generator torn down by an exception can leave child frames on
    the stack when an ancestor's __exit__ runs. The ancestor must
    discard them, charge only the deepest (actually-running) frame,
    and leave the stack consistent — total accounted time can never
    exceed the wall clock."""
    stack = []
    mp = Metric("parent", Metric.ESSENTIAL, "ns")
    mc = Metric("child", Metric.ESSENTIAL, "ns")
    mg = Metric("grandchild", Metric.ESSENTIAL, "ns")
    t_wall0 = time.perf_counter_ns()
    parent = SelfTimer(stack, mp, "parent")
    parent.__enter__()
    child = SelfTimer(stack, mc, "child")
    child.__enter__()
    grand = SelfTimer(stack, mg, "grandchild")
    grand.__enter__()
    time.sleep(0.01)
    # exception path: child and grandchild never see __exit__; the
    # parent's __exit__ fires directly (finally in the outer frame)
    parent.__exit__(None, None, None)
    wall = time.perf_counter_ns() - t_wall0
    assert stack == []
    # the grandchild was the running frame: it gets the sleep
    assert mg.value >= 10_000_000
    # exclusive times stay disjoint even through the teardown
    assert mp.value + mc.value + mg.value <= wall


def test_selftimer_nested_exclusive_times():
    stack = []
    mp = Metric("parent", Metric.ESSENTIAL, "ns")
    mc = Metric("child", Metric.ESSENTIAL, "ns")
    t0 = time.perf_counter_ns()
    with SelfTimer(stack, mp, "parent"):
        time.sleep(0.005)
        with SelfTimer(stack, mc, "child"):
            time.sleep(0.005)
        time.sleep(0.005)
    wall = time.perf_counter_ns() - t0
    assert stack == []
    assert mc.value >= 5_000_000
    assert mp.value >= 10_000_000
    assert mp.value + mc.value <= wall


def test_selftimer_reentry_after_exception():
    """The shared per-context stack stays usable for the next operator
    pull after an exception-skewed unwind."""
    stack = []
    m1 = Metric("a", Metric.ESSENTIAL, "ns")
    inner = SelfTimer(stack, m1, "a")
    outer = SelfTimer(stack, Metric("o", Metric.ESSENTIAL, "ns"), "o")
    outer.__enter__()
    inner.__enter__()
    outer.__exit__(None, None, None)  # inner abandoned
    assert stack == []
    m2 = Metric("b", Metric.ESSENTIAL, "ns")
    with SelfTimer(stack, m2, "b"):
        pass
    assert stack == [] and m2.value >= 0


def test_selftimer_emits_operator_spans():
    tracer = Tracer()
    stack = []
    with tracer.span("q", kind="query") as q:
        with SelfTimer(stack, Metric("opTime"), "ScanExec#0", tracer):
            with SelfTimer(stack, Metric("opTime"), "FilterExec#1",
                           tracer):
                pass
    spans = {s.name: s for s in tracer.spans() if s.kind == "operator"}
    assert set(spans) == {"ScanExec#0", "FilterExec#1"}
    assert spans["ScanExec#0"].parent_id == q.span_id
    assert spans["FilterExec#1"].parent_id == spans["ScanExec#0"].span_id


def test_query_trace_written(tmp_path):
    session = _session(tmp_path, trace=True)
    _run_small_query(session)
    qid = session._last_execution["query_id"]
    path = tmp_path / f"trace-{qid}.json"
    assert path.exists()
    doc = json.loads(path.read_text())
    kinds = {e["cat"] for e in doc["traceEvents"]}
    assert "query" in kinds and "operator" in kinds
    # spans nest inside the query span on the same monotonic timeline
    ctx = session._last_execution["ctx"]
    assert isinstance(ctx, ExecContext) and ctx.tracer is not None


# ---------------------------------------------------------------------------
# NDS profile smoke (fast tier): one real star-join query, event log
# on, profiled offline — the acceptance check from the subsystem spec
# ---------------------------------------------------------------------------

def test_nds_q3_profile_smoke(tmp_path):
    from spark_rapids_tpu.datagen import generate_table
    from spark_rapids_tpu.models.nds import NDS_QUERIES, nds_specs
    needed = {"store_sales", "date_dim", "item"}
    session = _session(tmp_path / "events")
    data_dir = tmp_path / "nds"
    for spec in nds_specs(3_000):
        if spec.name not in needed:
            continue
        out = str(data_dir / spec.name)
        generate_table(session, spec, out, chunk_rows=1 << 16)
        session.create_or_replace_temp_view(
            spec.name, session.read.parquet(out))
    rows = session.sql(NDS_QUERIES["q3"]).collect()
    assert isinstance(rows, list)  # may legitimately be empty at 3k
    # two joins: the split of the pulling thread's wall still adds up
    phases = session._last_execution["record"]["phases"]
    assert phases["join_readbacks"] >= 1
    assert phases["readbacks"] >= phases["join_readbacks"]
    assert_consumer_partition(phases)
    reports = profile_report.report(str(tmp_path / "events"))
    # datagen itself runs no queries; exactly the q3 execution shows
    assert len(reports) == 1
    rep = reports[0]
    assert rep["status"] == "ok"
    assert rep["operators"], "NDS q3 produced no operator metrics"
    assert rep["op_time_ns"] > 0
    # per-thread-disjoint op-times: the busy/wait/overlap decomposition
    # must be internally consistent (pipelined producer threads can
    # legitimately push busy past the wall — that surfaces as overlap)
    cp = rep["critical_path"]
    assert 0 < cp["busy_ns"] <= rep["op_time_ns"]
    assert cp["wait_ns"] == max(rep["wall_ns"] - cp["busy_ns"], 0)
    assert cp["overlap_ns"] == max(cp["busy_ns"] - rep["wall_ns"], 0)
    names = " ".join(o["exec_id"] for o in rep["operators"])
    assert "Exec" in names
    text = profile_report.render(rep)
    assert "critical path" in text


# ---------------------------------------------------------------------------
# program names, host ranges on the profiler's clock, query phases
# ---------------------------------------------------------------------------

def test_program_names_are_registry_labels():
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import (ColumnVector,
                                                  ColumnarBatch, live_mask)
    from spark_rapids_tpu.exec import (BatchScanExec, FilterExec,
                                       LocalLimitExec)
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.ops import kernels as K
    n = 64
    b = ColumnarBatch([ColumnVector(jnp.arange(n, dtype=jnp.int64),
                                    live_mask(n, n), dt.INT64)], ["x"], n)
    scan = BatchScanExec([b], [("x", dt.INT64)])

    def module(program, *args):
        return program.lower(*args).as_text().split()[1]

    f1 = FilterExec(scan, col("x") < lit(31_337))
    f2 = FilterExec(scan, col("x") < lit(31_337))
    assert f1._jit is f2._jit  # shared by key, whatever the name
    assert f1._jit is not FilterExec(scan, col("x") < lit(31_338))._jit
    # shared_method_jit, shared_fn_jit, and the two private jits of
    # ops/kernels.py: no _filter / run / <lambda> left in a name
    assert module(f1._jit, b) == "@jit_FilterExec._filter"
    assert module(LocalLimitExec(scan, 5)._jit, b, jnp.int64(3)) \
        == "@jit__local_limit_builder"
    K.concat_batches([b, b], 128)
    K.repack_to(b, 64)
    assert module(K._CONCAT_JIT[128], [b, b]) == "@jit_concat_batches"
    assert module(K._COMPACT_JIT[64], b) == "@jit_repack_to"


PHASE_KEYS = {"parse_ns", "plan_ns", "execute_ns", "fetch_ns",
              "scan_decode_ns", "scan_pooled_files", "scan_ahead_files",
              "scan_batches", "scan_inplace_batches",
              "scan_wait_ns", "scan_upload_ns", "prefetch_wait_ns", "prefetch_early_starts",
              "dispatch_ns", "dispatch_cpu_ns", "launches",
              # device reads (jit_registry.read_back) and the pulling
              # thread's split of execute_ns (plan/session.py)
              "readback_ns", "readbacks", "consumer_scan_wait_ns",
              "consumer_semaphore_wait_ns", "consumer_dispatch_ns",
              "consumer_readback_ns", "consumer_host_ns",
              # the waits at the two gates, the server's share (0 for a
              # query no server ran), the reader pool's gauge
              "admission_wait_ns", "semaphore_wait_ns", "serve_ns",
              "reader_threads_peak",
              # the join execs' counters (exec/join.py JOIN_COUNTERS)
              "join_build_ns", "lookup_join_batches", "hash_join_batches",
              "join_capacity_relaunches", "join_readbacks",
              "hash_join_probe_ns", "hash_join_probe_rows",
              "hash_join_candidates", "hash_join_matches",
              # the grouped aggregate's (exec/aggregate.py LANE_COUNTERS,
              # exec/fused.py aggMaskedFilterBatches)
              "pallas_batches", "groups_direct_batches",
              "groups_hash_claim_batches", "agg_masked_filter_batches",
              # the shuffle exchange's (exec/exchange.py EXCHANGE_COUNTERS)
              "exchange_whole_batches", "exchange_split_batches",
              # filters' compactions and exchanges' cuts (exec/base.py
              # COMPACT_COUNTERS)
              "compact_sorted_batches", "compact_gathered_batches"}


@pytest.fixture(scope="module")
def traced_parquet_query(tmp_path_factory):
    """One tiny SQL query over three parquet files, run once to compile
    and once under a CPU ``jax.profiler`` trace: (its registry record,
    its metrics, {host range name: thread lines it appeared on})."""
    import glob

    import jax
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from jax.profiler import ProfileData

    from spark_rapids_tpu.obs.registry import registry
    data = tmp_path_factory.mktemp("phases_data")
    for i in range(3):
        pq.write_table(
            pa.table({"a": np.arange(1000, dtype=np.int64) + 1000 * i,
                      "b": np.linspace(0.0, 1.0, 1000)}),
            str(data / f"part-{i}.parquet"))
    session = TpuSession(SrtConf({}))
    session.create_or_replace_temp_view(
        "t", session.read.parquet(str(data)))
    sql = "SELECT sum(a) AS r FROM t WHERE b < 0.5"
    assert session.sql(sql).collect() == [{"r": 1874250}]
    trace_dir = tmp_path_factory.mktemp("phases_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        session.sql(sql).collect()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    ranges = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for event in line.events:
                ranges.setdefault(event.name, set()).add((plane.name, i))
    last = session._last_execution
    assert last["record"] is registry().queries()[-1]
    return last["record"], last["ctx"].metrics, ranges


def test_query_record_carries_phases(traced_parquet_query):
    record, metrics, _ = traced_parquet_query
    phases = record["phases"]
    assert set(phases) == PHASE_KEYS
    assert all(isinstance(v, int) and v >= 0 for v in phases.values())
    assert phases["launches"] > 0 and phases["dispatch_ns"] > 0
    assert 0 < phases["dispatch_cpu_ns"] <= phases["dispatch_ns"]
    assert phases["readbacks"] >= 1 and phases["readback_ns"] > 0
    assert_consumer_partition(phases)
    assert phases["parse_ns"] > 0 and phases["plan_ns"] > 0
    assert phases["fetch_ns"] > 0
    assert phases["execute_ns"] == record["wall_ns"]
    assert phases["execute_ns"] >= phases["scan_upload_ns"] > 0
    from spark_rapids_tpu.plan.session import TIMED_PHASES
    assert set(TIMED_PHASES) <= PHASE_KEYS
    # one query, in process: nobody to queue behind or to serve
    assert phases["admission_wait_ns"] == phases["serve_ns"] == 0
    assert phases["semaphore_wait_ns"] == 0
    # three files on the reader pool: one to three threads at once
    assert 1 <= phases["reader_threads_peak"] <= 3
    totals = {}
    for per_exec in metrics.values():
        for name, metric in per_exec.items():
            totals[name] = totals.get(name, 0) + metric.value
    assert totals["scanDecodeTime"] == phases["scan_decode_ns"] > 0
    assert totals["scanWaitTime"] == phases["scan_wait_ns"] > 0
    assert totals["scanTime"] == phases["scan_upload_ns"]
    assert totals["prefetchWaitTime"] == phases["prefetch_wait_ns"]
    # no join in the plan: the scan's producer started at its first pull
    assert phases["prefetch_early_starts"] == 0
    # three files, one batch; the pyarrow lane (this backend's default)
    # places nothing
    assert totals["scanBatches"] == phases["scan_batches"] == 1
    assert phases["scan_inplace_batches"] == 0
    # the global sum's one-partition exchange writes its batch as it is
    assert totals["shuffleWholeBatches"] == \
        phases["exchange_whole_batches"] == 1
    assert phases["exchange_split_batches"] == 0
    # the filter is the aggregate's mask: nothing compacted, nothing cut
    assert phases["compact_sorted_batches"] == \
        phases["compact_gathered_batches"] == 0


def test_read_back_counts_a_device_read_once_on_the_current_query():
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.jit_registry import read_back, read_int
    from spark_rapids_tpu.robustness.admission import (QueryContext,
                                                       query_scope)
    token = QueryContext("reader")
    with query_scope(token):
        host, three = read_back(jnp.arange(4, dtype=jnp.int32), 3)
        assert isinstance(host, np.ndarray) and host.tolist() == [0, 1, 2, 3]
        assert three == 3
        # values on the host are no round trip
        assert read_int(5) == 5 and read_back(np.int32(2), 1.5) == (2, 1.5)
    assert token.readbacks == 1 and token.readback_ns > 0
    # no thread of this token pulls a plan: nothing is the consumer's
    assert token.consumer_readback_ns == 0
    # outside any query a read is charged to none
    assert read_int(jnp.int32(9)) == 9 and token.readbacks == 1


def test_host_ranges_are_in_the_profilers_trace(traced_parquet_query):
    _, _, ranges = traced_parquet_query
    for name in ("plan.parse", "plan.physical", "result.fetch",
                 "scan.wait", "scan.upload", "prefetch.wait",
                 "device.read"):
        assert name in ranges, f"no {name} range in the trace"
    launches = [n for n in ranges if n.startswith("launch.")]
    assert launches and all(" " not in n for n in launches)
    # the scan's ranges are on the prefetch producer's thread, the
    # consumer's wait and the planner's on the caller's
    assert ranges["scan.wait"] == ranges["scan.upload"]
    assert ranges["plan.physical"] == ranges["prefetch.wait"]
    # the pulling thread reads its row counts and the answer; the only
    # other reader is the shuffle's producer, which stages a device
    # batch (SpillableBatch reads its row count), never the scan's
    assert ranges["plan.physical"] <= ranges["device.read"]
    assert ranges["device.read"].isdisjoint(ranges["scan.wait"])
    assert ranges["scan.wait"].isdisjoint(ranges["plan.physical"])
    # decode is a counter only: a range on a reader-pool thread would
    # be taken for the cause of idle gaps it merely overlaps
    assert not [n for n in ranges if "decode" in n.lower()]
