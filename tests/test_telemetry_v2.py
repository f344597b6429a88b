"""Distributed telemetry v2 tests:

- log-bucketed histograms: bucket monotonicity, quantile clamping,
  Prometheus exposition (_bucket/_sum/_count/_quantile), label
  escaping, and the disabled-registry zero-allocation contract;
- event-log rotation: ``srt.eventLog.maxBytes`` rollover to ``.1``/
  ``.2`` and readers stitching segments back in write order;
- cross-process trace propagation: ``Tracer.context()`` /
  ``from_context()``, pid-namespaced span ids, clock anchors, and
  ``merge_chrome_traces`` alignment;
- prefetch producer-thread span parenting (no orphaned spans);
- the resource sampler: conf-gated start/stop and the no-thread
  zero-overhead path;
- ``tools/history_report.py``: job/shuffle aggregation and the
  advisor rules over a synthetic multi-process event log.
"""

import json
import os
import sys
import threading
import time

import pytest

from spark_rapids_tpu.conf import (EVENT_LOG_MAX_BYTES,
                                   RESOURCE_SAMPLE_INTERVAL_MS, SrtConf)
from spark_rapids_tpu.obs import events, resource
from spark_rapids_tpu.obs.registry import (Histogram, MetricsRegistry,
                                           _escape_label)
from spark_rapids_tpu.obs.trace import Tracer, merge_chrome_traces

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
import history_report  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_obs():
    """No event sink or sampler leaks in or out of any test here."""
    events.install(None)
    resource.shutdown()
    yield
    events.install(None)
    resource.shutdown()


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_buckets_cumulative_and_monotonic():
    h = Histogram("t", unit="ns")
    for v in [0, 1, 1, 2, 3, 100, 5000, 5000, 70000]:
        h.observe(v)
    assert h.count == 9 and h.sum == 80107
    buckets = h.buckets()
    les = [le for le, _ in buckets]
    cums = [c for _, c in buckets]
    assert les == sorted(les)          # bucket bounds increase
    assert cums == sorted(cums)        # cumulative counts monotonic
    assert cums[-1] == h.count         # last bucket covers everything
    # bucket 0 is exactly {0}; bucket i covers [2^(i-1), 2^i - 1]
    assert buckets[0] == (0, 1)
    assert buckets[1] == (1, 3)        # two 1s, cumulative with the 0

def test_histogram_quantiles_clamped_to_observed_range():
    h = Histogram("t")
    for v in [10, 11, 12, 13, 1000]:
        h.observe(v)
    for q in (0.5, 0.9, 0.99):
        assert 10 <= h.quantile(q) <= 1000
    assert h.quantile(0.99) == 1000   # upper bound clamps to max
    p = h.percentiles()
    assert set(p) == {"p50", "p90", "p99"}
    assert p["p50"] <= p["p90"] <= p["p99"]

def test_histogram_negative_clamped_empty_zero():
    h = Histogram("t")
    assert h.quantile(0.5) == 0       # empty histogram
    h.observe(-5)
    assert h.count == 1 and h.sum == 0
    assert h.buckets()[0] == (0, 1)

def test_histogram_snapshot_shape():
    h = Histogram("t", unit="bytes")
    h.observe(64)
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["sum"] == 64
    assert snap["min"] == 64 and snap["max"] == 64
    assert snap["unit"] == "bytes"
    assert snap["p50"] == 64


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_prometheus_histogram_exposition():
    reg = MetricsRegistry()
    for ns in [1_000_000, 2_000_000, 3_000_000, 50_000_000]:
        reg.observe("task_time_ns", ns, "ns")
    for b in [1024, 2048, 1 << 20]:
        reg.observe("shuffle_block_bytes", b, "bytes")
    prom = reg.prometheus_text()
    # the acceptance contract: p50/p90/p99 for task time AND shuffle
    # block size in the exposition text
    for metric in ("srt_task_time_ns", "srt_shuffle_block_bytes"):
        assert f"# TYPE {metric} histogram" in prom
        assert f'{metric}_quantile{{quantile="0.5"}}' in prom
        assert f'{metric}_quantile{{quantile="0.9"}}' in prom
        assert f'{metric}_quantile{{quantile="0.99"}}' in prom
        assert f'{metric}_bucket{{le="+Inf"}}' in prom
    # bucket counts are cumulative and end at _count
    lines = prom.splitlines()
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines
            if ln.startswith('srt_task_time_ns_bucket{le="')
            and "+Inf" not in ln]
    assert cums == sorted(cums)
    inf = next(ln for ln in lines
               if ln.startswith('srt_task_time_ns_bucket{le="+Inf"'))
    count = next(ln for ln in lines
                 if ln.startswith("srt_task_time_ns_count"))
    assert inf.rsplit(" ", 1)[1] == count.rsplit(" ", 1)[1] == "4"
    assert "srt_task_time_ns_sum 56000000" in prom

def test_prometheus_label_escaping():
    assert _escape_label('a"b') == 'a\\"b'
    assert _escape_label("a\\b") == "a\\\\b"
    assert _escape_label("a\nb") == "a\\nb"
    reg = MetricsRegistry()
    reg.record_query("q1", {'Exec"odd\n': {"opTime": {
        "value": 5, "level": "ESSENTIAL", "unit": "ns"}}}, wall_ns=9)
    prom = reg.prometheus_text()
    assert 'exec_id="Exec\\"odd\\n"' in prom

def test_disabled_registry_exposes_and_allocates_nothing():
    reg = MetricsRegistry(enabled=False)
    reg.observe("task_time_ns", 123, "ns")
    assert reg.histograms() == {}     # dropped without allocating
    assert reg.prometheus_text() == ""
    snap = reg.snapshot()
    assert "histograms" not in snap

def test_registry_quantiles_ride_query_records():
    reg = MetricsRegistry()
    reg.observe("batch_rows", 100, "rows")
    rec = reg.record_query("q1", {}, wall_ns=10)
    assert rec["quantiles"]["batch_rows"]["count"] == 1
    assert "histograms" in reg.snapshot()


# ---------------------------------------------------------------------------
# event-log rotation
# ---------------------------------------------------------------------------

def test_event_log_rotation_and_stitched_read(tmp_path):
    w = events.EventLogWriter(str(tmp_path), max_bytes=400)
    n = 40
    for i in range(n):
        w.emit("TaskEnd", seq=i, rows=i)
    w.close()
    # the live file rolled at least twice: .1 and .2 both exist;
    # rollover fires right after the record that crossed the cap, so
    # every surviving segment (live included, when present) is bounded
    assert os.path.exists(w.path + ".1")
    assert os.path.exists(w.path + ".2")
    for seg in (w.path, w.path + ".1", w.path + ".2"):
        if os.path.exists(seg):
            assert os.path.getsize(seg) <= 400 + 200  # cap + 1 record
    # readers stitch .2, .1, live in write order
    files = list(events.iter_log_files(str(tmp_path)))
    expect = [w.path + ".2", w.path + ".1", w.path]
    assert files == [p for p in expect if os.path.exists(p)]
    recs = events.read_all_events(str(tmp_path))
    seqs = [r["seq"] for r in recs]
    assert seqs == sorted(seqs)       # still in emit order
    assert seqs[-1] == n - 1          # newest records survive
    # oldest records were dropped with the discarded segment
    assert len(seqs) < n

def test_event_log_no_rotation_by_default(tmp_path):
    w = events.EventLogWriter(str(tmp_path))
    for i in range(50):
        w.emit("TaskEnd", seq=i)
    w.close()
    assert not os.path.exists(w.path + ".1")
    assert len(events.read_all_events(str(tmp_path))) == 50

def test_rotation_conf_parsed_and_validated():
    conf = SrtConf({"srt.eventLog.maxBytes": "1m",
                    "srt.obs.resource.intervalMs": "250"})
    assert conf.get(EVENT_LOG_MAX_BYTES) == 1 << 20
    assert conf.get(RESOURCE_SAMPLE_INTERVAL_MS) == 250
    assert SrtConf({}).get(EVENT_LOG_MAX_BYTES) == 0
    assert SrtConf({}).get(RESOURCE_SAMPLE_INTERVAL_MS) == 0
    with pytest.raises(ValueError):
        SrtConf({"srt.eventLog.maxBytes": "-1"}) \
            .get(EVENT_LOG_MAX_BYTES)


# ---------------------------------------------------------------------------
# cross-process trace propagation
# ---------------------------------------------------------------------------

def test_trace_context_roundtrip_parents_remote_spans():
    driver = Tracer()
    job = driver.begin("job-j1", kind="job")
    ctx = driver.context(job)
    assert ctx["trace_id"] == driver.trace_id
    assert ctx["span_id"] == job.span_id
    worker = Tracer.from_context(ctx)
    assert worker.trace_id == driver.trace_id
    task = worker.begin("task-w0", kind="task")
    worker.end(task)
    driver.end(job)
    # the worker's root span parents under the driver's job span
    assert task.parent_id == job.span_id

def test_trace_context_defaults_to_open_scope():
    tr = Tracer()
    with tr.span("job", kind="job") as j:
        ctx = tr.context()
        assert ctx["span_id"] == j.span_id
    # falsy context → fresh root tracer
    fresh = Tracer.from_context(None)
    s = fresh.begin("root")
    fresh.end(s)
    assert s.parent_id is None

def test_span_ids_pid_namespaced():
    tr = Tracer()
    s = tr.begin("x")
    tr.end(s)
    assert s.span_id >> 32 == os.getpid() & 0x3FFFFF

def test_chrome_trace_metadata_carries_anchors(tmp_path):
    tr = Tracer()
    with tr.span("q", kind="query"):
        pass
    doc = json.loads(tr.export_chrome_trace())
    meta = doc["metadata"]
    assert meta["trace_id"] == tr.trace_id
    assert meta["pid"] == os.getpid()
    assert meta["anchor_mono_ns"] == tr.anchor_mono_ns
    assert meta["anchor_unix_s"] == tr.anchor_unix_s

def test_merge_chrome_traces_clock_aligns(tmp_path):
    # two synthetic "processes" whose monotonic clocks differ by
    # exactly 5 seconds; after alignment the event order must follow
    # wall-clock, not raw monotonic, time
    def fake(path, pid, mono0, wall0, name, ts_us):
        doc = {"traceEvents": [
                   {"name": name, "cat": "task", "ph": "X",
                    "ts": ts_us, "dur": 10.0, "pid": pid, "tid": 1,
                    "args": {"span_id": (pid << 32) + 1}}],
               "metadata": {"trace_id": "t1", "pid": pid,
                            "anchor_mono_ns": mono0,
                            "anchor_unix_s": wall0}}
        path.write_text(json.dumps(doc))
    # process A: monotonic origin 0 at wall t=1000s; event at +2s
    fake(tmp_path / "trace-a.json", 11, 0, 1000.0, "A", 2e6)
    # process B: monotonic origin 5e9ns at wall t=1000s; event at
    # monotonic +6s → wall t=1001s, BEFORE A's event at t=1002s
    fake(tmp_path / "trace-b.json", 22, int(5e9), 1000.0, "B", 6e6)
    merged = merge_chrome_traces([tmp_path / "trace-a.json",
                                  tmp_path / "trace-b.json"])
    names = [e["name"] for e in merged["traceEvents"]]
    assert names == ["B", "A"]
    by = {e["name"]: e for e in merged["traceEvents"]}
    assert by["A"]["ts"] - by["B"]["ts"] == pytest.approx(1e6)
    assert merged["metadata"]["trace_id"] == "t1"
    assert len(merged["metadata"]["sources"]) == 2

def test_merge_chrome_traces_skips_unreadable(tmp_path):
    (tmp_path / "trace-bad.json").write_text("{not json")
    merged = merge_chrome_traces([tmp_path / "trace-bad.json",
                                  tmp_path / "trace-gone.json"])
    assert merged["traceEvents"] == []


# ---------------------------------------------------------------------------
# prefetch producer-thread span parenting
# ---------------------------------------------------------------------------

def test_prefetch_producer_span_parents_under_consumer():
    from spark_rapids_tpu.exec.pipeline import PrefetchIterator
    tr = Tracer()
    with tr.span("query", kind="query") as q:
        pf = PrefetchIterator(lambda: iter([1, 2, 3]), depth=2,
                              name="scan", tracer=tr,
                              parent_span_id=tr.current_id())
        assert list(pf) == [1, 2, 3]
    spans = {s.name: s for s in tr.spans()}
    prod = spans["prefetch-scan"]
    assert prod.kind == "producer"
    assert prod.parent_id == q.span_id      # NOT orphaned
    assert prod.t1_ns is not None

def test_prefetch_producer_span_scopes_source_spans():
    """Operator spans opened ON the producer thread (SelfTimer falls
    back to tracer.current_id()) parent under the producer span."""
    from spark_rapids_tpu.exec.pipeline import PrefetchIterator
    tr = Tracer()
    inner = {}

    def source():
        s = tr.begin("DecodeExec", kind="operator",
                     parent=tr.current_id())
        yield 1
        tr.end(s)
        inner["span"] = s

    with tr.span("query", kind="query"):
        pf = PrefetchIterator(source, depth=2, name="src",
                              tracer=tr,
                              parent_span_id=tr.current_id())
        assert list(pf) == [1]
    spans = {s.name: s for s in tr.spans()}
    assert inner["span"].parent_id == spans["prefetch-src"].span_id

def test_prefetch_buffer_bytes_gauge():
    from spark_rapids_tpu.exec import pipeline

    def source():
        yield b"x" * 100
        yield b"y" * 100

    pf = pipeline.PrefetchIterator(source, depth=2, name="g",
                                   nbytes=len)
    deadline = time.time() + 2.0
    while pipeline.prefetch_buffer_bytes() < 200 and \
            time.time() < deadline:
        time.sleep(0.005)
    assert pipeline.prefetch_buffer_bytes() >= 200
    assert list(pf) == [b"x" * 100, b"y" * 100]
    pf.close()
    assert pipeline.prefetch_buffer_bytes() == 0


# ---------------------------------------------------------------------------
# resource sampler
# ---------------------------------------------------------------------------

def _sampler_threads():
    return [t for t in threading.enumerate()
            if t.name == "srt-resource-sampler"]

def test_resource_sampler_emits_samples(tmp_path):
    conf = SrtConf({"srt.eventLog.enabled": "true",
                    "srt.eventLog.dir": str(tmp_path),
                    "srt.obs.resource.intervalMs": "10"})
    events.configure_from_conf(conf)
    resource.configure_from_conf(conf)
    assert resource.enabled()
    deadline = time.time() + 3.0
    samples = []
    while not samples and time.time() < deadline:
        time.sleep(0.03)
        samples = [r for r in events.read_all_events(str(tmp_path))
                   if r["event"] == "ResourceSample"]
    resource.shutdown()
    assert samples, "sampler emitted nothing"
    s = samples[0]
    assert s["rss_bytes"] > 0
    assert "device_bytes_in_use" in s
    assert not _sampler_threads()     # shutdown joined the thread

def test_resource_sampler_zero_overhead_when_disabled(tmp_path):
    before = _sampler_threads()
    # interval set but event log off → no thread
    resource.configure_from_conf(
        SrtConf({"srt.obs.resource.intervalMs": "10"}))
    assert not resource.enabled()
    # event log on but interval 0 (default) → no thread
    resource.configure_from_conf(
        SrtConf({"srt.eventLog.enabled": "true",
                 "srt.eventLog.dir": str(tmp_path)}))
    assert not resource.enabled()
    assert _sampler_threads() == before
    assert not list(tmp_path.iterdir())   # and no files either

def test_resource_sampler_disabled_conf_tears_down(tmp_path):
    on = SrtConf({"srt.eventLog.enabled": "true",
                  "srt.eventLog.dir": str(tmp_path),
                  "srt.obs.resource.intervalMs": "50"})
    resource.configure_from_conf(on)
    assert resource.enabled()
    resource.configure_from_conf(SrtConf({}))
    assert not resource.enabled()
    assert not _sampler_threads()

def test_resource_sample_probes_never_raise():
    s = resource.sample()
    assert s["rss_bytes"] > 0
    assert isinstance(s["device_bytes_in_use"], int)
    assert isinstance(s.get("prefetch_buffer_bytes", 0), int)


# ---------------------------------------------------------------------------
# history report + advisor (synthetic multi-process log)
# ---------------------------------------------------------------------------

def _write_jsonl(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")

def _synthetic_cluster_log(tmp_path):
    """Driver (pid 100) + two workers (pids 201, 202); worker 1 is a
    3x straggler, shuffle 0 is skewed, one fetch retry, one spill."""
    ts = 1000.0
    driver = [
        {"event": "StageSubmitted", "ts": ts, "pid": 100,
         "job_token": "j1", "attempt": 0, "num_workers": 2},
        {"event": "ShuffleWrite", "ts": ts + 1, "pid": 100,
         "shuffle_id": 0, "bytes": 100, "rows": 10, "blocks": 2},
        {"event": "ShuffleWrite", "ts": ts + 1, "pid": 100,
         "shuffle_id": 0, "bytes": 110, "rows": 11, "blocks": 2},
        {"event": "ShuffleWrite", "ts": ts + 1, "pid": 100,
         "shuffle_id": 0, "bytes": 120, "rows": 12, "blocks": 2},
    ]
    w0 = [
        {"event": "TaskEnd", "ts": ts + 2, "pid": 201,
         "job_token": "j1", "worker_id": 0, "rows": 50,
         "wall_ns": 1_000_000,
         "metrics": {"ScanExec#0": {
             "opTime": {"value": 800_000, "level": "ESSENTIAL"},
             "prefetchWaitTime": {"value": 600_000,
                                  "level": "MODERATE"}}}},
        {"event": "ShuffleWrite", "ts": ts + 2, "pid": 201,
         "shuffle_id": 0, "bytes": 5000, "rows": 500, "blocks": 2},
        {"event": "SpillToHost", "ts": ts + 2, "pid": 201,
         "bytes": 4096},
    ]
    w1 = [
        {"event": "TaskEnd", "ts": ts + 5, "pid": 202,
         "job_token": "j1", "worker_id": 1, "rows": 50,
         "wall_ns": 3_000_000,
         "metrics": {"ScanExec#0": {
             "opTime": {"value": 2_500_000,
                        "level": "ESSENTIAL"}}}},
        {"event": "RetryAttempt", "ts": ts + 3, "pid": 202,
         "scope": "fetch", "attempt": 1},
        {"event": "ResourceSample", "ts": ts + 3, "pid": 202,
         "rss_bytes": 1 << 20, "device_bytes_in_use": 0,
         "prefetch_buffer_bytes": 512},
    ]
    _write_jsonl(tmp_path / "events-100.jsonl", driver)
    _write_jsonl(tmp_path / "events-201.jsonl", w0)
    _write_jsonl(tmp_path / "events-202.jsonl", w1)

def test_history_report_jobs_and_workers(tmp_path):
    _synthetic_cluster_log(tmp_path)
    rep = history_report.build_report(str(tmp_path))
    assert rep["events"] == 10
    assert rep["processes"] == [100, 201, 202]
    assert len(rep["jobs"]) == 1
    job = rep["jobs"][0]
    assert job["job_token"] == "j1"
    assert job["num_workers"] == 2
    assert {w["worker_id"] for w in job["workers"]} == {0, 1}
    w0 = next(w for w in job["workers"] if w["worker_id"] == 0)
    # busy = opTime - prefetchWaitTime; wait = wall - busy
    assert w0["busy_ns"] == 200_000
    assert w0["prefetch_wait_ns"] == 600_000
    assert w0["wait_ns"] == 800_000
    assert job["task_wall"]["spread"] == pytest.approx(3.0)

def test_history_report_shuffle_skew(tmp_path):
    _synthetic_cluster_log(tmp_path)
    rep = history_report.build_report(str(tmp_path))
    sh = rep["shuffles"]["0"]
    assert sh["maps"] == 4 and sh["bytes"] == 5330
    assert sh["skew_ratio"] == pytest.approx(5000 / 120)

def test_history_report_advisor_rules(tmp_path):
    _synthetic_cluster_log(tmp_path)
    rep = history_report.build_report(str(tmp_path))
    rules = {a["rule"]: a for a in rep["advisor"]}
    # every rule is evaluated and reported
    assert set(rules) == {"shuffle-partition-skew",
                          "prefetch-starvation", "spill-pressure",
                          "fetch-instability", "worker-straggler",
                          "adaptive-coverage"}
    assert rules["shuffle-partition-skew"]["triggered"]
    assert "srt.shuffle.partitions" in \
        rules["shuffle-partition-skew"]["suggestion"]
    assert rules["spill-pressure"]["triggered"]
    assert rules["fetch-instability"]["triggered"]
    assert rules["worker-straggler"]["triggered"]
    # starvation: 600k wait / 4M wall → not triggered
    assert not rules["prefetch-starvation"]["triggered"]
    assert rules["prefetch-starvation"]["suggestion"] is None
    # untriggered rules still carry their measured evidence
    assert "prefetch wait is" in \
        rules["prefetch-starvation"]["evidence"]

def test_history_report_resources_section(tmp_path):
    _synthetic_cluster_log(tmp_path)
    rep = history_report.build_report(str(tmp_path))
    res = rep["resources"]
    assert res["samples"] == 1 and res["processes"] == 1
    assert res["rss_bytes"]["p50"] == 1 << 20

def test_history_report_render_and_cli(tmp_path):
    _synthetic_cluster_log(tmp_path)
    rep = history_report.build_report(str(tmp_path))
    text = history_report.render(rep)
    assert "job j1" in text and "advisor:" in text
    assert "[!] shuffle-partition-skew" in text
    assert history_report.main([str(tmp_path)]) == 0
    assert history_report.main([str(tmp_path / "nope")]) == 2
    out = tmp_path / "merged.json"
    assert history_report.main([str(tmp_path), "--json",
                                "--merge-trace", str(out)]) == 0

def test_history_report_merges_traces(tmp_path):
    _synthetic_cluster_log(tmp_path)
    # driver job span + worker task span parented across processes
    driver = Tracer()
    job = driver.begin("job-j1", kind="job")
    worker = Tracer.from_context(driver.context(job))
    worker._id_base = (202 & 0x3FFFFF) << 32   # simulate another pid
    task = worker.begin("task-w1-a0", kind="task")
    worker.end(task)
    driver.end(job)
    driver.write_chrome_trace(str(tmp_path / "trace-j1-driver.json"))
    worker.write_chrome_trace(str(tmp_path / "trace-j1-w1.json"))
    rep = history_report.build_report(str(tmp_path))
    tr = rep["trace"]
    assert tr["spans"] == 2
    assert tr["unparented"] == []     # task resolves into the job span
    assert tr["trace_id"] == driver.trace_id
    assert rep["_merged_trace"]["traceEvents"]
