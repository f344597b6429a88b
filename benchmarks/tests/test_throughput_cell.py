"""The throughput deployment: the tiny cell's rehearsal end to end (four streams through one server), the
configuration against the star cell's, the check that keeps an engine without the new phases out of the
cell, each new reader on a synthetic run, and the loop on fake calls: with a fake clock, and traced with a
fake switch."""

import collections
import contextlib
import importlib.util
import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.configs import tpcds, tpcds_throughput
from benchmarks.harness import window as win
from benchmarks.loops import closed_streams

from .conftest import ROOT, load_config

READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
CELL = "tpcds_sf1_throughput.streams4"
NEW_METRICS = {"semaphore_wait_ms": "operators", "admission_wait_ms": "serve", "serve_overhead_ms": "serve"}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", os.path.join(READERS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def workload(name):
    with open(os.path.join(ROOT, "benchmarks", "workloads", f"{name}.json")) as f:
        return json.load(f)


def test_cell_end_to_end_at_a_tiny_size(capsys):
    from spark_rapids_tpu.obs.registry import registry
    try:
        rc = run.main(["--workload", "tpcds_tiny_throughput.streams4", "--seed", str(2**31 + 33), "--seconds", "3",
                       "--trace", "0", "--rehearse"])
    finally:
        tpcds_throughput.close_servers()
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["compared"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert result["compared"]["rows_compared"]["value"] > 0
    assert result["run"]["compiles_in_window"] == 0 and result["attempted"] >= 12
    assert set(result["metrics"]) >= {"rehearsal.rows_per_s", "rehearsal.setup_s"}
    # every stream its own session of the one server, each at least one whole order of the three texts; the
    # engine's ring holds the newest 64 queries, all of them the window's
    sessions = collections.Counter(r["query_id"].split("-")[1].split("r")[0] for r in registry().queries()[-12:])
    assert len(sessions) == 4
    mean = result["run"]["engine_mean"]
    assert mean["stream"] == pytest.approx(1.5, abs=0.5) and mean["admission_wait_ns"] == 0
    assert all(k in mean for k in ("semaphore_wait_ns", "serve_ns", "wall_ns", "parse_ns", "plan_ns"))
    # what the unlisted readers take from a record's own engine numbers (plan_ms, scan_upload_ms) is there
    assert mean["scanTime"] > 0 and mean["wall_ns"] > 0


def test_the_configuration_is_the_star_cells_data_and_limits():
    new, star = load_config("tpcds_sf1_throughput"), load_config("tpcds_sf1")
    for key in ("tables", "queries", "float_limits", "chunk_rows", "store_sales_rows", "item_rows", "date_dim_rows",
                "fact_keys_follow_seed"):
        assert new[key] == star[key], key
    assert list(new["tables"]) == list(star["tables"])  # the same order: a seed writes the same bytes
    assert {k: new["guarantees"][k] for k in star["guarantees"]} == star["guarantees"]
    assert set(new["guarantees"]) - set(star["guarantees"]) == {"acknowledged", "failures"}
    assert new["assumed"][:len(star["assumed"])] == star["assumed"]
    assert new["family"] == "tpcds_throughput" and tpcds_throughput.SQL is tpcds.SQL
    assert tpcds_throughput.reference is tpcds.reference
    assert new["engine_conf"] == {"srt.sql.concurrentQueryTasks": 4, "srt.sql.concurrentTpuTasks": 2,
                                  "srt.sql.resultCache.enabled": False}
    # stated because they define the deployment; each is today's default
    from spark_rapids_tpu.conf import CONCURRENT_QUERY_TASKS, CONCURRENT_TASKS, RESULT_CACHE_ENABLED, SrtConf
    defaults = SrtConf({})
    assert [defaults.get(e) for e in (CONCURRENT_QUERY_TASKS, CONCURRENT_TASKS, RESULT_CACHE_ENABLED)] == [4, 2, False]
    assert [e.key for e in (CONCURRENT_QUERY_TASKS, CONCURRENT_TASKS, RESULT_CACHE_ENABLED)] == list(new["engine_conf"])
    tiny = load_config("tpcds_tiny_throughput")
    assert tiny["tables"] == load_config("tpcds_tiny")["tables"] and tiny["family"] == new["family"]


def test_the_benchmark_lists_the_configuration_its_cell_and_three_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    config = load_config("tpcds_sf1_throughput")
    entry = benchmark["configs"][-1]
    assert entry["name"] == "tpcds_sf1_throughput" and entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == config["reduced"] == [
        "fact_keys_follow_seed", "stream_of_3_of_99_templates", "same_substitution_in_every_stream"]
    assert entry["file"] == "benchmarks/configs/tpcds_sf1_throughput.json"
    cell = benchmark["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, "tpcds_sf1_throughput", "streams4", 1)
    assert len(cell["why"]) <= 200
    file = workload(CELL)
    assert (file["entry"], file["loop"], file["streams"], file["chips"]) == ("served", "closed_streams", 4, 1)
    assert len(file["stream_orders"]) == 4 and len({tuple(o) for o in file["stream_orders"]}) == 4
    assert all(sorted(order) == sorted(file["queries"]) for order in file["stream_orders"])
    listed = {m["name"]: m for m in benchmark["per_layer"][-3:]}
    assert set(listed) == set(NEW_METRICS)
    for name, layer in NEW_METRICS.items():
        m = listed[name]
        assert (m["workloads"], m["moves"], m["better"], m["unit"], m["layer"]) == ([CELL], "rows_per_s", "lower", "ms", layer)
        assert callable(reader(name))
    # the cell reports the unlisted metrics and its own three, and none of the other cells' own
    reported = {m["name"] for m in run.cell_metrics(benchmark, "per_layer", CELL)}
    assert set(NEW_METRICS) <= reported and "device_idle_pct" in reported and "join_device_ms" not in reported
    assert not set(NEW_METRICS) & {m["name"] for m in run.cell_metrics(benchmark, "per_layer", "tpcds_sf1.star_power")}


def test_an_engine_without_the_new_phases_fails_at_once(monkeypatch):
    from spark_rapids_tpu.plan import session
    assert tpcds_throughput.missing_phases() == []
    monkeypatch.setattr(session, "TIMED_PHASES", tuple(p for p in session.TIMED_PHASES if p != "serve_ns"))
    with pytest.raises(RuntimeError, match="serve_ns"):
        tpcds_throughput.make_query(None, {}, "q3", "served")
    monkeypatch.delattr(session, "TIMED_PHASES")  # the parent commit: no such symbol
    assert tpcds_throughput.missing_phases() == list(tpcds_throughput.REQUIRED_PHASES)


# ------------------------------------------------------------------ readers

@pytest.fixture
def engine_queries(monkeypatch):
    from spark_rapids_tpu.obs import registry as engine_registry
    held = []
    monkeypatch.setattr(engine_registry, "registry", lambda: SimpleNamespace(queries=lambda: list(held)))
    return held


@pytest.mark.parametrize("name, key", [("semaphore_wait_ms", "semaphore_wait_ns"), ("admission_wait_ms", "admission_wait_ns")])
def test_wait_readers_average_the_windows_queries(engine_queries, name, key):
    engine_queries.append({"phases": {key: 900_000_000}})  # warm-up: not the window's
    engine_queries.extend({"phases": {key: ns}} for ns in (0, 4_000_000, 8_000_000))
    assert reader(name)(SimpleNamespace(records=[object()] * 3)) == pytest.approx(4.0)
    engine_queries[:] = [{"phases": {"parse_ns": 1}}]  # a parent commit: no such phase
    assert reader(name)(SimpleNamespace(records=[object()] * 3)) is None


def test_serve_overhead_is_the_clients_wall_less_the_sessions_phases():
    def record(wall_ms, **engine):
        return win.QueryRecord("q3", 0.0, wall_ms / 1e3, 1000, [], engine)
    served = dict(parse_ns=1_000_000, plan_ns=2_000_000, admission_wait_ns=3_000_000, wall_ns=80_000_000)
    records = [record(100.0, **served), record(110.0, **served), record(500.0)]  # the last: nothing to subtract from
    assert reader("serve_overhead_ms")(SimpleNamespace(records=records)) == pytest.approx(19.0)
    assert reader("serve_overhead_ms")(SimpleNamespace(records=[record(500.0, wall_ns=1)])) is None


# --------------------------------------------------------------------- loop

class FakeClock:
    def __init__(self):
        self.now, self.lock = 0.0, threading.Lock()

    def __call__(self):
        with self.lock:
            return self.now

    def advance(self, seconds):
        with self.lock:
            self.now += seconds


def fake_cell(streams=4):
    cell = workload("tpcds_tiny_throughput.streams4")
    return {**cell, "streams": streams, "stream_orders": cell["stream_orders"][:streams]}


def fake_calls(clock, seconds=1.0):
    def make(qid):
        def call():
            time.sleep(0.001)  # lets the other streams in
            clock.advance(seconds)
            return [{"qid": qid}]
        return call
    return [(q, make(q)) for q in ("q3", "q42", "q52")]


def test_no_stream_starts_after_seconds_and_the_window_closes_at_the_last_answer():
    clock = FakeClock()
    rows = {"q3": 1000, "q42": 2000, "q52": 3000}
    w = closed_streams.run(fake_cell(), fake_calls(clock), 30.0, rows, probe=lambda: {"wall_ns": 1}, clock=clock)
    assert w.open_s == 0.0 and all(r.start_s < 30.0 for r in w.records)
    assert w.close_s == max(r.end_s for r in w.records) >= 30.0
    assert [r.end_s for r in w.records] == sorted(r.end_s for r in w.records)
    assert all(r.result == [{"qid": r.qid}] for r in w.records)
    assert all("wall_ns" not in r.engine for r in w.records)  # the probe reads another thread's last query
    assert win.end_to_end(w, 1.0)["rows_per_s"][0] == sum(rows[r.qid] for r in w.records) / w.seconds
    by_stream = collections.defaultdict(list)
    for r in w.records:
        by_stream[r.engine["stream"]].append(r.qid)
    orders = fake_cell()["stream_orders"]
    for k, sent in by_stream.items():  # each stream its own order, round robin
        assert sent == [orders[k][i % 3] for i in range(len(sent))]
    assert sum(len(sent) for sent in by_stream.values()) == len(w.records) >= 30


class AnsweringCall:
    """A family's callable that says what its own answer's trailer said (``configs/tpcds_throughput.py``)."""

    def __init__(self, qid, call):
        self.qid, self.call = qid, call

    def answer(self):
        return self.call(), {"wall_ns": 7, "of": self.qid}


def test_a_calls_own_answer_is_its_records_engine():
    clock = FakeClock()
    calls = [(qid, AnsweringCall(qid, call)) for qid, call in fake_calls(clock)]
    w = closed_streams.run(fake_cell(2), calls, 5.0, {"q3": 1, "q42": 1, "q52": 1}, clock=clock)
    assert all(r.engine == {"wall_ns": 7, "of": r.qid, "stream": r.engine["stream"]} for r in w.records)
    assert {r.engine["stream"] for r in w.records} == {0, 1}


def test_a_stream_that_fails_ends_the_run_with_its_error():
    clock = FakeClock()
    calls = fake_calls(clock)

    def shed():
        raise RuntimeError("load shed")
    calls[1] = ("q42", shed)
    with pytest.raises(RuntimeError, match="load shed"):
        closed_streams.run(fake_cell(), calls, 5.0, {"q3": 1, "q42": 1, "q52": 1}, clock=clock)
    with pytest.raises(ValueError, match="stream_orders"):
        closed_streams.run({**fake_cell(), "streams": 3}, calls, 5.0, {}, clock=clock)


def test_the_loops_copy_of_the_trace_rule_is_run_pys():
    assert (closed_streams.TRACE_ROUNDS, closed_streams.TRACE_SECONDS) == (run.TRACE_ROUNDS, run.TRACE_SECONDS)


def test_traced_streams_meet_where_the_trace_starts_and_where_it_stops(monkeypatch):
    """A fake switch with ``run.py``'s rule and a fake span: every query that ran while the profiler was on
    carries a span, none was in flight when it started or stopped, and the first round is untraced."""
    clock = FakeClock()
    log, lock = [], threading.Lock()
    in_flight = [0]

    def note(*event):
        with lock:
            log.append(event)

    switch = run.Tracing.__new__(run.Tracing)
    switch.trace_dir, switch.round_len, switch.state, switch.started_at = None, 3, "before", 0.0

    @contextlib.contextmanager
    def span(qid):
        note("span", qid)
        yield

    @contextlib.contextmanager
    def around(index, qid):  # run.py's Tracing.around, with the profiler and the host clock faked
        if switch.state == "before" and index == switch.round_len:
            note("start", in_flight[0])
            switch.state, switch.started_at = "on", clock()
        if switch.state != "on":
            yield
            return
        with span(qid):
            yield
        rounds, into_next = divmod(index + 1, switch.round_len)
        if not into_next and rounds - 1 >= run.TRACE_ROUNDS and clock() - switch.started_at >= run.TRACE_SECONDS:
            note("stop", in_flight[0])
            switch.state = "done"

    def make(qid):
        def call():
            with lock:
                in_flight[0] += 1
                log.append(("query", qid, switch.state))
            time.sleep(0.002)
            clock.advance(0.2)
            with lock:
                in_flight[0] -= 1
            return []
        return call

    monkeypatch.setattr(closed_streams, "_span", span)
    calls = [(q, make(q)) for q in ("q3", "q42", "q52")]
    w = closed_streams.run(fake_cell(), calls, 40.0, {"q3": 1, "q42": 1, "q52": 1}, around=around, clock=clock)
    events = [e[0] for e in log]
    start, stop = events.index("start"), events.index("stop")
    assert log[start] == ("start", 0) and events.count("start") == events.count("stop") == 1
    assert log[stop] == ("stop", 0)  # no stream has a query in flight when the profiler stops
    assert events[:start].count("query") == 4 * 3 and "span" not in events[:start]  # each stream's first round
    traced = [e for e in log[start:stop] if e[0] == "query"]
    assert all(state == "on" for _, _, state in traced) and len(traced) >= 4 * 3 * run.TRACE_ROUNDS
    assert events[start:stop].count("span") == len(traced)
    assert "span" not in events[stop:] and events[stop:].count("query") > 0  # the window goes on untraced
    assert len(w.records) == events.count("query")
