"""Serving front door (spark_rapids_tpu/serve/): the networked SQL
service and the cross-tenant result cache.

What must hold:

- protocol round-trip over a real socket returns exactly what an
  in-process ``collect`` returns;
- concurrent multi-tenant clients through admission get bit-identical
  answers to serial execution;
- a ``timeout_ms`` on SUBMIT surfaces the typed deadline; a client
  disconnect mid-query cancels server-side and releases the admission
  permit, the budget slice, and every prefetch producer thread;
- the result cache hits on a repeat, invalidates on a Delta commit,
  is bit-identical on/off, and a checksum mismatch evicts + recomputes
  instead of serving garbage;
- QueryStart/End events carry session/tenant identity so the report
  tools group by tenant.
"""

import threading
import time

import pytest

from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.memory.budget import (device_budget,
                                            reset_device_budget)
from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.robustness.admission import (query_semaphore,
                                                   reset_query_semaphore,
                                                   set_current_query)
from spark_rapids_tpu.robustness.faults import (arm_fault_plan,
                                                disarm_fault_plan)
from spark_rapids_tpu.serve import (ResultCache, ServeError,
                                    ServeLoadShed, SqlClient, SqlServer)
from spark_rapids_tpu.testing.asserts import assert_consumer_partition

Q_SUM = ("SELECT b, sum(a) AS s FROM t WHERE a > 100 "
         "GROUP BY b ORDER BY b")
Q_CNT = "SELECT b, count(*) AS c FROM t GROUP BY b ORDER BY b"


@pytest.fixture(autouse=True)
def _clean():
    yield
    disarm_fault_plan()
    set_current_query(None)
    reset_query_semaphore()
    reset_device_budget(None)


def _session(extra=None):
    settings = {"srt.shuffle.partitions": 2}
    settings.update(extra or {})
    s = TpuSession(SrtConf(settings))
    df = s.create_dataframe(
        {"a": list(range(3000)), "b": [float(i % 7) for i in range(3000)]})
    s.create_or_replace_temp_view("t", df)
    return s


def _rows_to_pydict(rows):
    return {k: [r[k] for r in rows] for k in rows[0]} if rows else {}


def _drain(conf, timeout=30.0):
    """Wait for the engine to release every permit and budget slice."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if query_semaphore(conf).active() == 0 \
                and device_budget().active_owners() == set():
            return True
        time.sleep(0.05)
    return False


# ---------------------------------------------------------------- protocol

def test_protocol_roundtrip_over_socket():
    s = _session()
    oracle = _rows_to_pydict(s.sql(Q_SUM).collect())
    with SqlServer(s) as server:
        with SqlClient(server.endpoint, tenant="acme") as c:
            assert c.session_id >= 1
            r = c.submit(Q_SUM)
            assert r.info["status"] == "ok"
            assert r.info["cache"] == "off"  # cache conf defaults off
            assert r.info["tier"] in ("immediate", "queued")
            assert r.to_pydict() == oracle
            # requests multiplex on one session: a second submit reuses
            # the connection with a fresh request id
            r2 = c.submit(Q_CNT)
            assert r2.num_rows == 7
        assert server.requests == 2
    assert server.open_sessions() == 0


def test_streamed_chunking_reassembles():
    s = _session({"srt.serve.streamChunkRows": "256"})
    oracle = _rows_to_pydict(
        s.sql("SELECT a, b FROM t ORDER BY a").collect())
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        r = c.submit("SELECT a, b FROM t ORDER BY a")
        assert len(r.payloads) == (3000 + 255) // 256
        assert r.num_rows == 3000
        assert r.to_pydict() == oracle


def test_hello_auth_token():
    s = _session({"srt.serve.authToken": "sesame"})
    with SqlServer(s) as server:
        with pytest.raises(ServeError) as ei:
            SqlClient(server.endpoint, token="wrong")
        assert ei.value.kind == "AuthError"
        assert server.auth_failures == 1
        with SqlClient(server.endpoint, token="sesame") as c:
            assert c.submit(Q_CNT).num_rows == 7


def test_error_reply_keeps_session_usable():
    s = _session()
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        with pytest.raises(ServeError):
            c.submit("SELECT nope FROM no_such_table")
        # a failed request is terminal for its request id only
        assert c.submit(Q_CNT).num_rows == 7


# ----------------------------------------------- multi-tenant concurrency

def test_concurrent_multitenant_bit_identical_vs_serial():
    s = _session({"srt.sql.concurrentQueryTasks": "2",
                  "srt.sql.admission.maxQueueDepth": "8",
                  "srt.sql.admission.backoffBaseSec": "0.01"})
    reset_query_semaphore(s.conf)
    oracles = {Q_SUM: _rows_to_pydict(s.sql(Q_SUM).collect()),
               Q_CNT: _rows_to_pydict(s.sql(Q_CNT).collect())}
    with SqlServer(s) as server:
        results = [None] * 4
        errors = []

        def run(i):
            sql = Q_SUM if i % 2 == 0 else Q_CNT
            try:
                with SqlClient(server.endpoint,
                               tenant=f"tenant-{i}") as c:
                    for attempt in range(20):
                        try:
                            results[i] = c.submit(sql).to_pydict()
                            return
                        except ServeLoadShed:
                            time.sleep(0.02 * (attempt + 1))
                    errors.append((i, "shed every attempt"))
            except BaseException as e:  # noqa: BLE001
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors
        for i, got in enumerate(results):
            want = oracles[Q_SUM if i % 2 == 0 else Q_CNT]
            assert got == want, f"client {i} diverged"
    assert _drain(s.conf)


# ------------------------------------------------- deadline / disconnect

def test_submit_timeout_ms_surfaces_deadline():
    s = _session()
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        with pytest.raises(ServeError) as ei:
            c.submit(Q_SUM, timeout_ms=1)
        assert ei.value.kind == "DeadlineExceeded"
        # engine healthy afterwards on the same session
        assert c.submit(Q_CNT).num_rows == 7
    assert _drain(s.conf)


def test_disconnect_mid_query_cancels_and_releases_everything(tmp_path):
    """SIGKILL-shaped teardown: the socket dies with a query running.
    The server must cancel it, release the admission permit and budget
    slice, close live prefetch iterators (zero leaked threads), and
    drop the session."""
    from spark_rapids_tpu.exec.pipeline import prefetch_thread_leaks

    s = _session()
    # park the query inside its scan long enough for the disconnect to
    # land while it is provably in flight
    fact = str(tmp_path / "fact")
    s.sql("SELECT a, b FROM t").write.parquet(fact)
    df = s.read.parquet(fact)
    s.create_or_replace_temp_view("slow", df)
    leaks_before = prefetch_thread_leaks()
    with SqlServer(s) as server:
        c = SqlClient(server.endpoint, tenant="doomed")
        arm_fault_plan("seed=1|scan.file:delay@1+2.0")
        try:
            rid = next(c._rid)
            from spark_rapids_tpu.serve import protocol as P
            P.send_json(c._sock, P.OP_SUBMIT, c.session_id, rid,
                        {"sql": "SELECT b, sum(a) AS s FROM slow "
                                "GROUP BY b ORDER BY b"})
            time.sleep(0.3)  # let the request thread enter execute
            c._sock.close()  # abrupt: no CLOSE frame, models a crash
            deadline = time.monotonic() + 30
            while server.open_sessions() and time.monotonic() < deadline:
                time.sleep(0.05)
            assert server.open_sessions() == 0
            assert server.disconnect_cancels >= 1
        finally:
            disarm_fault_plan()
        assert _drain(s.conf)
        assert prefetch_thread_leaks() == leaks_before
        # the server keeps serving new sessions after the crash
        with SqlClient(server.endpoint) as c2:
            assert c2.submit(Q_CNT).num_rows == 7


def test_load_shed_surfaces_as_retryable(tmp_path):
    s = _session({"srt.sql.concurrentQueryTasks": "1",
                  "srt.sql.admission.maxQueueDepth": "0"})
    fact = str(tmp_path / "fact")
    s.sql("SELECT a, b FROM t").write.parquet(fact)
    s.create_or_replace_temp_view("slowt", s.read.parquet(fact))
    reset_query_semaphore(s.conf)
    # the delay fault holds the first file scan (the hog's) for 1.5s so
    # the permit is provably occupied when the second submit arrives
    arm_fault_plan("seed=1|scan.file:delay@1+1.5")
    try:
        with SqlServer(s) as server:
            outcome = {}

            def slow():
                try:
                    with SqlClient(server.endpoint, tenant="hog") as c:
                        outcome["slow"] = c.submit(
                            "SELECT b, sum(a) AS s FROM slowt "
                            "GROUP BY b ORDER BY b").info["status"]
                except BaseException as e:  # noqa: BLE001
                    outcome["slow"] = repr(e)

            t = threading.Thread(target=slow)
            t.start()
            deadline = time.monotonic() + 10
            while query_semaphore(s.conf).active() == 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            with SqlClient(server.endpoint, tenant="shed") as c:
                with pytest.raises(ServeLoadShed) as ei:
                    c.submit(Q_CNT)
                assert ei.value.retryable
            assert server.load_shed == 1
            t.join(60)
            assert outcome["slow"] == "ok"
    finally:
        disarm_fault_plan()
    assert _drain(s.conf)


# ------------------------------------------------------------ result cache

def _cache_session(extra=None):
    settings = {"srt.sql.resultCache.enabled": "true"}
    settings.update(extra or {})
    return _session(settings)


def test_result_cache_hit_replays_identical_bytes():
    s = _cache_session()
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        r1 = c.submit(Q_SUM)
        assert r1.info["cache"] == "miss"
        r2 = c.submit(Q_SUM)
        assert r2.info["cache"] == "hit"
        assert r2.info["tier"] == "cached"
        assert r2.payloads == r1.payloads  # bit-identical replay
        # a different query is its own entry
        assert c.submit(Q_CNT).info["cache"] == "miss"
        stats = server.result_cache.stats()
        assert stats["hits"] == 1 and stats["misses"] == 2
        assert stats["entries"] == 2


def test_result_cache_on_off_bit_identity():
    s = _cache_session()
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        warm = c.submit(Q_SUM)           # fills the cache
        hit = c.submit(Q_SUM)            # served from cache
        cold = c.submit(Q_SUM, cache=False)  # forced recompute
        assert hit.info["cache"] == "hit"
        assert cold.info["cache"] == "off"
        assert cold.payloads == warm.payloads == hit.payloads


def test_result_cache_invalidated_by_delta_commit(tmp_path):
    s = _cache_session()
    root = str(tmp_path / "tbl")
    s.create_dataframe({"k": [1, 2, 3], "v": [10.0, 20.0, 30.0]}) \
        .write.delta(root)
    s.create_or_replace_temp_view("d", s.read.delta(root))
    sql = "SELECT sum(v) AS s FROM d"
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        assert c.submit(sql).info["cache"] == "miss"
        assert c.submit(sql).info["cache"] == "hit"
        # a commit to the scanned table evicts the entry immediately
        s.create_dataframe({"k": [4], "v": [40.0]}) \
            .write.mode("append").delta(root)
        assert server.result_cache.invalidations >= 1
        # same plan (snapshot pinned at view registration) recomputes:
        # the cache may not serve across the commit
        r3 = c.submit(sql)
        assert r3.info["cache"] == "miss"
        assert r3.to_pydict() == {"s": [60.0]}  # pinned pre-append


def test_result_cache_checksum_mismatch_evicts_and_recomputes():
    s = _cache_session()
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        good = c.submit(Q_SUM)
        cache = server.result_cache
        digest = next(iter(cache._entries))
        entry = cache._entries[digest]
        flipped = bytearray(entry.framed[0])
        flipped[len(flipped) // 2] ^= 0xFF  # bit rot inside the frame
        entry.framed[0] = bytes(flipped)
        r = c.submit(Q_SUM)  # verify fails -> evict -> recompute
        assert r.info["cache"] == "miss"
        assert r.payloads == good.payloads
        assert cache.corrupt_evictions == 1
        # the recompute refilled a clean entry
        assert c.submit(Q_SUM).payloads == good.payloads
        assert cache.hits == 1


def test_result_cache_lru_byte_bound():
    cache = ResultCache(max_bytes=4096, subscribe=False)
    from spark_rapids_tpu.serve.result_cache import Fingerprint
    fps = [Fingerprint(f"{i:064x}", ()) for i in range(4)]
    payload = b"x" * 1500
    assert not cache.put(Fingerprint("f" * 64, ()), [b"y" * 8192], 1)
    for fp in fps[:3]:
        assert cache.put(fp, [payload], 1)
    assert cache.evictions >= 1  # third insert pushed out the oldest
    assert cache.bytes <= 4096
    assert cache.get(fps[0]) is None  # LRU victim
    assert cache.get(fps[2]) is not None
    cache.close()


# --------------------------------------------------------- tenant tagging

def test_events_tagged_and_reports_group_by_tenant(tmp_path):
    import os
    import sys

    from spark_rapids_tpu.obs import events
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import profile_report

    events.install(None)
    try:
        s = _session({"srt.eventLog.enabled": "true",
                      "srt.eventLog.dir": str(tmp_path)})
        with SqlServer(s) as server:
            with SqlClient(server.endpoint, tenant="alice") as a:
                a.submit(Q_SUM)
            with SqlClient(server.endpoint, tenant="bob") as b:
                b.submit(Q_CNT)
        events.install(None)
        records = events.read_all_events(str(tmp_path))
        starts = [r for r in records if r.get("event") == "QueryStart"]
        assert {r.get("tenant") for r in starts} == {"alice", "bob"}
        assert all(r.get("session_id") for r in starts)
        opens = [r for r in records
                 if r.get("event") == "ServeSessionOpen"]
        assert len(opens) == 2
        reports = profile_report.report(str(tmp_path))
        summary = profile_report.tenant_summary(reports)
        assert set(summary) == {"alice", "bob"}
        assert summary["alice"]["queries"] == 1
        assert profile_report.report(str(tmp_path), tenant="bob")[0][
            "tenant"] == "bob"
    finally:
        events.install(None)


def test_in_process_queries_stay_untagged():
    """A plain session (no server) must not grow identity fields on
    its events — single-session logs stay byte-compatible."""
    captured = []

    from spark_rapids_tpu.obs import events

    class _Sink:
        def emit(self, event, **fields):
            captured.append(dict(fields, event=event))

        def close(self):
            pass

    events.install(_Sink())
    try:
        s = _session()
        s.sql(Q_CNT).collect()
    finally:
        events.install(None)
    starts = [r for r in captured if r.get("event") == "QueryStart"]
    assert starts and all("tenant" not in r and "session_id" not in r
                          for r in starts)


# -------------------------------------- concurrent streams: the record

NEW_PHASES = ("admission_wait_ns", "semaphore_wait_ns", "serve_ns")


def _served_concurrently(s, clients=4, rounds=1):
    """``clients`` sessions submit Q_SUM / Q_CNT at once, ``rounds``
    times each; returns [(sql, pydict, EOS info)] of every answer."""
    answers, errors = [], []
    together = threading.Barrier(clients)

    def run(i):
        try:
            with SqlClient(server.endpoint, tenant=f"stream-{i}") as c:
                together.wait(60)
                for r in range(rounds):
                    sql = Q_SUM if (i + r) % 2 == 0 else Q_CNT
                    got = c.submit(sql)
                    answers.append((sql, got.to_pydict(), got.info))
        except BaseException as e:  # noqa: BLE001
            errors.append((i, repr(e)))

    with SqlServer(s) as server:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    assert not errors, errors
    assert _drain(s.conf)
    return answers


def _records(answers):
    from spark_rapids_tpu.obs.registry import registry
    by_id = {r["query_id"]: r for r in registry().queries()}
    return [by_id[info["query_id"]] for _, _, info in answers]


def test_four_concurrent_streams_get_the_reference_answers_and_phases():
    s = _session({"srt.sql.concurrentQueryTasks": "4"})
    reset_query_semaphore(s.conf)
    oracles = {q: _rows_to_pydict(s.sql(q).collect())
               for q in (Q_SUM, Q_CNT)}
    answers = _served_concurrently(s, clients=4, rounds=2)
    assert len(answers) == 8
    for sql, got, info in answers:
        assert got == oracles[sql]
        assert info["status"] == "ok" and info["cache"] == "off"
        # the trailer carries the query's own phases
        assert all(k in info["phases"] for k in NEW_PHASES)
    for rec in _records(answers):
        phases = rec["phases"]
        assert all(k in phases for k in NEW_PHASES)
        # four streams under four permits: nobody queues
        assert phases["admission_wait_ns"] == 0
        assert phases["serve_ns"] > 0 and phases["semaphore_wait_ns"] >= 0
        # four pulling threads at once, each split of its own wall whole
        assert_consumer_partition(phases)


def test_a_served_querys_pulling_thread_is_its_request_thread(
        monkeypatch):
    """The thread that executes a served query is the server's request
    thread: its launches and reads are the record's ``consumer_*``."""
    s = _session()
    pullers = []
    execute = TpuSession._execute_physical

    def recorded(self, *args, **kwargs):
        pullers.append(threading.current_thread().name)
        return execute(self, *args, **kwargs)
    monkeypatch.setattr(TpuSession, "_execute_physical", recorded)
    with SqlServer(s) as server, SqlClient(server.endpoint) as c:
        info = c.submit(Q_CNT).info
    assert info["status"] == "ok"
    assert len(pullers) == 1 and pullers[0].startswith("srt-serve-")
    [rec] = _records([(Q_CNT, None, info)])
    phases = rec["phases"]
    assert phases["consumer_dispatch_ns"] > 0
    assert phases["consumer_readback_ns"] > 0 and phases["serve_ns"] > 0
    assert_consumer_partition(phases)


def test_one_admission_permit_makes_the_queued_streams_wait():
    s = _session({"srt.sql.concurrentQueryTasks": "1",
                  "srt.sql.admission.maxQueueDepth": "8",
                  "srt.sql.admission.backoffBaseSec": "0.01"})
    reset_query_semaphore(s.conf)
    answers = _served_concurrently(s, clients=4)
    waits = sorted(r["phases"]["admission_wait_ns"]
                   for r in _records(answers))
    # the first in runs at once; whoever arrives while it runs queues
    assert waits[0] == 0 and waits[-1] > 0
    tiers = [info["tier"] for _, _, info in answers]
    assert tiers.count("queued") == sum(w > 0 for w in waits)
    assert tiers.count("immediate") == sum(w == 0 for w in waits)
    # a queued query's wait is no part of its execution span, nor of
    # what its server spent around the session
    for rec in _records(answers):
        p = rec["phases"]
        assert rec["wall_ns"] == p["execute_ns"]
        assert p["serve_ns"] > 0


def test_admission_wait_is_not_counted_as_semaphore_wait():
    from spark_rapids_tpu.robustness.admission import (QueryContext,
                                                       QuerySemaphore,
                                                       query_scope)
    sem = QuerySemaphore(1, max_queue_depth=4, backoff_base_s=0.01)
    sem.acquire(QueryContext("holder"))
    waited = {}

    def queued():
        token = QueryContext("queued")
        with query_scope(token):
            sem.acquire(token)
            sem.release()
        waited["admission"] = token.admission_wait_ns
        waited["semaphore"] = token.semaphore_wait_ns

    t = threading.Thread(target=queued)
    t.start()
    time.sleep(0.05)
    sem.release()
    t.join(30)
    assert waited["admission"] > 0 and waited["semaphore"] == 0


def test_the_sessions_conf_sizes_the_device_semaphore():
    from spark_rapids_tpu.exec.base import (device_semaphore,
                                            reset_device_semaphore)
    try:
        reset_device_semaphore()
        s = _session({"srt.sql.concurrentTpuTasks": "1"})
        s.sql(Q_CNT).collect()
        assert device_semaphore().permits == 1
        assert reset_device_semaphore(
            SrtConf({"srt.sql.concurrentTpuTasks": "3"})).permits == 3
    finally:
        reset_device_semaphore()


def test_a_querys_blocked_time_at_the_device_gate_is_its_own():
    """Two queries' threads over a one-permit semaphore: the one that
    stood blocked records the wait on its own token, the holder none."""
    from spark_rapids_tpu.exec.base import (ExecContext,
                                            reset_device_semaphore)
    from spark_rapids_tpu.robustness.admission import (QueryContext,
                                                       query_scope)
    try:
        conf = SrtConf({"srt.sql.concurrentTpuTasks": "1"})
        reset_device_semaphore(conf)
        holder, blocked = QueryContext("holder"), QueryContext("blocked")
        sem = ExecContext(conf, query=holder).semaphore

        def stand():
            with query_scope(blocked), sem:
                pass
        with query_scope(holder):
            sem.acquire_if_necessary()
            t = threading.Thread(target=stand)
            t.start()
            time.sleep(0.05)
            sem.release_if_held()
        t.join(30)
        assert blocked.semaphore_wait_ns >= 40_000_000
        assert holder.semaphore_wait_ns == 0
    finally:
        reset_device_semaphore()


def test_two_streams_scan_placed_batches_over_one_byte_budget(tmp_path):
    """Two clients at once over a table of eight files whose scan lays
    three files to a batch (``hold_until`` runs of the reader pool),
    with a byte budget that holds one such batch and not two: neither
    stream may come to wait for a file the other's bytes keep out, in a
    plain scan or under a join that starts both its scans at once."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    for i in range(8):
        lo = 400 * i
        pq.write_table(pa.table({
            "a": np.arange(lo, lo + 400, dtype=np.int64),
            "b": np.arange(lo, lo + 400) / 4.0}),
            str(tmp_path / f"part-{i:05d}.parquet"))
    s = TpuSession(SrtConf({
        "srt.shuffle.partitions": 2,
        "srt.sql.format.parquet.nativeDecode.enabled": "true",
        "srt.sql.reader.batchSizeRows": "1024",
        "srt.exec.pipeline.maxBytesInFlight": "40000"}))
    s.create_or_replace_temp_view("m", s.read.parquet(str(tmp_path)))
    q_scan = "SELECT count(*) AS n, sum(a) AS sa, sum(b) AS sb FROM m"
    q_join = ("SELECT count(*) AS n, sum(x.b + y.b) AS sb "
              "FROM m x JOIN m y ON x.a = y.a")
    want = {q_scan: {"n": [3200], "sa": [3200 * 3199 // 2],
                     "sb": [3200 * 3199 / 8.0]},
            q_join: {"n": [3200], "sb": [3200 * 3199 / 4.0]}}
    answers, errors = [], []
    together = threading.Barrier(2)

    def run(i):
        try:
            with SqlClient(server.endpoint, tenant=f"stream-{i}") as c:
                together.wait(60)
                for sql in (q_scan, q_join, q_scan):
                    got = c.submit(sql, timeout_ms=60_000)
                    answers.append((sql, got.to_pydict(), got.info))
        except BaseException as e:  # noqa: BLE001
            errors.append((i, repr(e)))

    with SqlServer(s) as server:
        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not [t for t in threads if t.is_alive()], "a stream stalled"
    assert not errors, errors
    assert len(answers) == 6
    for sql, got, info in answers:
        assert got == want[sql]
        # three batches a scan, each decoded into its own buffers
        assert info["phases"]["scan_inplace_batches"] >= 3
    assert _drain(s.conf)
    from spark_rapids_tpu.exec import pipeline
    assert pipeline._READERS.bytes == 0 and pipeline._READERS.live == 0
