"""Driver-side SQL server: the serving front door.

``SqlServer`` listens on the framed serving protocol
(serve/protocol.py) and routes every submitted query through the
engine's existing serving machinery — nothing here re-implements
admission or isolation, it only gives them a socket:

- **Admission**: execution goes through ``TpuSession.execute``, so
  each request passes the QuerySemaphore (FIFO tickets, bounded
  queue). ``AdmissionRejected`` surfaces to the client as a retryable
  SHED frame; the admission tier the query took (immediate vs queued,
  stamped on its QueryContext) rides back on the EOS frame so clients
  and the bench bucket latency per tier.
- **Memory isolation**: per-query MemoryBudget slices are claimed and
  released inside execute, exactly as for in-process callers.
- **Cancel/deadline**: the server creates the QueryContext *before*
  calling execute and keeps the handle, so a client disconnect — EOF
  on the session socket or a send failure mid-stream — cancels the
  query server-side even while it is still queued for admission. A
  ``timeout_ms`` on SUBMIT arms the same deadline clients get from
  ``collect(timeout=)``.
- **Teardown hygiene**: per-session teardown cancels in-flight
  queries, joins their request threads, and closes any live
  PrefetchIterators the abandoned streams left behind
  (exec/pipeline.close_live_iterators) — zero leaked producer
  threads is asserted by tests and the chaos sweep.

Result streams go back in the serializer's columnar wire format, one
BATCH frame per ``srt.serve.streamChunkRows`` rows. With
``srt.sql.resultCache.enabled`` the server consults the cross-tenant
result cache (serve/result_cache.py) first: a verified hit replays
the exact frames of the original fill — bypassing admission entirely
— and a miss refills the cache after streaming.

Tenancy: each connection is one session; its HELLO names the tenant.
The per-request engine sessions share the server session's catalog
and plan cache (cross-tenant reuse of compiled plans is the point),
and carry ``session_id``/``tenant`` so QueryStart/QueryEnd events
group by tenant in the report tools.
"""

from __future__ import annotations

import itertools
import socketserver
import threading
import time
from typing import Dict, List, Optional

from ..conf import (RESULT_CACHE_ENABLED, RESULT_CACHE_MAX_BYTES,
                    SERVE_AUTH_TOKEN, SERVE_HOST, SERVE_MAX_SESSIONS,
                    SERVE_PORT, SERVE_STREAM_CHUNK_ROWS, SrtConf)
from ..obs import events as _events
from ..obs.trace import annotate
from ..robustness.admission import (AdmissionRejected, QueryContext,
                                    QueryInterrupted)
from . import protocol as P
from .result_cache import ResultCache, fingerprint


class _SessionState:
    """One connected client session."""

    def __init__(self, session_id: int, tenant: str, peer: str):
        self.session_id = session_id
        self.tenant = tenant
        self.peer = peer
        self.inflight: Dict[int, QueryContext] = {}
        self.threads: List[threading.Thread] = []
        self.requests = 0
        self.lock = threading.Lock()


class _SessionHandler(socketserver.BaseRequestHandler):
    def handle(self):
        self.server.sql_server._handle_connection(self.request)  # type: ignore


class SqlServer:
    """Networked SQL service over one engine session.

    >>> server = SqlServer(session); server.start()
    >>> client = SqlClient(server.endpoint)   # serve/client.py
    """

    def __init__(self, session, host: Optional[str] = None,
                 port: Optional[int] = None):
        self.session = session
        conf: SrtConf = session.conf
        self.conf = conf
        self.auth_token = conf.get(SERVE_AUTH_TOKEN)
        self.max_sessions = conf.get(SERVE_MAX_SESSIONS)
        self.chunk_rows = conf.get(SERVE_STREAM_CHUNK_ROWS)
        self.result_cache: Optional[ResultCache] = None
        if conf.get(RESULT_CACHE_ENABLED) \
                and conf.get(RESULT_CACHE_MAX_BYTES) > 0:
            self.result_cache = ResultCache(
                conf.get(RESULT_CACHE_MAX_BYTES))
        self._host = host if host is not None else conf.get(SERVE_HOST)
        self._port = port if port is not None else conf.get(SERVE_PORT)
        self._server: Optional[socketserver.ThreadingTCPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._sessions: Dict[int, _SessionState] = {}
        self._session_seq = itertools.count(1)
        self._lock = threading.Lock()
        # lifetime counters (tests/chaos/bench)
        self.requests = 0
        self.load_shed = 0
        self.auth_failures = 0
        self.disconnect_cancels = 0

    # --- lifecycle --------------------------------------------------------
    def start(self) -> "SqlServer":
        # the session installs the event sink lazily at first execute;
        # a server emits session-lifecycle events before any query
        # runs, so configure observability up front
        _events.configure_from_conf(self.conf)
        srv = socketserver.ThreadingTCPServer(
            (self._host, self._port), _SessionHandler,
            bind_and_activate=True)
        srv.daemon_threads = True
        srv.sql_server = self  # type: ignore
        self._server = srv
        self._thread = threading.Thread(target=srv.serve_forever,
                                        daemon=True,
                                        name="srt-sql-server")
        self._thread.start()
        return self

    @property
    def endpoint(self) -> str:
        assert self._server is not None, "server not started"
        host, port = self._server.server_address[:2]
        return f"{host}:{port}"

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        if self.result_cache is not None:
            self.result_cache.close()

    def __enter__(self) -> "SqlServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def open_sessions(self) -> int:
        with self._lock:
            return len(self._sessions)

    # --- connection loop --------------------------------------------------
    def _handle_connection(self, sock) -> None:
        send_lock = threading.Lock()
        state: Optional[_SessionState] = None
        try:
            op, _sid, rid, payload = P.recv_frame(sock)
            if op != P.OP_HELLO:
                P.send_json(sock, P.OP_ERR, 0, rid,
                            {"error": "expected HELLO", "retryable": False},
                            lock=send_lock)
                return
            hello = P.decode_json(payload)
            if self.auth_token and hello.get("token") != self.auth_token:
                with self._lock:
                    self.auth_failures += 1
                P.send_json(sock, P.OP_ERR, 0, rid,
                            {"error": "authentication failed",
                             "type": "AuthError", "retryable": False},
                            lock=send_lock)
                return
            with self._lock:
                if len(self._sessions) >= self.max_sessions:
                    P.send_json(sock, P.OP_ERR, 0, rid,
                                {"error": "session limit reached",
                                 "type": "SessionLimit",
                                 "retryable": True}, lock=send_lock)
                    return
                sid = next(self._session_seq)
                try:
                    pn = sock.getpeername()
                    peer = f"{pn[0]}:{pn[1]}" if isinstance(pn, tuple) \
                        and len(pn) >= 2 else str(pn)
                except OSError:
                    peer = "?"
                state = _SessionState(
                    sid, str(hello.get("tenant") or f"tenant-{sid}"),
                    peer)
                self._sessions[sid] = state
            _events.emit("ServeSessionOpen", session_id=sid,
                         tenant=state.tenant, peer=state.peer)
            P.send_json(sock, P.OP_OK, sid, rid,
                        {"session_id": sid}, lock=send_lock)
            while True:
                op, _sid, rid, payload = P.recv_frame(sock)
                if op == P.OP_CLOSE:
                    P.send_json(sock, P.OP_OK, sid, rid, {},
                                lock=send_lock)
                    return
                if op == P.OP_CANCEL:
                    with state.lock:
                        qctx = state.inflight.get(rid)
                    if qctx is not None:
                        qctx.cancel("client cancel")
                    continue
                if op != P.OP_SUBMIT:
                    P.send_json(sock, P.OP_ERR, sid, rid,
                                {"error": f"unexpected opcode {op}",
                                 "retryable": False}, lock=send_lock)
                    continue
                req = P.decode_json(payload)
                t = threading.Thread(
                    target=self._run_request,
                    args=(state, sock, send_lock, rid, req),
                    daemon=True, name=f"srt-serve-s{sid}r{rid}")
                with state.lock:
                    # a session lives for thousands of requests: keep
                    # the threads teardown may have to join, not all
                    state.threads = [x for x in state.threads
                                     if x.is_alive()] + [t]
                    state.requests += 1
                t.start()
        except (ConnectionError, OSError, P.ProtocolError):
            pass  # disconnect; teardown below cancels in-flight work
        finally:
            if state is not None:
                self._teardown_session(state)

    # --- request execution ------------------------------------------------
    def _run_request(self, state: _SessionState, sock, send_lock,
                     rid: int, req: dict) -> None:
        with annotate("serve.request"):
            self._serve(state, sock, send_lock, rid, req)

    def _serve(self, state: _SessionState, sock, send_lock,
               rid: int, req: dict) -> None:
        import os as _os

        qid = f"q{_os.getpid()}-s{state.session_id}r{rid}"
        qctx = QueryContext(query_id=qid)
        timeout_ms = req.get("timeout_ms")
        if timeout_ms:
            qctx.set_timeout(float(timeout_ms) / 1000.0)
        with state.lock:
            state.inflight[rid] = qctx
        with self._lock:
            self.requests += 1
        sid = state.session_id
        t0 = time.perf_counter_ns()
        try:
            sess = self._request_session(state)
            df = sess.sql(str(req.get("sql", "")))
            plan = df.plan
            use_cache = self.result_cache is not None \
                and req.get("cache", True)
            fp = fingerprint(plan, sess.conf) if use_cache else None
            if fp is not None:
                cached = self.result_cache.get(fp)
                if cached is not None:
                    for payload in cached:
                        P.send_frame(sock, P.OP_BATCH, sid, rid,
                                     payload, lock=send_lock)
                    P.send_json(sock, P.OP_EOS, sid, rid, {
                        "status": "ok", "cache": "hit",
                        "tier": "cached", "wait_ns": 0,
                        "wall_ns": time.perf_counter_ns() - t0,
                    }, lock=send_lock)
                    return
            table, rec = sess._execute_recorded(plan, query=qctx,
                                                parse_ns=df.parse_ns)
            with annotate("serve.serialize"):
                payloads = self._serialize_result(table)
            phases = rec["phases"]
            # what the request spent outside the session's own phases:
            # this thread's start, the per-request session, the frames
            engine_ns = sum(phases[k] for k in (
                "parse_ns", "plan_ns", "admission_wait_ns", "execute_ns"))
            with annotate("serve.send"):
                for payload in payloads:
                    P.send_frame(sock, P.OP_BATCH, sid, rid, payload,
                                 lock=send_lock)
                if fp is not None:
                    self.result_cache.put(fp, payloads, table.num_rows)
                # the trailer's serve_ns lacks the trailer's own frame;
                # the record's is whole once that is sent
                phases["serve_ns"] = \
                    time.perf_counter_ns() - t0 - engine_ns
                P.send_json(sock, P.OP_EOS, sid, rid, {
                    "status": "ok",
                    "cache": "miss" if fp is not None else "off",
                    "tier": qctx.admission_tier,
                    "wait_ns": qctx.admission_wait_ns or 0,
                    "rows": table.num_rows,
                    "wall_ns": time.perf_counter_ns() - t0,
                    "query_id": qid,
                    "phases": phases,
                }, lock=send_lock)
            phases["serve_ns"] = time.perf_counter_ns() - t0 - engine_ns
        except AdmissionRejected as e:
            with self._lock:
                self.load_shed += 1
            _events.emit("ServeLoadShed", session_id=sid,
                         tenant=state.tenant, request_id=rid)
            self._safe_send(sock, P.OP_SHED, sid, rid,
                            {"error": str(e),
                             "type": "AdmissionRejected",
                             "retryable": True}, send_lock)
        except QueryInterrupted as e:
            self._safe_send(sock, P.OP_ERR, sid, rid,
                            {"error": str(e),
                             "type": type(e).__name__,
                             "retryable": False}, send_lock)
        except (ConnectionError, OSError):
            # client went away mid-stream: cancel our own query so the
            # engine tears down (budget slice, admission permit) and
            # leaves nothing running for a dead socket
            qctx.cancel("client disconnected mid-stream")
            with self._lock:
                self.disconnect_cancels += 1
        except Exception as e:
            self._safe_send(sock, P.OP_ERR, sid, rid,
                            {"error": f"{e}", "type": type(e).__name__,
                             "retryable": False}, send_lock)
        finally:
            with state.lock:
                state.inflight.pop(rid, None)
            # reap prefetch producers an abandoned stream left behind
            from ..exec.pipeline import close_live_iterators
            close_live_iterators(qctx)

    def _request_session(self, state: _SessionState):
        """Per-request engine session: shares the server session's
        catalog and plan cache (cross-tenant plan reuse), carries the
        client's identity for event tagging."""
        from ..plan.session import TpuSession
        sess = TpuSession(self.session.conf)
        sess._catalog = self.session._catalog
        sess._plan_cache = self.session._plan_cache
        sess.session_id = f"s{state.session_id}"
        sess.tenant = state.tenant
        return sess

    def _serialize_result(self, table) -> List[bytes]:
        """HostTable -> serialized columnar frames of at most
        ``srt.serve.streamChunkRows`` rows each (always at least one
        frame, so empty results still carry their schema)."""
        from ..parallel.serializer import serialize_batch
        from ..plan.host_table import (HostColumn, HostTable,
                                       table_to_batch)
        n = table.num_rows
        chunk = max(int(self.chunk_rows), 1)
        payloads: List[bytes] = []
        if n <= chunk:
            payloads.append(serialize_batch(table_to_batch(table)))
            return payloads
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            cols = [HostColumn(c.values[lo:hi], c.mask[lo:hi], c.dtype)
                    for c in table.columns]
            payloads.append(serialize_batch(
                table_to_batch(HostTable(cols, table.names))))
        return payloads

    def _safe_send(self, sock, opcode, sid, rid, obj, lock) -> None:
        try:
            P.send_json(sock, opcode, sid, rid, obj, lock=lock)
        except (ConnectionError, OSError):
            pass

    # --- teardown ---------------------------------------------------------
    def _teardown_session(self, state: _SessionState) -> None:
        """Cancel in-flight queries, join request threads, close any
        abandoned prefetch iterators, drop the session."""
        from ..exec.pipeline import close_live_iterators
        with state.lock:
            inflight = dict(state.inflight)
            threads = list(state.threads)
        for qctx in inflight.values():
            qctx.cancel("client disconnected")
        if inflight:
            with self._lock:
                self.disconnect_cancels += len(inflight)
        for t in threads:
            t.join(timeout=30)
        for qctx in inflight.values():
            close_live_iterators(qctx)
        with self._lock:
            self._sessions.pop(state.session_id, None)
        _events.emit("ServeSessionClose", session_id=state.session_id,
                     tenant=state.tenant, requests=state.requests,
                     cancelled=len(inflight))
