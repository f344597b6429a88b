"""TPC-DS throughput family: the star-join reporting queries (3, 42, 52) as the deployment
``tpcds_sf1_throughput`` sends them: SQL text over TCP to one ``serve.SqlServer``, one client session a
stream. The texts and the plain pandas reference are the tpcds family's own (``configs/tpcds.py``: ``SQL``,
``reference``), imported and not copied: one query, one reference, whatever carries it to the engine.

What is this family's own: the ``served`` entry. ``make_query`` registers the tables as views of the
benchmark's session, starts one server over it for the process, and returns a callable that submits the
text through the *calling thread's* own client (tenant: the thread's name where a loop named it
``stream-<k>``, else ``warmup``) and returns the rows as ``.collect()`` would. ``call.answer()`` returns
the rows with what that query's own EOS trailer said of it: never another thread's "last query". A submit
that is shed, fails, is cancelled or is answered from a result cache raises, and the run ends with it.

And its check, after ``configs/tpcds.py``'s and ``configs/tpch_pricing.py``'s: the cell's per-layer metrics
read the waits a served query's record holds (``phases.admission_wait_ns``, ``semaphore_wait_ns``,
``serve_ns``; ``spark_rapids_tpu.plan.session.TIMED_PHASES`` names what an engine records), and an engine
that has none of them has no business in the cell, so it fails here, at once, before any query runs.
"""

from __future__ import annotations

import atexit
import threading

from benchmarks.configs.tpcds import SQL, missing_join_counters, reference  # noqa: F401 (reference: the family's)

ENTRIES = ("served",)

#: the phases of a served query's record this cell's metrics read (docs/OBSERVABILITY.md, "Host ranges and
#: query phases")
REQUIRED_PHASES = ("admission_wait_ns", "semaphore_wait_ns", "serve_ns")

#: what ``answer()`` hands the loop of a query's phases, under the names the harness reads: ``wall_ns`` is the
#: session's execution span (``plan_ms`` subtracts it from the client's wall), ``pallasBatches`` says which
#: lane answered (``harness/engine.py::lane_precision``), ``scanTime`` is what ``scan_upload_ms`` reads; the rest
#: keep their names
_RENAMED = {"execute_ns": "wall_ns", "pallas_batches": "pallasBatches", "scan_upload_ns": "scanTime"}
_KEPT = ("parse_ns", "plan_ns", "reader_threads_peak") + REQUIRED_PHASES


def missing_phases() -> list[str]:
    """The phases of ``REQUIRED_PHASES`` that the engine in this checkout does not record."""
    from spark_rapids_tpu.plan import session
    recorded = getattr(session, "TIMED_PHASES", ())
    return [name for name in REQUIRED_PHASES if name not in recorded]


class Served:
    """One ``SqlServer`` on loopback over the benchmark's session, and one ``SqlClient`` a calling thread."""

    def __init__(self, session, tables: dict):
        from spark_rapids_tpu.serve import SqlServer
        self.session = session  # kept: the module finds its server by the session's id
        for name, frame in tables.items():
            session.create_or_replace_temp_view(name, frame)
        self.server = SqlServer(session, host="127.0.0.1", port=0).start()
        self._local = threading.local()
        self._clients: list = []
        self._lock = threading.Lock()

    def client(self):
        client = getattr(self._local, "client", None)
        if client is None:
            from spark_rapids_tpu.serve import SqlClient
            name = threading.current_thread().name
            client = SqlClient(self.server.endpoint, tenant=name if name.startswith("stream-") else "warmup")
            self._local.client = client
            with self._lock:
                self._clients.append(client)
        return client

    def close(self) -> None:
        with self._lock:
            clients, self._clients = self._clients, []
        for client in clients:
            client.close()
        self.server.stop()


class ServedQuery:
    """``call()``: the rows. ``call.answer()``: ``(rows, what the query's own trailer said)``."""

    def __init__(self, served: Served, text: str):
        self.served, self.text = served, text

    def answer(self) -> tuple[list, dict]:
        result = self.served.client().submit(self.text, cache=False)
        info = result.info
        if info.get("status") != "ok" or info.get("cache") != "off":
            raise RuntimeError(f"not an answer computed from the files: EOS {info!r}")
        data = result.to_pydict()
        rows = [dict(zip(data, values)) for values in zip(*data.values())]
        phases = info.get("phases") or {}
        return rows, {_RENAMED.get(key, key): phases[key] for key in (*_RENAMED, *_KEPT) if key in phases}

    def __call__(self) -> list:
        return self.answer()[0]


_SERVED: dict = {}  # id(session) -> Served: one server a session, so one a process in a run


def make_query(session, tables: dict, qid: str, entry: str) -> ServedQuery:
    """Raises, before any query runs, where the engine lacks what this family's cell is read through."""
    assert entry in ENTRIES, entry
    missing = missing_join_counters() + missing_phases()
    if missing:
        raise RuntimeError("the throughput cell reads a served query's waits and the join execs' counters, and "
                           "this engine records no " + ", ".join(missing)
                           + " (spark_rapids_tpu.plan.session.TIMED_PHASES, spark_rapids_tpu.exec.join.JOIN_COUNTERS)")
    if id(session) not in _SERVED:
        if not _SERVED:
            atexit.register(close_servers)
        _SERVED[id(session)] = Served(session, tables)
    return ServedQuery(_SERVED[id(session)], SQL[qid])


def close_servers() -> None:
    """Close every client and stop every server this module started (at exit; between tests)."""
    while _SERVED:
        _SERVED.popitem()[1].close()
