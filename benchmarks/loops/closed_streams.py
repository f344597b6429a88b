"""``"loop": "closed_streams"``: the cell's ``streams`` clients, one thread each, start together; stream *k*
sends ``cell["stream_orders"][k]`` round robin, the next query when the answer to the one before is whole.
After ``seconds`` no stream starts a query; the window closes when the last query in flight answers, and
its length is that whole span, so ``rows_per_s`` is all the streams' fact rows over all that time
(``harness/window.py::end_to_end``, unchanged). Every completed query is a ``QueryRecord``, in the order
the answers came; ``record.engine`` holds what that query's own answer said of it (``call.answer()``, where
the family's callable has one) and the stream that sent it, never ``probe()``: with several queries in
flight "the engine's last query" is another thread's. A stream that raises ends the run with its error.

A traced run (``around`` given): ``around(index, qid)`` is ``run.py``'s one-thread switch, so one stream,
the first, carries it around its own queries, and the others put the same ``bench.query.<qid>`` span
around theirs while the profiler is on (``xplane.reduce`` counts the spans of all threads). The streams
meet when the trace starts (every stream has ended its first, untraced round; none starts its second
before the profiler runs) and when it stops (the first stream, at the round's end at which the switch
will stop, holds its span open until the others have their answers, so no span is cut and no device time
is counted without its query). They meet in the traced run only, and nowhere else in it.
"""

from __future__ import annotations

import contextlib
import threading
import time

from benchmarks.harness import window
from benchmarks.harness.xplane import QUERY_SPAN

#: ``run.py``'s rule for stopping a trace (its ``TRACE_ROUNDS``, ``TRACE_SECONDS``), which the first stream
#: has to foresee: after this many whole traced rounds of its own, once this long. The switch has the
#: last word; the margin makes this copy answer first.
TRACE_ROUNDS, TRACE_SECONDS, _MARGIN_S = 2, 3.0, 0.05


def _span(qid: str):
    import jax
    return jax.profiler.TraceAnnotation(QUERY_SPAN + qid)


class _Meeting:
    """Where the streams of a traced run wait for one another. A stream that leaves the loop, done or
    failed, breaks it: the others then go on without waiting."""

    def __init__(self, streams: int):
        self._barrier = threading.Barrier(streams)
        self.tracing = False    # the profiler runs: every stream spans its queries
        self.stopping = False   # the first stream waits to stop it: finish your query and come
        self.started_s = 0.0

    def wait(self) -> None:
        with contextlib.suppress(threading.BrokenBarrierError):
            self._barrier.wait()

    def leave(self) -> None:
        self._barrier.abort()


def run(cell: dict, calls: list, seconds: float, fact_rows: dict, probe=None, around=None,
        clock=time.perf_counter) -> window.Window:
    streams, orders, by_qid = cell["streams"], cell["stream_orders"], dict(calls)
    if len(orders) != streams or any(q not in by_qid for order in orders for q in order):
        raise ValueError(f"{cell['name']}: stream_orders must give each of the {streams} streams an order "
                         f"of the cell's queries {sorted(by_qid)}")
    round_len = len(calls)
    if around is not None and any(len(order) != round_len for order in orders):
        raise ValueError(f"{cell['name']}: a traced run needs every stream's order as long as a round ({round_len})")
    per_stream: list[list] = [[] for _ in range(streams)]
    errors: list = []
    opened: list = []
    together = threading.Barrier(streams, action=lambda: opened.append(clock()))
    meeting = _Meeting(streams) if around is not None else None

    def one(k: int, qid: str) -> None:
        call = by_qid[qid]
        answer = getattr(call, "answer", None)
        start = clock()
        result, engine = answer() if answer is not None else (call(), {})
        end = clock()
        per_stream[k].append(window.QueryRecord(qid, start, end, fact_rows[qid], result, {**engine, "stream": k}))

    def traced(k: int, index: int, qid: str) -> None:
        """Query ``index`` of stream ``k`` in a traced run; stream 0 carries the switch."""
        starts = index == round_len
        if starts:
            meeting.wait()  # every stream has ended its first round
        if k:
            if starts:
                meeting.wait()  # ... and the profiler runs
            with _span(qid) if meeting.tracing else contextlib.nullcontext():
                one(k, qid)
            if meeting.stopping:
                meeting.wait()  # idle, so the trace may stop
                meeting.wait()  # ... and has
            return
        stops = False
        with around(index, qid):
            if starts:
                meeting.tracing, meeting.started_s = True, clock()
                meeting.wait()
            one(k, qid)
            rounds, into_next = divmod(index + 1, round_len)
            stops = (meeting.tracing and not into_next and rounds - 1 >= TRACE_ROUNDS
                     and clock() - meeting.started_s >= TRACE_SECONDS - _MARGIN_S)
            if stops:
                meeting.stopping = True
                meeting.wait()
        if stops:
            meeting.tracing = meeting.stopping = False
            meeting.wait()

    def stream(k: int) -> None:
        order = orders[k]
        try:
            together.wait()
            open_s, index = opened[0], 0
            while not errors and clock() - open_s < seconds:
                qid = order[index % len(order)]
                if meeting:
                    traced(k, index, qid)
                else:
                    one(k, qid)
                index += 1
        except threading.BrokenBarrierError:
            pass  # another stream failed before the start
        except BaseException as error:  # noqa: BLE001: handed to the caller below
            errors.append(error)
            together.abort()
        finally:
            if meeting:
                meeting.leave()

    threads = [threading.Thread(target=stream, args=(k,), name=f"stream-{k}", daemon=True) for k in range(streams)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    if errors:
        raise errors[0]
    records = sorted((r for records in per_stream for r in records), key=lambda r: r.end_s)
    return window.Window(opened[0], records[-1].end_s, records)
