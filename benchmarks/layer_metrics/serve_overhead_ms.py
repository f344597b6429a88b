"""serve: milliseconds of a served query's wall, as its client saw it from submit to the whole answer, that
the engine's session did not spend parsing, planning, queued for admission or executing: the request
thread's start, the per-request session, ``_serialize_result``, the frames and the socket
(``serve/server.py``: the ``serve.request`` / ``serve.serialize`` / ``serve.send`` ranges), and the client's
own decode of the frames. The client's span less what the query's own EOS trailer said of those phases,
mean over the window's queries; ``None`` where no query carries them (an in-process entry, a parent commit)."""

ENGINE_PHASES = ("parse_ns", "plan_ns", "admission_wait_ns", "wall_ns")  # wall_ns: the execution span


def read(run):
    spans = [r.wall_ms - sum(r.engine[k] for k in ENGINE_PHASES) / 1e6
             for r in run.records if all(k in r.engine for k in ENGINE_PHASES)]
    return sum(spans) / len(spans) if spans else None
