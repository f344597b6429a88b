"""NDS (TPC-DS derived) 99-query suite, end-to-end as SQL text through
session.sql, differential device-vs-CPU (the
reference proves breadth the same way with its 99-query
integration_tests suite).

Queries execute in CHUNKED SUBPROCESSES (spark_rapids_tpu/testing/
nds_check.py) rather than in the pytest process: jaxlib's XLA:CPU
intermittently SIGSEGVs deep inside compile/AOT-load under long
many-query processes (round-4 investigation, docs/PERF_NOTES.md), and
one crash must not take down the whole suite. Each chunk appends
per-query verdicts progressively; queries lost to a crash retry once
in a fresh process. Chunks run lazily, so ``-k q40`` only pays for
q40's chunk. SRT_NDS_INPROCESS=1 restores the in-process path for
debugging a single query.

Tier-1 (``-m 'not slow'``) keeps a dozen queries, one per distinct plan
shape; the other 87 are marked ``slow`` — at ~1-60 s each from a cold
compile cache the full suite alone outlasts the tier-1 time limit.
"""

import json
import os
import subprocess
import sys

import pytest

from spark_rapids_tpu.models.nds import NDS_QUERIES

CHUNK = 6
TIMEOUT_PER_QUERY_S = int(os.environ.get("SRT_NDS_TEST_TIMEOUT_Q", 400))
#: the tier-1 dozen and the plan shape each is there for
TIER1 = {
    "q1": "CTE + correlated scalar subquery",
    "q3": "star join + group-by + sort/limit",
    "q9": "CASE over global-aggregate scalar subqueries",
    "q16": "EXISTS / NOT EXISTS + COUNT(DISTINCT)",
    "q27": "ROLLUP",
    "q8": "INTERSECT",
    "q42": "date-dim star join, top-N",
    "q51": "cumulative window + FULL OUTER JOIN",
    "q87": "EXCEPT chain",
    "q93": "LEFT OUTER JOIN + CASE",
    "q96": "global count over a star join",
    "q53": "window average over an aggregate",
}
# tier-1 queries first, so they share chunks (and child processes) with
# each other and never pay for a slow neighbour
QIDS = sorted(TIER1) + sorted(set(NDS_QUERIES) - set(TIER1))


def _scale() -> int:
    # SRT_NDS_TEST_SCALE=100000 runs the full-scale differential proof
    # (VERDICT r3 #4); default stays CI-sized
    return int(os.environ.get("SRT_NDS_TEST_SCALE", 20_000))


def _run_chunk(data_dir, out_path, qids) -> None:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # PREPEND the repo root: setdefault would drop it whenever the
    # caller exports a PYTHONPATH, and the child then dies on import
    env["PYTHONPATH"] = root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # child stderr goes to a file so systemic failures (import error,
    # datagen crash) surface in the missing-verdict message instead of
    # vanishing into DEVNULL
    err_path = out_path + ".stderr"
    try:
        with open(err_path, "ab") as errf:
            subprocess.run(
                [sys.executable, "-m",
                 "spark_rapids_tpu.testing.nds_check",
                 data_dir, str(_scale()), out_path, ",".join(qids)],
                env=env, timeout=TIMEOUT_PER_QUERY_S * len(qids) + 300,
                stdout=subprocess.DEVNULL, stderr=errf)
    except subprocess.TimeoutExpired:
        pass  # completed queries are already on disk


def _stderr_tail(out_path: str, n: int = 800) -> str:
    try:
        with open(out_path + ".stderr", "rb") as f:
            f.seek(0, 2)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return "<no stderr captured>"


@pytest.fixture(scope="module")
def nds_verdict(tmp_path_factory):
    """qid -> verdict string, materializing one CHUNK-sized subprocess
    per group of queries on first demand, with one fresh-process retry
    for queries a crashed/hung chunk lost."""
    root = tmp_path_factory.mktemp("nds")
    data_dir = str(root / "data")
    out_path = str(root / "results.json")
    state = {"results": {}, "chunks": set(), "retried": set()}

    def _reload():
        try:
            with open(out_path) as f:
                state["results"] = json.load(f)
        except (OSError, ValueError):
            pass

    def get(qid: str) -> str:
        ci = QIDS.index(qid) // CHUNK
        chunk = QIDS[ci * CHUNK:(ci + 1) * CHUNK]
        if ci not in state["chunks"]:
            state["chunks"].add(ci)
            _run_chunk(data_dir, out_path, chunk)
            _reload()
        if qid not in state["results"] and ci not in state["retried"]:
            state["retried"].add(ci)
            missing = [q for q in chunk if q not in state["results"]]
            if missing:
                _run_chunk(data_dir, out_path, missing)
                _reload()
        return state["results"].get(
            qid, "no verdict in two subprocess attempts (crash or "
                 "timeout both times); runner stderr tail:\n"
                 + _stderr_tail(out_path))
    return get


@pytest.mark.parametrize("qid", [
    q if q in TIER1 else pytest.param(q, marks=pytest.mark.slow)
    for q in QIDS])
def test_nds_query_differential(nds_verdict, qid, tmp_path):
    if os.environ.get("SRT_NDS_INPROCESS"):
        from spark_rapids_tpu.testing.nds_check import run
        out = str(tmp_path / "one.json")
        run(str(tmp_path / "data"), _scale(), out, [qid])
        with open(out) as f:
            verdict = json.load(f)[qid]
    else:
        verdict = nds_verdict(qid)
    assert verdict == "pass", f"{qid}: {verdict}"


def test_nds_query_count():
    assert len(NDS_QUERIES) >= 99, \
        "the NDS suite must cover all 99 query shapes"
