"""Mesh-lane NDS subset: representative NDS query
shapes through the SPMD mesh executor on a virtual device mesh,
differential against single-stream execution of the same plans.

The subset covers the plan vocabulary a pod-wide NDS run exercises:
broadcast + shuffled joins, partial/final staged aggregates,
ROLLUP expand, window functions over exchanges, INTERSECT/EXCEPT,
subqueries, CASE aggregates and global sorts.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python tools/mesh_nds.py [out.json]
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

def _pin_cpu_emulation() -> None:
    """Standalone/subprocess entry ONLY (must run before jax imports):
    embedded callers (__graft_entry__.dryrun_multichip_nds) keep
    whatever platform the driver initialized. This tool is a
    virtual-mesh rehearsal by definition, so it must never ask for a
    chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    os.environ["XLA_FLAGS"] = flags.strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
#: generated tables live inside the checkout (git-ignored), not /tmp
_CACHE = os.path.join(_ROOT, ".bench_cache")

#: joins/aggregates (q3 q7 q19 q42 q52 q55 q62 q68 q96), rollup (q36
#: q77), windows (q51 q67 q89), set-ops (q38 q87), sort-limit
#: everywhere. Deep-subquery shapes (q1/q6-class: correlated + scalar
#: subqueries) lower to SPMD programs whose single-core emulation runs
#: 20+ minutes per query — they are out of this subset's budget, not
#: its vocabulary.
#: cheap-first order: a timeboxed run persists incrementally, so the
#: record carries maximal coverage even when the heavy tail is cut
SUBSET = ["q42", "q52", "q55", "q96", "q62", "q3", "q19", "q38",
          "q87", "q36", "q77", "q51", "q89", "q68", "q67", "q7"]


def run_subset(scale_rows: int, qids=None, n_devices: int = 8):
    from spark_rapids_tpu import parallel as par
    from spark_rapids_tpu.columnar.vector import batch_to_pydict
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.models.nds import NDS_QUERIES, register_nds
    from spark_rapids_tpu.plan import overrides
    from spark_rapids_tpu.plan.host_table import to_pydict
    from spark_rapids_tpu.plan.mesh_executor import run_on_mesh

    qids = qids or SUBSET
    mesh = par.data_mesh(n_devices)
    conf = SrtConf({"srt.shuffle.partitions": n_devices})
    from spark_rapids_tpu.plan.session import TpuSession
    sess = TpuSession(conf)
    register_nds(sess, os.path.join(_CACHE, f"nds_mesh_{scale_rows}"),
                 scale_rows=scale_rows)
    results = {}
    for qid in qids:
        t0 = time.time()
        try:
            df = sess.sql(NDS_QUERIES[qid])
            physical = overrides.apply_overrides(df.plan, conf)
            mesh_rows = []
            for b in run_on_mesh(physical, mesh, conf):
                d = batch_to_pydict(b)
                ks = list(d)
                for i in range(len(d[ks[0]]) if ks else 0):
                    mesh_rows.append(tuple(d[k][i] for k in ks))
            single = to_pydict(sess.execute(df.plan))
            ks = list(single)
            single_rows = [tuple(single[k][i] for k in ks)
                           for i in range(len(single[ks[0]]) if ks else 0)]
            _assert_rows_equal(qid, mesh_rows, single_rows)
            results[qid] = {"ok": True, "rows": len(mesh_rows),
                            "s": round(time.time() - t0, 2)}
        except Exception as e:
            results[qid] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:200],
                            "s": round(time.time() - t0, 2)}
        print(f"{qid}: {results[qid]}", flush=True)
    return results


def _key(row):
    """Canonical row key: floats collapse to 6 significant digits (a
    RELATIVE tolerance, so the multiset equality below and the sort
    that feeds it use the SAME equivalence — a pairwise-tolerance walk
    over separately sorted lists can misalign near boundaries)."""
    out = []
    for v in row:
        if isinstance(v, float):
            if math.isnan(v):
                out.append(("nan",))
            else:
                out.append(f"{v:.6g}")
        else:
            out.append(v)
    return tuple(out)


def _assert_rows_equal(qid, mesh_rows, single_rows):
    if len(mesh_rows) != len(single_rows):
        raise AssertionError(
            f"{qid}: row count mesh={len(mesh_rows)} "
            f"single={len(single_rows)}")
    ms = sorted(map(_key, mesh_rows))
    ss = sorted(map(_key, single_rows))
    for i, (a, b) in enumerate(zip(ms, ss)):
        if a != b:
            raise AssertionError(f"{qid}: row {i}: {a} != {b}")


#: the shapes light enough to push 100k fact rows through the mesh on
#: this environment's single-core emulation host
SCALE_SUBSET = ["q42", "q52", "q55", "q96", "q62"]


def _run_one_subprocess(qid: str, scale: int, n_devices: int,
                        timeout_s: int, attempts: int = 2) -> dict:
    """One query per subprocess: an XLA rendezvous deadlock/abort (a
    1-core thread-starvation flake, LOG(FATAL) kills the process) then
    loses one ATTEMPT, not the whole record; retries re-roll the
    scheduler."""
    import resource
    import subprocess

    def _cap_memory():
        # q19-class mesh programs have blown past 100 GB on retry
        # ladders; cap the subprocess address space so a memory bomb
        # dies as ONE failed attempt instead of OOMing the box
        lim = 48 * 2 ** 30
        resource.setrlimit(resource.RLIMIT_AS, (lim, lim))

    last = None
    for attempt in range(attempts):
        t0 = time.time()
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 qid, str(scale), str(n_devices)],
                capture_output=True, timeout=timeout_s,
                preexec_fn=_cap_memory)
            out = p.stdout.decode("utf-8", "replace")
            for line in reversed(out.splitlines()):
                if line.startswith("{"):
                    return json.loads(line)
            last = {"ok": False, "s": round(time.time() - t0, 1),
                    "error": f"rc={p.returncode} (rendezvous abort?): "
                             f"{p.stderr.decode()[-160:]}"}
        except subprocess.TimeoutExpired:
            last = {"ok": False, "s": round(time.time() - t0, 1),
                    "error": f"timeout {timeout_s}s"}
    return last


def main():
    """Composite record: the FULL 16-shape subset on the 8-device mesh
    at 8k rows (exchange-placement + SPMD vocabulary proof), plus the
    lighter shapes at 100k fact rows on a 2-device mesh (scale proof).

    Why split: each virtual device is an OS thread; on the 1-core build
    box the 8 threads serialize and stagger through every collective,
    so 8-device x 100k-row programs run tens of minutes per query (the
    collectives themselves are correct). Real multi-chip lanes have a
    core per device and keep the default rendezvous timeouts."""
    _pin_cpu_emulation()
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        qid, scale, ndev = sys.argv[2], int(sys.argv[3]), int(sys.argv[4])
        res = run_subset(scale, qids=[qid], n_devices=ndev)[qid]
        print(json.dumps(res))
        return
    os.makedirs(_CACHE, exist_ok=True)
    out_path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        _CACHE, "mesh_nds.json")
    t0 = time.time()
    full = {}
    at_scale = {}
    # resume: earlier ok results in an existing record are kept (the
    # driver may be restarted after pruning a pathological query)
    try:
        with open(out_path) as f:
            prev = json.load(f)
        full.update({q: r for q, r in prev.get(
            "vocabulary_pass", {}).get("per_query", {}).items()
            if r.get("ok")})
        at_scale.update({q: r for q, r in prev.get(
            "scale_pass", {}).get("per_query", {}).items()
            if r.get("ok")})
    except Exception:
        pass

    def persist():
        rec = _record(full, at_scale, time.time() - t0)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    # scale pass FIRST: the >=100k datapoints carry the most evidence
    # weight; the vocabulary tail fills whatever budget remains
    for qid in SCALE_SUBSET:
        if qid in at_scale:
            continue
        at_scale[qid] = _run_one_subprocess(qid, 100_000, 2,
                                            timeout_s=1800)
        print(f"scale {qid}: {at_scale[qid]}", flush=True)
        persist()
    for qid in SUBSET:
        if qid in full:
            continue
        full[qid] = _run_one_subprocess(qid, 8000, 8, timeout_s=1500)
        print(f"vocab {qid}: {full[qid]}", flush=True)
        persist()
    rec = persist()
    print(json.dumps({
        "vocab_ok": rec["vocabulary_pass"]["queries_ok"],
        "vocab_total": rec["vocabulary_pass"]["queries_total"],
        "scale_ok": rec["scale_pass"]["queries_ok"],
        "scale_total": rec["scale_pass"]["queries_total"],
        "total_s": rec["total_s"]}))


def _record(full, at_scale, elapsed):
    return {
        "vocabulary_pass": {
            "scale_rows": 8000, "n_devices": 8,
            "queries_ok": sum(1 for r in full.values() if r["ok"]),
            "queries_total": len(full), "per_query": full},
        "scale_pass": {
            "scale_rows": 100_000, "n_devices": 2,
            "queries_ok": sum(1 for r in at_scale.values() if r["ok"]),
            "queries_total": len(at_scale), "per_query": at_scale},
        "total_s": round(elapsed, 1),
    }


if __name__ == "__main__":
    main()
