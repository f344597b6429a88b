#!/usr/bin/env python3
"""Proves that every device->host read on a benchmark cell's query path goes
through ``jit_registry.read_back``: runs each query of the named cells once to
warm up, then once more with ``jax_transfer_guard_device_to_host`` set to
``disallow`` for the whole process, every thread. The guard refuses every
implicit read (``int(array)``, ``np.asarray(array)``, ``float(array)``) and lets
``read_back``'s through: it lifts the guard for its own read alone.

    python3 tools/audit_readbacks.py --seed 7 tpch_sf1.q6_power tpcds_sf1.star_power ...
    JAX_PLATFORMS=cpu python3 tools/audit_readbacks.py --emulate --seed 7 tpch_tiny.q6_power

One JSON line a query: ``ok``, the guarded run's ``readbacks`` / ``launches``
from its record, and for a refused read the engine frames that made it. The
CPU backend copies nothing, so the real guard is silent there: ``--emulate``
refuses the same reads in Python (``ArrayImpl._value`` outside an explicit
``device_get``), for a rehearsal; on the chip the guard is jax's own. A served
cell's answer goes to the device and back in ``serve.serialize`` after the
query's record is closed: that function runs with the guard lifted (its reads
are in ``serve_ns``, docs/OBSERVABILITY.md). Nothing of the engine or the
benchmark imports this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
from jax._src import array as _array  # noqa: E402
from jax._src import config as _config  # noqa: E402
from jax._src.lib import _jax  # noqa: E402

#: engine frames of each refused read, newest last
REFUSED: list = []


def _engine_frames() -> list:
    return [f"{os.path.relpath(f.filename, ROOT)}:{f.lineno} {f.name}" for f in traceback.extract_stack()
            if os.path.join(ROOT, "spark_rapids_tpu") in f.filename]


def install_watch(emulate: bool, keep_going: bool) -> None:
    """Wraps ``ArrayImpl._value`` (what ``__array__``, ``__int__``, ``item`` ... read through) so that a
    refused read leaves its engine frames in ``REFUSED``; with ``emulate`` it also does the refusing, or
    with ``keep_going`` only notes the read and lets it through, to list every site in one run."""
    value = _array.ArrayImpl._value

    def guarded(self):
        try:
            if emulate and self._npy_value is None \
                    and _config.transfer_guard_device_to_host.value in ("disallow", "disallow_explicit") \
                    and not _jax.guard_lib.thread_local_state().explicit_device_get:
                if keep_going:
                    REFUSED.append(_engine_frames())
                else:
                    raise RuntimeError("Disallowed device-to-host transfer (emulated guard)")
            return value.fget(self)
        except Exception as e:
            if "isallowed" in str(e):
                REFUSED.append(_engine_frames())
            raise
    _array.ArrayImpl._value = property(guarded)


def lift_guard_in_serialize() -> None:
    from spark_rapids_tpu.serve.server import SqlServer
    serialize = SqlServer._serialize_result

    def allowed(self, table):
        with jax.transfer_guard_device_to_host("allow"):
            return serialize(self, table)
    SqlServer._serialize_result = allowed


def audit(workload: str, seed: int, work: str) -> list:
    from benchmarks.harness import datagen, engine
    from spark_rapids_tpu.obs.registry import registry
    here = os.path.join(ROOT, "benchmarks")
    with open(os.path.join(here, "workloads", f"{workload}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(here, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    family = importlib.import_module(f"benchmarks.configs.{config['family']}")
    qids = list(dict.fromkeys(cell["queries"]))
    tables = sorted({t for q in qids for t in config["queries"][q]["scans"]})
    paths, _ = datagen.generate(config, tables, seed, os.path.join(work, "data"))
    session = engine.open_session(config["engine_conf"])
    frames = engine.open_tables(session, paths, as_views=cell["entry"] == "sql")
    calls = [(q, family.make_query(session, frames, q, cell["entry"])) for q in qids]
    for _ in range(2):
        for _, call in calls:
            call()
    lines = []
    for qid, call in calls:
        REFUSED.clear()
        error = None
        t0 = time.perf_counter()
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        try:
            call()
        except Exception as e:  # noqa: BLE001 - the finding is the line
            error = f"{type(e).__name__}: {str(e).splitlines()[0][:300]}"
        finally:
            jax.config.update("jax_transfer_guard_device_to_host", "allow")
        phases = (registry().queries() or [{}])[-1].get("phases", {})
        lines.append({"workload": workload, "query": qid, "entry": cell["entry"],
                      "ok": error is None and not REFUSED, "error": error,
                      "refused_at": sorted({tuple(frames[-3:]) for frames in REFUSED}),
                      "readbacks": phases.get("readbacks"), "launches": phases.get("launches"),
                      "seconds": round(time.perf_counter() - t0, 4)})
    return lines


def clock_cost(n: int = 1_000_000) -> dict:
    """Nanoseconds one ``time.thread_time_ns()`` / ``time.perf_counter_ns()`` read costs on this host."""
    out = {}
    for name, clock in (("thread_time_ns", time.thread_time_ns), ("perf_counter_ns", time.perf_counter_ns)):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            clock()
        out[f"{name}_read_ns"] = (time.perf_counter_ns() - t0) / n
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workloads", nargs="+")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--emulate", action="store_true", help="refuse implicit reads in Python (CPU rehearsal)")
    ap.add_argument("--keep-going", action="store_true", help="with --emulate: note each implicit read, refuse none")
    args = ap.parse_args()
    # the compile cache at <checkout>/.jax_cache, as benchmarks/run.py keeps it: its runs then find what this
    # one compiled
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import spark_rapids_tpu  # noqa: F401 - x64 and the compile cache, as the benchmark has them
    install_watch(args.emulate, args.keep_going)
    lift_guard_in_serialize()
    work = os.path.join(ROOT, "benchmarks", "work", "audit")
    clean = True
    try:
        for workload in args.workloads:
            shutil.rmtree(work, ignore_errors=True)
            for line in audit(workload, args.seed, work):
                clean &= line["ok"]
                print(json.dumps(line), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
        from benchmarks.configs import tpcds_throughput
        tpcds_throughput.close_servers()
    print(json.dumps({"clean": clean, "device": jax.devices()[0].platform, "emulated": args.emulate,
                      **clock_cost()}), flush=True)
    return 0 if clean else 1


if __name__ == "__main__":
    sys.exit(main())
