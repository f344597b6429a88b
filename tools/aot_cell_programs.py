#!/usr/bin/env python3
"""Compile seconds of a benchmark cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python tools/aot_cell_programs.py --workload tpcds_sf1.star_power --data DIR \
        [--batch-rows 131072] [--out table.json]

Runs the cell's queries once on the CPU over data already generated under DIR (``benchmarks/harness/
datagen.py``), notes every program the engine's jit registry launches (``jit_<label>``, its input shapes),
then lowers and compiles each for one device of a described ``v5e:2x2`` as ``tests/test_tpu_compile.py``
does, and ranks them by compile seconds. Nothing runs on a TPU: the seconds are this host's compiler, the
order and the outliers are what carries to the chip's host. What the plan decided on the CPU stays decided
(a lane the planner gates on ``pallas_kernels.on_tpu()`` at plan time is the CPU's choice); what a program
decides while it is traced is the chip's (``on_tpu`` answers True during the AOT lowering).
"""

import argparse
import importlib
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--data", required=True, help="directory holding one directory of parquet files a table")
    ap.add_argument("--batch-rows", type=int, help="srt.sql.batchSizeRows and srt.sql.reader.batchSizeRows")
    ap.add_argument("--out", help="write the table here as JSON")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_enable_compilation_cache", False)  # described-chip entries cannot be read back
    import spark_rapids_tpu  # noqa: F401
    from spark_rapids_tpu import jit_registry
    from spark_rapids_tpu.ops import pallas_kernels as PK

    from benchmarks.harness import engine

    seen, programs = set(), []

    def note(label, fn, call_args):
        leaves, treedef = jax.tree_util.tree_flatten(call_args)
        sig = (label, treedef, tuple((getattr(x, "shape", None), str(getattr(x, "dtype", type(x)))) for x in leaves))
        if sig not in seen:
            seen.add(sig)
            programs.append((label, fn, leaves, treedef))

    program_call = jit_registry._NamedProgram.__call__

    def noted_call(self, *a, **k):
        if not k:
            note(self._span[len("launch."):], self.fn, a)
        return program_call(self, *a, **k)

    jit_registry._NamedProgram.__call__ = noted_call

    cell = json.load(open(os.path.join(ROOT, "benchmarks", "workloads", f"{args.workload}.json")))
    config = json.load(open(os.path.join(ROOT, "benchmarks", "configs", f"{cell['config']}.json")))
    family = importlib.import_module(f"benchmarks.configs.{config['family']}")
    conf = dict(config["engine_conf"])
    if args.batch_rows:
        conf.update({"srt.sql.batchSizeRows": args.batch_rows, "srt.sql.reader.batchSizeRows": args.batch_rows})
    session = engine.open_session(conf)
    tables = sorted({t for q in cell["queries"] for t in config["queries"][q]["scans"]})
    frames = engine.open_tables(session, {t: os.path.join(args.data, t) for t in tables}, cell["entry"] == "sql")
    per_query = {}
    for qid in cell["queries"]:
        before, t0 = len(programs), time.perf_counter()
        rows = family.make_query(session, frames, qid, cell["entry"])()
        per_query[qid] = {"rows": len(rows), "new_programs": len(programs) - before,
                          "cpu_seconds": round(time.perf_counter() - t0, 1)}
        print(json.dumps({"query": qid, **per_query[qid]}), flush=True)

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    PK.on_tpu = lambda: True
    table = []
    for label, fn, leaves, treedef in programs:
        shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip) if isinstance(x, jax.Array) else x
                  for x in leaves]
        rows = max((x.shape[0] for x in leaves if getattr(x, "shape", ())), default=0)
        t0 = time.perf_counter()
        try:
            fn.lower(*jax.tree_util.tree_unflatten(treedef, shapes)).compile()
            error = None
        except Exception as e:  # what the chip's compiler would refuse: part of the table
            error = f"{type(e).__name__}: {str(e)[:200]}"
        entry = {"program": "jit_" + jit_registry.program_name(label), "largest_input_rows": rows,
                 "compile_s": round(time.perf_counter() - t0, 2), "error": error}
        table.append(entry)
        print(json.dumps(entry), flush=True)
    table.sort(key=lambda e: -e["compile_s"])
    result = {"workload": args.workload, "batch_rows": args.batch_rows or "default", "programs": len(table),
              "compile_s_total": round(sum(e["compile_s"] for e in table), 1), "queries": per_query, "table": table}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "table"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
