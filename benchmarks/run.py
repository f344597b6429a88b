#!/usr/bin/env python3
"""The benchmark's one command: one cell, one process, one result line.

    python3 benchmarks/run.py --workload tpch_sf1.q6_power --seed 7 --seconds 30 --trace 0

Everything a cell needs is data found by name: ``workloads/<cell>.json`` (configuration, queries
and their order, entry, loop, streams), ``configs/<config>.json`` (tables, queries with the precision
each states, guarantees, limits) with its family module ``configs/<family>.py`` (query builders for
the entries it has, plain references), the loop in ``loops/<loop>.py``, and one reader per per-layer
metric in ``layer_metrics/<metric>.py``. ``BENCHMARK.json`` says which metrics a cell reports. A
cell that names an entry, a loop or a stream count that no file implements does not run.

The run: build the data from ``--seed``, open the tables, warm up the cell's own queries until a pass
compiles nothing (all of that is ``setup_s``), run the window, read the device's memory peak, then
compare every answer the window produced with the plain reference and print the result line.
Without a TPU it fails, unless ``--rehearse`` is given; a rehearsal prints its numbers under
``rehearsal.<name>``, never under a metric's own name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as the script can read it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
#: data, traces: made at run time, removed when the run ends, never committed
WORK = os.path.join(HERE, "work")
#: a trace covers whole rounds of the cell's queries: at least this many, and at least this long
TRACE_ROUNDS, TRACE_SECONDS = 2, 3.0
MAX_WARMUP_PASSES = 6


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def cell_metrics(benchmark: dict, section: str, cell: str) -> list[dict]:
    return [m for m in benchmark[section] if "workloads" not in m or cell in m["workloads"]]


def layer_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmarks.layer_metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class Tracing:
    """Switches the profiler on for some whole rounds in the middle of the window and wraps every traced
    query in a ``bench.query.<qid>`` host span."""

    def __init__(self, trace_dir: str, round_len: int):
        self.trace_dir, self.round_len = trace_dir, round_len
        self.state = "before"
        self.started_at = 0.0

    @contextlib.contextmanager
    def around(self, index: int, qid: str):
        import jax
        if self.state == "before" and index == self.round_len:  # the first round runs untraced
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.state, self.started_at = "on", time.perf_counter()
        if self.state != "on":
            yield
            return
        with jax.profiler.TraceAnnotation(f"bench.query.{qid}"):
            yield
        rounds, into_next = divmod(index + 1, self.round_len)
        if not into_next and rounds - 1 >= TRACE_ROUNDS and time.perf_counter() - self.started_at >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        if self.state == "on":
            import jax
            jax.profiler.stop_trace()
            self.state = "done"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU: numbers are printed under rehearsal.<name> and mean nothing")
    ap.add_argument("--keep-work", action="store_true", help="leave the data and the trace under benchmarks/work")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    cell = load_json("workloads", f"{args.workload}.json")
    config = load_json("configs", f"{cell['config']}.json")
    family = importlib.import_module(f"benchmarks.configs.{config['family']}")
    if cell["entry"] not in family.ENTRIES:
        print(f"{args.workload}: the {config['family']} family has no entry {cell['entry']!r}", file=sys.stderr)
        return 1
    loop = importlib.import_module(f"benchmarks.loops.{cell['loop']}")
    qids = cell["queries"]

    # The cache lives at one fixed path inside the checkout: the package's own rule, once the
    # environment names no other. Parent and change then share nothing, and a cell's second run finds
    # what its first compiled.
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"the benchmark needs a TPU; jax found {devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips; jax found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]

    import spark_rapids_tpu  # x64, and the compile cache at <checkout>/.jax_cache
    from spark_rapids_tpu import native

    from benchmarks.harness import compare, datagen, engine, window as win, xplane

    counter = engine.CompileCounter()
    work = os.path.join(WORK, args.workload, f"seed-{args.seed}")
    shutil.rmtree(work, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        native.load()
        t1 = time.perf_counter()
        tables = sorted({t for q in qids for t in config["queries"][q]["scans"]})
        paths, written = datagen.generate(config, tables, args.seed, os.path.join(work, "data"))
        t2 = time.perf_counter()
        session = engine.open_session(config["engine_conf"])
        frames = engine.open_tables(session, paths, as_views=cell["entry"] == "sql")
        calls = [(q, family.make_query(session, frames, q, cell["entry"])) for q in qids]
        passes = 0
        while passes < MAX_WARMUP_PASSES:
            before = counter.requests
            for qid, call in calls:
                q0, r0 = time.perf_counter(), counter.requests
                call()
                # as it ends: a run killed in warm-up says how far it got
                log(phase="warmup", query=qid, seconds=time.perf_counter() - q0, compile_requests=counter.requests - r0)
            passes += 1
            if counter.requests == before:
                break
        t3 = time.perf_counter()
        log(phase="setup", native_build_s=t1 - t0, datagen_s=t2 - t1, data_bytes_written=written,
            warmup_s=t3 - t2, warmup_passes=passes, compile_requests=counter.requests,
            compile_cache_hits=counter.cache_hits, compile_cache_dir=jax.config.jax_compilation_cache_dir)

        fact_rows = {q: datagen.fact_rows(config, q) for q in qids}
        tracing = Tracing(os.path.join(work, "trace"), len(calls)) if args.trace else None
        requests_before = counter.requests
        setup_s = time.perf_counter() - T_START
        window = loop.run(cell, calls, args.seconds, fact_rows, lambda: engine.last_query_counters(session),
                          tracing.around if tracing else None)
        if tracing:
            tracing.stop()
        compiles_in_window = counter.requests - requests_before

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": max((p for p in peaks if p is not None), default=None)}

        # the plain reference, once the window has closed: every answer of the window is compared
        comparison = compare.Comparison()
        limits = config["float_limits"]
        references = {q: family.reference(q, paths) for q in dict.fromkeys(qids)}
        for i, record in enumerate(window.records):
            spec = config["queries"][record.qid]
            comparison.add(f"query {i} ({record.qid})", record.result, references[record.qid], spec,
                           limits[spec["precision"]], lane=engine.lane_precision(record.engine))
        reference_s = time.perf_counter() - window.close_s

        if args.trace:
            trace = xplane.reduce(xplane.load(xplane.newest_trace(tracing.trace_dir)))
            device["busy_s"], device["window_s"] = trace.busy_s, trace.window_s
            run = SimpleNamespace(records=window.records, trace=trace, config=config, cell=cell,
                                  device_kind=devices[0].device_kind, compiles_in_window=compiles_in_window)
            metrics = {}
            for m in cell_metrics(benchmark, "per_layer", args.workload):
                value = layer_reader(m["name"])(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            measured = win.end_to_end(window, setup_s)
            metrics = {m["name"]: {"value": measured[m["name"]][0], "unit": m["unit"]}
                       for m in cell_metrics(benchmark, "end_to_end", args.workload)}
    finally:
        if not args.keep_work:
            shutil.rmtree(work, ignore_errors=True)

    if devices[0].platform != "tpu":
        metrics = {f"rehearsal.{name}": m for name, m in metrics.items()}
    compared = comparison.numbers(limits)
    result = {"correct": comparison.correct(limits), "attempted": len(window.records),
              "failed": 0, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": trace.device_ops, "idle_gaps": trace.idle_gaps}
    result["run"] = {"workload": args.workload, "seed": args.seed, "window_s": window.seconds,
                     "queries": len(window.records), "compiles_in_window": compiles_in_window,
                     "reference_s": reference_s,
                     "query_ms": {q: win.spread([r.wall_ms for r in window.records if r.qid == q])
                                  for q in dict.fromkeys(qids)},
                     # every query's wall in window order: where a stall fell, which the spread cannot say
                     "walls_ms": [round(r.wall_ms, 1) for r in window.records],
                     "engine_mean": engine.mean_counters([r.engine for r in window.records]),
                     "lanes": {q: sorted({engine.lane_precision(r.engine) for r in window.records if r.qid == q})
                               for q in dict.fromkeys(qids)}}
    result["compared"] = compared
    for note in comparison.notes:
        print(note, file=sys.stderr)
    for name, number in compared.items():
        print(f"{name} = {number['value']!r} (limit {number['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
