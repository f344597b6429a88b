"""Benchmark driver: TPC-H q6/q1/q3 END-TO-END through the framework —
session -> planner (staged exchanges) -> parquet scan -> device exec ->
collect — vs a single-process pandas CPU baseline running the same
queries over the same parquet files (the stand-in for CPU Spark until a
real cluster baseline is captured). BASELINE.md config 1.

Prints JSON lines as stages complete; the LAST line is the full record:
{"metric", "value", "unit", "vs_baseline", ...}. ``value`` is q6
end-to-end throughput in Mrows/s over the lineitem table;
``vs_baseline`` is the speedup over the pandas baseline (>1 = faster).
Earlier lines are prefixes of the same record (so a timeout kill still
leaves the q6 number on stdout). Extra keys carry q1/q3 wall-clocks,
the kernel-only q6 number (so regressions are attributable to kernels
vs the pipeline around them), effective scan bandwidth, and a
measured-roofline HBM utilization estimate for the kernel pipeline.
Each query lane also records its first-iteration wall (``*_first_s``:
compile + cache warmup, split from the steady-state best-of-N), the
record embeds the jit-registry compile ledger (``compile_ledger``,
per-module trace/lower/compile totals), and the run ends with a
report-only perf-gate readout against the newest committed
BENCH_r*.json (tools/perf_gate.py).

Backend: the bench measures an accelerator. Where jax finds none it
fails; with JAX_PLATFORMS=cpu set explicitly it runs as a CPU rehearsal
and every record it writes says ``"backend": "cpu"`` — a CPU number is
never a device number.

Budget discipline (the round-2 bench TIMED OUT, rc=124, and recorded
nothing): the parquet inputs are
generated once into a repo-local cache that persists across runs, every
XLA compile round-trips the persistent compilation cache, and a
wall-clock budget (SRT_BENCH_BUDGET, default 600s) skips the remaining
stages — emitting what completed — rather than overrunning.

Environment knobs: SRT_BENCH_SCALE (lineitem rows, default 6,000,000 =
SF1-shaped),
SRT_BENCH_ITERS, SRT_BENCH_DIR (parquet cache), SRT_BENCH_BUDGET,
SRT_BENCH_PIPELINE=on|off|both (async-pipeline A/B on the NDS sweep;
"both" records pipelined-vs-sync walls and their delta),
SRT_BENCH_FUSION=on|off|both (operator-fusion A/B: "off" disables
srt.exec.fusion.enabled for every engine session; "both" additionally
re-times q6/q3 unfused — recording q*_unfused_s / q*_fusion_speedup —
and switches the NDS A/B dimension from pipeline to fusion, with
nds_fusion_* common-query delta keys and jit-registry hit/miss counts
for the fused-program cache),
SRT_BENCH_ADAPTIVE=on|off|both (adaptive-query-execution A/B: "off"
disables srt.sql.adaptive.enabled for every engine session; "both"
switches the NDS A/B dimension to adaptive, recording
nds_adaptive_on_* / nds_adaptive_off_* per-leg keys plus the
nds_adaptive_delta_pct common-query delta — adaptive takes the A/B
slot over fusion when both ask for it),
SRT_BENCH_SHUFFLE=push|pull|both (push-based-shuffle A/B on a seeded
skewed wide exchange at the transport layer: "pull" disables
srt.shuffle.push.enabled for every engine session; "both" times the
shuffle READ phase under eager push + per-reducer segments vs classic
per-block pull, recording nds_shuffle_push_read_s /
nds_shuffle_pull_read_s, per-partition fetch-latency p99s, the
nds_shuffle_push_speedup ratio, and the zero-copy
nds_shuffle_bytes_bypassed count from a local-session lane),
SRT_BENCH_SERVE=1 (sustained-QPS serving lane: >=4 socket replay
clients against one SqlServer for >=30s of Zipf-mixed NDS traffic
through tools/serve_bench.py — records serve_p50/p90/p99_ms with a
per-admission-tier split, serve_qps_sustained, load-shed and
cross-query-spill counts, and the result-cache / plan-cache hit
rates; SRT_BENCH_SERVE_SECONDS / _CLIENTS / _QPS tune the window),
SRT_BENCH_MESH=on|off|both (SPMD stage-per-program mesh lane, a
VIRTUAL-MESH REHEARSAL whatever the parent's backend: the
five scale-subset NDS shapes through tools/mesh_nds.py, one
subprocess per query pinned to an 8-virtual-device CPU mesh — the
parent holds the chip, so a child must never ask for it; records
mesh_<q>_s walls plus the stage-boundary byte split
shuffle_bytes_bypassed / shuffle_bytes_wire; "both" adds a
serialized single-stream leg per shape as mesh_off_<q>_s;
SRT_BENCH_MESH_SCALE sets the fact-row scale, default 20000).
"""

import json
import os
import sys
import time

import numpy as np

T_START = time.monotonic()
# 600s default: headline queries land inside the first ~100s and every
# later stage emits progressively, so a harness-side kill still leaves
# a complete JSON record; the extra room lets the NDS sweep + the
# delta-merge/mortgage stages (BASELINE configs 4-5) run on slow boxes
BUDGET = float(os.environ.get("SRT_BENCH_BUDGET", 600))
# the NDS sweep spends every second the budget has left (per-query
# left() checks + the A/B legs splitting the full remainder), so the
# socket serving lane behind it must reserve its window up front
SERVE_RESERVE = 130.0 if os.environ.get("SRT_BENCH_SERVE") == "1" \
    else 0.0
ITERS = int(os.environ.get("SRT_BENCH_ITERS", 2))
KERNEL_ROWS = 1 << 22
KERNEL_ITERS = 10

# bytes per lineitem row actually touched by q6 on device:
# l_extendedprice/l_discount/l_quantity float64 + l_shipdate int32-date
Q6_BYTES_PER_ROW = 8 * 3 + 4
# q1: quantity/extendedprice/discount/tax float64 + returnflag/
# linestatus 1B dictionary codes + shipdate int32-date
Q1_BYTES_PER_ROW = 8 * 4 + 1 + 1 + 4
# q3 lineitem side: orderkey/extendedprice/discount float64-width +
# shipdate int32-date (customer/orders are ~1/10th the rows; the
# effective-GB/s headline normalizes on lineitem like q6/q1)
Q3_BYTES_PER_ROW = 8 * 3 + 4
# mortgage ETL bytes per performance row touched on device:
# loan_id int64 + current_upb float64 + days_delinquent int32
# (acquisitions is 1/12th the rows; normalize on performance)
MORTGAGE_BYTES_PER_ROW = 8 + 8 + 4


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _rss_fraction() -> float:
    """This process's resident set as a fraction of the EFFECTIVE memory
    limit — the cgroup limit when one applies (container sandboxes cap
    far below host MemTotal), else host MemTotal. 0.0 when /proc is
    unreadable (never triggers the purge)."""
    try:
        with open("/proc/self/statm") as f:
            rss_kb = int(f.read().split()[1]) * \
                (os.sysconf("SC_PAGE_SIZE") // 1024)
        with open("/proc/meminfo") as f:
            limit_kb = int(f.readline().split()[1])
        for p in ("/sys/fs/cgroup/memory.max",
                  "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
            try:
                raw = open(p).read().strip()
                if raw.isdigit():
                    limit_kb = min(limit_kb, int(raw) // 1024)
                break
            except OSError:
                continue
        return rss_kb / max(limit_kb, 1)
    except Exception:
        return 0.0


def left(label: str, need: float = 15.0) -> bool:
    """True if at least ``need`` seconds of budget remain."""
    rem = BUDGET - (time.monotonic() - T_START)
    if rem < need:
        log(f"budget exhausted before {label} ({rem:.0f}s left)")
        return False
    return True


RESULT = {"metric": "tpch_q6_e2e_throughput", "value": None,
          "unit": "Mrows/s", "vs_baseline": None}

#: box-drift hardening (tools/perf_gate.py samples= path): lanes that
#: can re-measure themselves register here as
#:   name -> {"match": key -> bool, "rerun": () -> {key: value}}.
#: When the gate finds a regression in a lane's keys, run_perf_gate
#: reruns that lane up to 2x and gates the affected keys on the MEDIAN
#: of all measurements — one noisy-box outlier neither fails nor
#: exonerates a lane on its own.
RERUN_LANES: dict = {}


def emit(final: bool = False) -> None:
    RESULT["partial"] = not final
    print(json.dumps(RESULT), flush=True)


def embed_metrics() -> None:
    """Fold a COMPACT registry snapshot into the bench record itself
    (RESULT["metrics"]): lifetime counters, histogram quantiles
    (task time, shuffle block size, fetch latency, batch shapes), and
    per-query spill/retry counts — so every BENCH_*.json carries its
    own profile, not just wall clocks."""
    try:
        from spark_rapids_tpu.obs.registry import registry
        reg = registry()
        snap = reg.snapshot()
        per_query = [{"query_id": q.get("query_id"),
                      "status": q.get("status"),
                      "wall_ns": q.get("wall_ns"),
                      "op_time_ns": q.get("totals", {}).get("opTimeNs"),
                      "rows": q.get("totals", {}).get("numOutputRows"),
                      "shuffle_bytes": q.get("totals", {})
                                        .get("shuffleBytesWritten"),
                      "spilled_bytes": q.get("spilled_bytes", 0),
                      "oom_retries": q.get("oom_retries", 0)}
                     for q in snap.get("queries", [])]
        RESULT["metrics"] = {
            "counters": snap.get("counters", {}),
            "histograms": snap.get("histograms", {}),
            "queries": per_query,
        }
    except Exception as e:  # never let observability kill the bench
        log(f"metrics embed failed: {e}")


def embed_compile_ledger() -> None:
    """Fold the jit-registry compile ledger into the bench record
    (RESULT["compile_ledger"]: per-module trace/lower/compile wall
    totals + shared-program counts, spark_rapids_tpu/obs/roofline.py)
    so every BENCH_*.json says how much of its wall went to XLA
    compilation — the compile-share axis tools/perf_gate.py gates on,
    and the denominator for the *_first_s warmup splits."""
    try:
        from spark_rapids_tpu.obs import roofline
        RESULT["compile_ledger"] = roofline.ledger_totals()
    except Exception as e:  # never let observability kill the bench
        log(f"compile ledger embed failed: {e}")


def run_perf_gate() -> bool:
    """Regression gate against the newest committed BENCH_r*.json at
    the repo root (tools/perf_gate.py), printed to stderr and embedded
    as RESULT["perf_gate"]. ENFORCING by default: a comparable baseline
    with regressions beyond tolerance makes the bench exit non-zero
    (after emitting the record, so the numbers are still inspectable).
    ``SRT_BENCH_GATE=report`` opts back into report-only. Returns True
    when the gate passes (or cannot compare)."""
    enforce = os.environ.get("SRT_BENCH_GATE", "enforce") != "report"
    try:
        import glob
        here = os.path.dirname(os.path.abspath(__file__))
        prevs = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
        if not prevs:
            return True
        sys.path.insert(0, os.path.join(here, "tools"))
        import perf_gate
        base = perf_gate.load_bench(prevs[-1])
        res = perf_gate.compare(base, RESULT)
        samples: list = []
        reruns: list = []
        if res["comparable"] and res["regressions"]:
            for lane, spec in RERUN_LANES.items():
                for attempt in (1, 2):
                    lane_regs = sorted(r[0] for r in res["regressions"]
                                       if spec["match"](r[0]))
                    if not lane_regs or \
                            not left(f"gate rerun {lane}", need=60):
                        break
                    log(f"perf gate: rerunning lane '{lane}' "
                        f"(attempt {attempt}) for {lane_regs}")
                    try:
                        s = spec["rerun"]()
                    except Exception as e:
                        log(f"gate rerun {lane} failed: {e}")
                        break
                    if not s:
                        break
                    samples.append(s)
                    reruns.append({"lane": lane, "attempt": attempt,
                                   "sample": s})
                    res = perf_gate.compare(base, RESULT,
                                            samples=samples)
        for line in perf_gate.render(res, os.path.basename(prevs[-1]),
                                     "this run").splitlines():
            log(line)
        RESULT["perf_gate"] = {
            "baseline": os.path.basename(prevs[-1]),
            "comparable": res["comparable"],
            "enforcing": enforce,
            "regressions": [list(r) for r in res["regressions"]],
            "reruns": reruns,
            "median_keys": res.get("median_keys", []),
        }
        if enforce and res["comparable"] and res["regressions"]:
            log("perf gate: FAIL (enforcing; "
                "SRT_BENCH_GATE=report to opt out)")
            return False
        return True
    except Exception as e:  # infra failure is not a perf regression
        log(f"perf gate failed: {e}")
        return True


def dump_metrics_snapshot() -> None:
    """SRT_BENCH_METRICS=<path> writes the in-process metrics-registry
    snapshot (per-query summaries + lifetime counters, see
    spark_rapids_tpu/obs/registry.py) next to the bench record, plus a
    Prometheus text exposition at <path>.prom. The registry records
    every query the bench ran regardless of srt.eventLog.enabled, so
    this costs nothing when the variable is unset."""
    path = os.environ.get("SRT_BENCH_METRICS")
    if not path:
        return
    try:
        from spark_rapids_tpu.obs.registry import registry
        reg = registry()
        with open(path, "w") as f:
            json.dump(reg.snapshot(), f, indent=2, default=str)
        with open(path + ".prom", "w") as f:
            f.write(reg.prometheus_text())
        log(f"metrics snapshot -> {path}")
    except Exception as e:  # never let observability kill the bench
        log(f"metrics snapshot failed: {e}")


def ensure_data(scale: int, data_dir: str) -> dict:
    """Generate (once) lineitem/orders/customer parquet at ``scale``."""
    from spark_rapids_tpu.datagen import generate_table, lineitem_spec, \
        orders_spec
    from spark_rapids_tpu.models.tpch import customer_spec
    specs = (lineitem_spec(scale), orders_spec(max(scale // 4, 1)),
             customer_spec(max(scale // 40, 1)))
    for spec in specs:
        out = os.path.join(data_dir, spec.name)
        if not (os.path.isdir(out) and os.listdir(out)):
            log(f"generating {spec.name} ({spec.num_rows} rows)...")
            generate_table(None, spec, out, chunk_rows=1 << 20)
    return {s.name: os.path.join(data_dir, s.name) for s in specs}


def _best(fn, iters):
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# pandas CPU baseline (end-to-end: parquet read + query, per iteration)
# ---------------------------------------------------------------------------

def pandas_q6(paths):
    import pandas as pd
    li = pd.read_parquet(paths["lineitem"],
                         columns=["l_shipdate", "l_discount",
                                  "l_quantity", "l_extendedprice"])
    import datetime
    lo, hi = datetime.date(1994, 1, 1), datetime.date(1995, 1, 1)
    m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi) &
         (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07) &
         (li["l_quantity"] < 24.0))
    sel = li[m]
    return float((sel["l_extendedprice"] * sel["l_discount"]).sum())


def pandas_q1(paths):
    import pandas as pd
    import datetime
    li = pd.read_parquet(paths["lineitem"])
    li = li[li["l_shipdate"] <= datetime.date(1998, 9, 2)]
    li["disc_price"] = li["l_extendedprice"] * (1 - li["l_discount"])
    li["charge"] = li["disc_price"] * (1 + li["l_tax"])
    g = li.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "count"))
    return g.sort_index()


def pandas_q3(paths):
    import pandas as pd
    import datetime
    cutoff = datetime.date(1995, 3, 15)
    cust = pd.read_parquet(paths["customer"])
    orders = pd.read_parquet(paths["orders"])
    li = pd.read_parquet(paths["lineitem"],
                         columns=["l_orderkey", "l_extendedprice",
                                  "l_discount", "l_shipdate"])
    c = cust[cust["c_mktsegment"] == "BUILDING"]
    o = orders[orders["o_orderdate"] < cutoff]
    l = li[li["l_shipdate"] > cutoff]
    j = c.merge(o, left_on="c_custkey", right_on="o_custkey") \
         .merge(l, left_on="o_orderkey", right_on="l_orderkey")
    j["revenue"] = j["l_extendedprice"] * (1 - j["l_discount"])
    g = (j.groupby(["o_orderkey", "o_orderdate"], as_index=False)
          ["revenue"].sum()
          .sort_values("revenue", ascending=False).head(10))
    return g


def pandas_delta_merge(n, half):
    """CPU baseline for BASELINE config 4: the same upsert (merge on k,
    update matched, insert unmatched) + conditional update, as pandas
    over parquet with a full rewrite — what a single-process CPU
    engine actually does for a copy-on-write MERGE."""
    import shutil
    import tempfile

    import numpy as np
    import pandas as pd
    d = tempfile.mkdtemp(prefix="srt_delta_cpu_")
    try:
        rng = np.random.default_rng(0)
        base = pd.DataFrame({"k": np.arange(n),
                             "amount": rng.uniform(0, 1e4, n),
                             "flag": np.zeros(n, np.int32)})
        base.to_parquet(os.path.join(d, "t.parquet"))
        # source built OUTSIDE the timed region — the engine lane also
        # constructs its source DataFrame before its timer starts
        src = pd.DataFrame({"k": np.arange(half, n + half),
                            "amount": rng.uniform(0, 1e4, n),
                            "flag": np.ones(n, np.int32)})
        t0 = time.perf_counter()
        tgt = pd.read_parquet(os.path.join(d, "t.parquet"))
        if src["k"].duplicated().any():
            raise ValueError("dup keys")
        merged = tgt.merge(src, on="k", how="outer",
                           suffixes=("", "_src"), indicator=True)
        upd = merged["_merge"] == "both"
        merged.loc[upd, "amount"] = merged.loc[upd, "amount_src"]
        merged.loc[upd, "flag"] = merged.loc[upd, "flag_src"]
        ins = merged["_merge"] == "right_only"
        merged.loc[ins, "amount"] = merged.loc[ins, "amount_src"]
        merged.loc[ins, "flag"] = merged.loc[ins, "flag_src"]
        out = merged[["k", "amount", "flag"]]
        out.to_parquet(os.path.join(d, "t2.parquet"))
        t2 = pd.read_parquet(os.path.join(d, "t2.parquet"))
        t2.loc[t2["amount"] > 5e3, "flag"] += 2
        t2.to_parquet(os.path.join(d, "t3.parquet"))
        return time.perf_counter() - t0
    finally:
        shutil.rmtree(d, ignore_errors=True)


def pandas_mortgage(mort_dir):
    """Same per-loan feature ETL as models.mortgage.mortgage_etl, in
    pandas: the config-5 CPU baseline."""
    import pandas as pd
    acq = pd.read_parquet(os.path.join(mort_dir, "acquisitions"))
    perf = pd.read_parquet(os.path.join(mort_dir, "performance"))
    perf["delinq_90"] = (perf["days_delinquent"] >= 90).astype("int64")
    per_loan = perf.groupby("loan_id").agg(
        n_reports=("loan_id", "count"),
        n_delinq_90=("delinq_90", "sum"),
        max_delinq=("days_delinquent", "max"),
        avg_upb=("current_upb", "mean")).reset_index()
    feats = per_loan.merge(acq, on="loan_id")
    feats["ever_90"] = (feats["n_delinq_90"] > 0).astype("int64")
    # the device-arrays hand-off analogue: materialize numeric ndarray
    return feats.select_dtypes("number").to_numpy()


# ---------------------------------------------------------------------------
# framework end-to-end
# ---------------------------------------------------------------------------

# SRT_BENCH_FUSION=off flows through every engine session the bench
# creates (headline, delta, mortgage, NDS) via this module-level conf
# overlay; main() populates it before the first session is built.
_FUSION_EXTRA: dict = {}


def framework_session(extra: dict = None):
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.plan.session import TpuSession
    settings = {"srt.shuffle.partitions": 4}
    settings.update(_FUSION_EXTRA)
    if extra:
        settings.update(extra)
    return TpuSession(SrtConf(settings))


def fusion_counters() -> dict:
    """Fused-pipeline construction + jit-cache counters (cumulative
    for the process): chains/stages planned so far plus the shared-jit
    registry's hit/miss/entries stats for the fused-program module."""
    from spark_rapids_tpu.exec.fused import fusion_stats
    return fusion_stats()


def framework_queries(session, paths):
    from spark_rapids_tpu.models import q1, q3, q6
    t = {name: session.read.parquet(p) for name, p in paths.items()}
    return {
        "q6": lambda: q6(t["lineitem"]).collect(),
        "q1": lambda: q1(t["lineitem"]).collect(),
        "q3": lambda: q3(t["customer"], t["orders"],
                         t["lineitem"]).collect(),
    }


# ---------------------------------------------------------------------------
# kernel-only q6 (secondary metric: device pipeline without scan)
# ---------------------------------------------------------------------------

def kernel_q6_seconds() -> float:
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import ColumnarBatch, ColumnVector
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.exec.basic import BatchScanExec
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.expr.aggregates import CountStar, Sum
    from spark_rapids_tpu.expr.core import lit
    from spark_rapids_tpu.ops import kernels as K

    rows = KERNEL_ROWS
    rng = np.random.default_rng(42)
    data = {
        "extendedprice": (rng.uniform(100.0, 10_000.0, rows)
                          .astype(np.float32), dt.FLOAT32),
        "discount": ((rng.integers(0, 11, rows).astype(np.float32)
                      / 100.0), dt.FLOAT32),
        "quantity": (rng.integers(1, 51, rows).astype(np.float32),
                     dt.FLOAT32),
        "shipdate": (rng.integers(8766, 10957, rows).astype(np.int32),
                     dt.INT32),
    }
    valid = jnp.ones(rows, jnp.bool_)
    cols = [ColumnVector(jnp.asarray(a), valid, t)
            for a, t in data.values()]
    batch = ColumnarBatch(cols, list(data), rows)
    agg = HashAggregateExec(
        BatchScanExec([], batch.schema()), [],
        [(Sum(col("extendedprice") * col("discount")), "revenue"),
         (CountStar(), "n")])
    f32 = lambda v: lit(float(np.float32(v)), dt.FLOAT32)
    pred = ((col("shipdate") >= 9131) & (col("shipdate") < 9496) &
            (col("discount") >= f32(0.05)) & (col("discount") <= f32(0.07)) &
            (col("quantity") < f32(24.0)))

    @jax.jit
    def q6k(b):
        filtered = K.filter_batch(b, pred.eval(b))
        partial = agg._update(filtered, jnp.int32(0))
        return agg._merge_finalize(partial)

    out = q6k(batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(out))
    return _best(lambda: jax.block_until_ready(
        jax.tree_util.tree_leaves(q6k(batch))), KERNEL_ITERS)


def measured_peak_bw_gbs() -> float:
    """Empirical HBM roofline: best-case bytes/s of a device copy."""
    import jax
    import jax.numpy as jnp
    n = 1 << 26  # 64M f32 = 256MB
    x = jnp.arange(n, dtype=jnp.float32)
    f = jax.jit(lambda a: a * 1.0000001)
    jax.block_until_ready(f(x))
    t = _best(lambda: jax.block_until_ready(f(x)), 5)
    return (2 * 4 * n) / t / 1e9  # read + write


def main():
    import spark_rapids_tpu  # noqa: F401
    import jax
    backend = jax.default_backend()
    if backend == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit("bench.py measures an accelerator and jax found none; "
                 "set JAX_PLATFORMS=cpu to run it as a labelled CPU "
                 "rehearsal")
    RESULT["backend"] = backend

    fusion_mode = os.environ.get("SRT_BENCH_FUSION", "on").lower()
    if fusion_mode not in ("on", "off", "both"):
        fusion_mode = "on"
    RESULT["fusion_mode"] = fusion_mode
    if fusion_mode == "off":
        _FUSION_EXTRA["srt.exec.fusion.enabled"] = "false"

    adaptive_mode = os.environ.get("SRT_BENCH_ADAPTIVE", "on").lower()
    if adaptive_mode not in ("on", "off", "both"):
        adaptive_mode = "on"
    RESULT["adaptive_mode"] = adaptive_mode
    if adaptive_mode == "off":
        # single-lane off: every engine session the bench opens runs
        # with adaptive execution disabled (rides the same channel as
        # SRT_BENCH_FUSION=off)
        _FUSION_EXTRA["srt.sql.adaptive.enabled"] = "false"

    shuffle_mode = os.environ.get("SRT_BENCH_SHUFFLE", "push").lower()
    if shuffle_mode not in ("push", "pull", "both"):
        shuffle_mode = "push"
    RESULT["shuffle_mode"] = shuffle_mode
    if shuffle_mode == "pull":
        # single-lane pull: every engine session runs with the eager
        # push path disabled (classic fetch-on-demand shuffle)
        _FUSION_EXTRA["srt.shuffle.push.enabled"] = "false"

    scale = int(os.environ.get("SRT_BENCH_SCALE", 0))
    if not scale:
        scale = 6_000_000
    data_dir = os.environ.get(
        "SRT_BENCH_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache", f"sf_{scale}"))
    RESULT["rows"] = scale

    paths = ensure_data(scale, data_dir)
    log("data ready")

    session = framework_session()
    queries = framework_queries(session, paths)

    # --- q6: the headline number, first so a timeout still records it
    # (*_first_s = first-iteration wall: compile + cache population,
    # split out so steady-state numbers stay clean of warmup)
    t0 = time.perf_counter()
    queries["q6"]()  # warm: compile + populate caches
    RESULT["q6_first_s"] = round(time.perf_counter() - t0, 4)
    q6_s = _best(queries["q6"], ITERS)
    cpu_q6 = _best(lambda: pandas_q6(paths), 1)
    RESULT.update({
        "value": round(scale / q6_s / 1e6, 2),
        "q6_s": round(q6_s, 4),
        "vs_baseline": round(cpu_q6 / q6_s, 3),
        "q6_effective_gb_s": round(
            scale * Q6_BYTES_PER_ROW / q6_s / 1e9, 2),
    })
    log(f"q6: {q6_s:.3f}s (pandas {cpu_q6:.3f}s)")
    emit()

    # --- q1/q3 breadth numbers (effective GB/s headlined like q6)
    for name, baseline, row_bytes in (("q1", pandas_q1, Q1_BYTES_PER_ROW),
                                      ("q3", pandas_q3, Q3_BYTES_PER_ROW)):
        if not left(name, need=60):
            break
        t0 = time.perf_counter()
        queries[name]()
        RESULT[f"{name}_first_s"] = round(time.perf_counter() - t0, 4)
        t = _best(queries[name], max(ITERS - 1, 1))
        c = _best(lambda: baseline(paths), 1)
        RESULT[f"{name}_s"] = round(t, 4)
        RESULT[f"{name}_vs_baseline"] = round(c / t, 3)
        RESULT[f"{name}_effective_gb_s"] = round(
            scale * row_bytes / t / 1e9, 2)
        log(f"{name}: {t:.3f}s (pandas {c:.3f}s)")
        emit()

    # --- operator-fusion A/B on the headline queries: re-time q6/q3
    # with srt.exec.fusion.enabled=false in a fresh session and record
    # the unfused walls + speedups next to the fused headline numbers
    if fusion_mode == "both" and left("fusion A/B", need=60):
        try:
            RESULT["fusion_counters"] = fusion_counters()
            unfused_sess = framework_session(
                {"srt.exec.fusion.enabled": "false"})
            unfused_q = framework_queries(unfused_sess, paths)
            # iteration counts MUST mirror the fused headline lanes
            # (q6 ran ITERS, q3 ran ITERS-1) or min-of-N asymmetry
            # masquerades as a fusion delta on noisy boxes
            for name, iters in (("q6", ITERS), ("q3", max(ITERS - 1, 1))):
                if f"{name}_s" not in RESULT or not left(
                        f"fusion A/B {name}", need=45):
                    continue
                t0 = time.perf_counter()
                unfused_q[name]()  # warm: compile the unfused plans
                RESULT[f"{name}_unfused_first_s"] = round(
                    time.perf_counter() - t0, 4)
                t = _best(unfused_q[name], iters)
                RESULT[f"{name}_unfused_s"] = round(t, 4)
                RESULT[f"{name}_fusion_speedup"] = round(
                    t / RESULT[f"{name}_s"], 3)
                log(f"{name} unfused: {t:.3f}s (fusion speedup "
                    f"{RESULT[f'{name}_fusion_speedup']}x)")
            emit()
        except Exception as e:  # A/B must never kill the headline run
            log(f"fusion A/B failed: {e}")

    # --- kernel-only q6 + measured roofline (HBM utilization estimate)
    if backend == "cpu":
        global KERNEL_ITERS
        KERNEL_ITERS = 3  # ~3.5s/iter on a CPU rehearsal
    if left("kernel metrics", need=60):
        kq6 = kernel_q6_seconds()
        peak = measured_peak_bw_gbs()
        kernel_bytes_s = KERNEL_ROWS * (4 * 4) / kq6  # 4 f32/i32 cols
        RESULT.update({
            "q6_kernel_mrows_s": round(KERNEL_ROWS / kq6 / 1e6, 1),
            "kernel_hbm_util_est": round(kernel_bytes_s / 1e9 / peak, 4),
            "measured_peak_gb_s": round(peak, 1),
        })
        log(f"kernel q6: {kq6 * 1e3:.2f}ms, peak {peak:.0f} GB/s")
    # --- BASELINE config 4: Delta MERGE/UPDATE-heavy upsert ----------------
    if left("delta merge", need=45):
        try:
            import shutil
            import tempfile

            import numpy as np

            from spark_rapids_tpu.columnar import dtypes as dt
            from spark_rapids_tpu.delta.table import AcidTable
            from spark_rapids_tpu.expr.core import col, lit

            n = max(scale // 40, 10_000)
            half = n // 2
            sess = framework_session()
            tgt_dir = tempfile.mkdtemp(prefix="srt_delta_bench_")
            try:
                schema = [("k", dt.INT64), ("amount", dt.FLOAT64),
                          ("flag", dt.INT32)]
                tab = AcidTable.create(sess, tgt_dir, schema)
                rng = np.random.default_rng(0)
                base = sess.create_dataframe(
                    {"k": list(range(n)),
                     "amount": rng.uniform(0, 1e4, n).tolist(),
                     "flag": [0] * n}, schema)
                tab.append(base)
                # upsert: half the keys match (update), half are new
                src = sess.create_dataframe(
                    {"k": list(range(half, n + half)),
                     "amount": rng.uniform(0, 1e4, n).tolist(),
                     "flag": [1] * n}, schema)
                t0 = time.perf_counter()
                tab.merge(src, on=["k"], when_matched_update={
                    "amount": col("src_amount"), "flag": col("src_flag")})
                tab.update({"flag": col("flag") + lit(2)},
                           col("amount") > lit(5e3))
                merge_s = time.perf_counter() - t0
                RESULT["delta_merge_s"] = round(merge_s, 3)
                RESULT["delta_merge_rows_s"] = round(
                    2 * n / merge_s / 1e6, 3)  # target+source rows/s, M
                # pandas-equivalent baseline: same upsert + update
                # against parquet on disk (read, merge, rewrite)
                cpu_s = _best(lambda: pandas_delta_merge(n, half), 1)
                RESULT["delta_vs_baseline"] = round(cpu_s / merge_s, 3)
                log(f"delta merge+update ({n} target rows): "
                    f"{merge_s:.2f}s (pandas {cpu_s:.2f}s)")
                emit()
            finally:
                shutil.rmtree(tgt_dir, ignore_errors=True)
        except Exception as e:
            log(f"delta merge bench failed: {e}")

    # --- streaming micro-batch ingestion (exactly-once commit path) -------
    # measures the transactional lane end-to-end: stage -> fsync ->
    # rename -> O_EXCL commit -> txn bookkeeping, once with durable
    # commits (the shipped default) and once relaxed, so the fsync
    # tax on the exactly-once guarantee is a tracked number
    if left("streaming ingest", need=30):
        try:
            import shutil
            import tempfile

            from spark_rapids_tpu.delta.streaming import (DeltaIngestor,
                                                          demo_batch_dict,
                                                          demo_schema)
            from spark_rapids_tpu.delta.table import AcidTable

            batches = 16
            rows_per = max(scale // 400, 2_000)

            def run_ingest(durable: bool) -> float:
                sess = framework_session(
                    {"srt.delta.durableCommits": str(durable).lower(),
                     "srt.delta.checkpointInterval": "8"})
                d = tempfile.mkdtemp(prefix="srt_ingest_bench_")
                try:
                    tab = AcidTable.create(sess, d, demo_schema())

                    def bf(b):
                        return sess.create_dataframe(
                            demo_batch_dict(b, rows_per), demo_schema())

                    t0 = time.perf_counter()
                    DeltaIngestor(tab, "bench").ingest(bf, batches)
                    return time.perf_counter() - t0
                finally:
                    shutil.rmtree(d, ignore_errors=True)

            total = batches * rows_per
            durable_s = run_ingest(True)
            relaxed_s = run_ingest(False)
            RESULT["ingest_rows_per_s"] = round(total / durable_s, 1)
            RESULT["ingest_relaxed_rows_per_s"] = round(
                total / relaxed_s, 1)
            RESULT["ingest_batch_commit_ms"] = round(
                durable_s / batches * 1e3, 2)
            RESULT["ingest_durable_overhead_pct"] = round(
                (durable_s / relaxed_s - 1) * 100, 1)
            log(f"streaming ingest ({batches}x{rows_per} rows): "
                f"{RESULT['ingest_rows_per_s']:.0f} rows/s durable "
                f"({RESULT['ingest_durable_overhead_pct']}% fsync tax)")
            emit()
        except Exception as e:
            log(f"streaming ingest bench failed: {e}")

    # --- BASELINE config 5: Mortgage ETL -> device arrays (ML hand-off) ---
    if left("mortgage etl", need=45):
        try:
            from spark_rapids_tpu.models.mortgage import (mortgage_etl,
                                                          mortgage_tables)
            n_loans = max(scale // 60, 5_000)
            mort_dir = os.path.join(os.path.dirname(data_dir),
                                    f"mortgage_{n_loans}")
            sess = framework_session()
            tables = mortgage_tables(sess, mort_dir, n_loans=n_loans)
            perf_rows = n_loans * 12

            def run_etl():
                feats = mortgage_etl(tables["acquisitions"],
                                     tables["performance"])
                # ML hand-off: device-resident dense arrays
                # (ColumnarRdd -> XGBoost role)
                arrs = feats.to_device_arrays()
                return arrs

            t0 = time.perf_counter()
            run_etl()  # warm
            RESULT["mortgage_first_s"] = round(
                time.perf_counter() - t0, 3)
            etl_s = _best(run_etl, max(ITERS - 1, 1))
            c = _best(lambda: pandas_mortgage(mort_dir), 1)
            RESULT["mortgage_etl_s"] = round(etl_s, 3)
            RESULT["mortgage_rows_s"] = round(perf_rows / etl_s / 1e6, 3)
            RESULT["mortgage_vs_baseline"] = round(c / etl_s, 3)
            RESULT["mortgage_effective_gb_s"] = round(
                perf_rows * MORTGAGE_BYTES_PER_ROW / etl_s / 1e9, 2)
            log(f"mortgage etl ({perf_rows} perf rows): {etl_s:.2f}s "
                f"(pandas {c:.2f}s)")
            emit()
        except Exception as e:
            log(f"mortgage bench failed: {e}")

    # --- adaptive skew-join A/B: a seeded >=10x-skewed fact joined
    # against a small dim under WRONG compile-time settings (broadcast
    # disabled by a 1-row threshold), adaptive on vs off. Adaptive
    # demotes the shuffled join from the MEASURED build size — skipping
    # the probe-side shuffle entirely — while "off" pays the full
    # mis-planned shuffle of every fact row. Warm timings (second run)
    # so the delta is execution, not compile.
    if left("adaptive skew join", need=45):
        try:
            import numpy as np

            from spark_rapids_tpu.expr.aggregates import (CountStar,
                                                          Sum)
            from spark_rapids_tpu.expr.core import Alias, col as _col
            n_sk = max(scale // 3, 100_000)
            rng = np.random.default_rng(97)
            sk_keys = np.where(rng.random(n_sk) < 0.9, 7,
                               rng.integers(0, 100, n_sk))
            sk_dir = os.path.join(os.path.dirname(data_dir),
                                  f"skew_{n_sk}")
            if not os.path.isdir(sk_dir):
                base_sess = framework_session()
                base_sess.create_dataframe({
                    "k": sk_keys.tolist(),
                    "v": rng.uniform(0, 10, n_sk).tolist(),
                }).write.parquet(os.path.join(sk_dir, "fact"))
                base_sess.create_dataframe({
                    "k": list(range(100)),
                    "w": [float(i) for i in range(100)],
                }).write.parquet(os.path.join(sk_dir, "dim"))

            def run_skew(adaptive_on):
                sess = framework_session({
                    "srt.shuffle.partitions": 8,
                    "srt.sql.broadcastRowThreshold": 1,
                    "srt.sql.adaptive.enabled":
                        "true" if adaptive_on else "false",
                    "srt.sql.adaptive.autoBroadcastJoinRows": 100000})
                f = sess.read.parquet(os.path.join(sk_dir, "fact"))
                d = sess.read.parquet(os.path.join(sk_dir, "dim"))
                q = f.join(d, ([_col("k")], [_col("k")]),
                           how="inner") \
                    .agg(Alias(Sum(_col("v")), "sv"),
                         Alias(CountStar(), "c"))
                q.collect()  # warm: compile + plan
                t0 = time.perf_counter()
                rows = q.collect()
                return time.perf_counter() - t0, rows

            on_s, on_rows = run_skew(True)
            off_s, off_rows = run_skew(False)
            if on_rows[0]["c"] != off_rows[0]["c"]:
                log(f"adaptive skew join DIVERGED: "
                    f"{on_rows} vs {off_rows}")
            else:
                RESULT["skew_join_rows"] = n_sk
                RESULT["skew_join_adaptive_on_s"] = round(on_s, 3)
                RESULT["skew_join_adaptive_off_s"] = round(off_s, 3)
                RESULT["skew_join_adaptive_speedup"] = round(
                    off_s / on_s, 3) if on_s else 0.0
                log(f"adaptive skew join ({n_sk} rows, 90% hot key): "
                    f"on={on_s:.3f}s off={off_s:.3f}s "
                    f"({RESULT['skew_join_adaptive_speedup']}x)")
            emit()
        except Exception as e:
            log(f"adaptive skew join bench failed: {e}")

    # --- push-shuffle A/B (shuffle-phase micro-bench): a seeded skewed
    # wide exchange driven at the transport layer — two in-process
    # manager+server nodes, every map's blocks written on both, then
    # the READ phase (what a released reducer actually waits on) timed
    # with eager push + per-reducer segment consolidation vs classic
    # per-block pull. The in-process _LOCAL_ENDPOINTS short-circuit is
    # narrowed to each reader's OWN endpoint during the fetch so the
    # peer's blocks travel real sockets in both legs, matching the
    # production topology. A local-session lane records the zero-copy
    # bypass byte count.
    if left("shuffle A/B", need=30):
        try:
            import numpy as np

            from spark_rapids_tpu.columnar.vector import batch_from_pydict
            from spark_rapids_tpu.conf import SrtConf
            from spark_rapids_tpu.parallel import transport as _T
            from spark_rapids_tpu.parallel.shuffle_manager import (
                ShuffleManager, reset_shuffle_manager, shuffle_manager)
            from spark_rapids_tpu.parallel.transport import (
                ShuffleBlockServer, fetch_all_partitions)

            n_maps, n_parts, base_rows = 12, 8, 20000
            rng = np.random.default_rng(11)
            vals = rng.uniform(0, 1, base_rows * 6)

            def shuffle_leg(push_on):
                conf = SrtConf({
                    "srt.shuffle.mode": "MULTITHREADED",
                    "srt.shuffle.push.enabled":
                        "true" if push_on else "false"})
                nodes = [ShuffleManager(conf) for _ in range(2)]
                servers = [ShuffleBlockServer(m) for m in nodes]
                eps = [srv.endpoint for srv in servers]
                sid = 9100 + int(push_on)
                lat, rows = [], 0
                try:
                    # map phase on both nodes; partition 0 is the hot
                    # (6x) skew partition; push uploads each map's
                    # blocks at completion, bounded by the in-flight
                    # window, and drains before the "barrier"
                    t0 = time.perf_counter()
                    for w, mgr in enumerate(nodes):
                        mgr.register_shuffle(sid, n_parts)
                        route = {pp: eps[pp % 2] for pp in range(n_parts)}
                        for m in range(n_maps):
                            parts = [batch_from_pydict(
                                {"v": vals[:base_rows * 6 if pp == 0
                                           else base_rows].tolist()})
                                for pp in range(n_parts)]
                            mgr.write_map_output(sid, m, parts)
                            if push_on:
                                mgr.push_map_output(sid, m, route)
                        if push_on:
                            mgr.drain_pushes()
                    write_s = time.perf_counter() - t0

                    # read phase: each node fetches its owned
                    # partitions from both endpoints; only the
                    # reader's own endpoint may short-circuit. The
                    # fetch is idempotent (segment snapshot + pull
                    # with excludes), so best-of-3 like the headline
                    # queries — one pass is too noisy on a shared box
                    def read_pass():
                        got_rows, pass_lat = 0, []
                        t0 = time.perf_counter()
                        for w, mgr in enumerate(nodes):
                            _T._LOCAL_ENDPOINTS.clear()
                            _T._LOCAL_ENDPOINTS[eps[w]] = mgr
                            for pp in range(w, n_parts, 2):
                                tf = time.perf_counter_ns()
                                for b in fetch_all_partitions(
                                        eps, sid, pp, manager=mgr):
                                    got_rows += int(b.num_rows)
                                pass_lat.append(
                                    time.perf_counter_ns() - tf)
                        return (time.perf_counter() - t0, pass_lat,
                                got_rows)

                    saved = dict(_T._LOCAL_ENDPOINTS)
                    try:
                        read_s, lat, rows = min(
                            (read_pass() for _ in range(3)),
                            key=lambda r: r[0])
                    finally:
                        _T._LOCAL_ENDPOINTS.clear()
                        _T._LOCAL_ENDPOINTS.update(saved)
                finally:
                    for srv in servers:
                        srv.close()
                lat.sort()
                p99 = lat[min(len(lat) - 1,
                              max(0, int(len(lat) * 0.99)))]
                return write_s, read_s, p99, rows

            legs = {"push": [True], "pull": [False],
                    "both": [True, False]}[shuffle_mode]
            got = {}
            for on in legs:
                tag = "push" if on else "pull"
                w_s, r_s, p99, rows = shuffle_leg(on)
                got[tag] = (r_s, rows)
                RESULT[f"nds_shuffle_{tag}_write_s"] = round(w_s, 4)
                RESULT[f"nds_shuffle_{tag}_read_s"] = round(r_s, 4)
                RESULT[f"nds_shuffle_{tag}_fetch_p99_ns"] = p99
                log(f"shuffle [{tag}]: write={w_s:.3f}s "
                    f"read={r_s:.3f}s p99={p99 / 1e6:.1f}ms "
                    f"rows={rows}")
            if len(got) == 2:
                if got["push"][1] != got["pull"][1]:
                    log(f"shuffle A/B DIVERGED: {got}")
                else:
                    RESULT["nds_shuffle_push_speedup"] = round(
                        got["pull"][0] / got["push"][0], 3) \
                        if got["push"][0] else 0.0
                    log(f"shuffle A/B: push read is "
                        f"{RESULT['nds_shuffle_push_speedup']}x pull")
            # zero-copy lane: a local MULTITHREADED session under push
            # hands live batches through the device catalog — count
            # the bytes that skipped serialize/socket/deserialize
            if shuffle_mode != "pull":
                by_conf = SrtConf({
                    "srt.shuffle.mode": "MULTITHREADED",
                    "srt.shuffle.partitions": 4})
                reset_shuffle_manager(by_conf)
                try:
                    from spark_rapids_tpu.expr.aggregates import Sum
                    from spark_rapids_tpu.expr.core import Alias, col
                    from spark_rapids_tpu.plan.session import TpuSession
                    sess = TpuSession(by_conf)
                    sess.create_dataframe({
                        "k": [int(x) for x in rng.integers(0, 50, 20000)],
                        "v": rng.uniform(0, 1, 20000).tolist(),
                    }).group_by("k").agg(Alias(Sum(col("v")), "s")) \
                        .collect()
                    RESULT["nds_shuffle_bytes_bypassed"] = \
                        shuffle_manager().bypassed_bytes
                    log(f"shuffle local bypass: "
                        f"{RESULT['nds_shuffle_bytes_bypassed']} bytes "
                        f"zero-copy")
                finally:
                    reset_shuffle_manager()
            emit()
        except Exception as e:  # A/B must never kill the headline run
            log(f"shuffle A/B failed: {e}")

    # --- SPMD mesh lane (stage-per-program executor), a VIRTUAL-MESH
    # REHEARSAL: the five scale-subset NDS shapes through
    # tools/mesh_nds.py, ONE SUBPROCESS per query, each pinned to an
    # 8-virtual-device CPU mesh — this process holds the chip, and a
    # chip belongs to one process at a time, so the children must
    # never ask for it. (The real four-chip mesh path is
    # `python chip_smoke.py --chips 4`, one process.) Records
    # mesh_<q>_s walls plus the stage-boundary
    # byte split: shuffle_bytes_bypassed (device-resident, never
    # serialized — gate-protected, shrinking it means stages fell
    # back to serialization) and shuffle_bytes_wire (the subset that
    # rode in-program collectives). "both" adds a serialized
    # single-stream leg per shape (mesh_off_<q>_s).
    mesh_mode = os.environ.get("SRT_BENCH_MESH", "on").lower()
    if mesh_mode != "off" and left("mesh lane",
                                   need=90 + SERVE_RESERVE):
        try:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            import mesh_nds
            # a CPU rehearsal keeps the toy scale (matching
            # nds_scale): each shape is a fresh subprocess with its
            # own compile, and 20k-row programs on a small emulation
            # box cost tens of seconds each — starving the NDS sweep
            mesh_scale = int(os.environ.get(
                "SRT_BENCH_MESH_SCALE",
                20000 if backend != "cpu" else 8000))
            mesh_shapes = list(mesh_nds.SCALE_SUBSET)

            def mesh_lane() -> dict:
                got: dict = {}
                bypassed = wire = 0
                for qid in mesh_shapes:
                    if not left(f"mesh {qid}",
                                need=45 + SERVE_RESERVE):
                        break
                    rec = mesh_nds.bench_one_subprocess(
                        qid, mesh_scale, 8,
                        ab=(mesh_mode == "both"), timeout_s=600)
                    if not rec.get("ok"):
                        log(f"mesh {qid}: FAILED {rec.get('error')}")
                        continue
                    got[f"mesh_{qid}_s"] = rec["mesh_s"]
                    if "off_s" in rec:
                        got[f"mesh_off_{qid}_s"] = rec["off_s"]
                    bypassed += rec["bypassed"]
                    wire += rec["wire"]
                    log(f"mesh {qid}: {rec['mesh_s']}s (first "
                        f"{rec['mesh_first_s']}s, {rec['stages']} "
                        f"stages, {rec['bypassed']} B bypassed)"
                        + (f" vs {rec['off_s']}s serialized"
                           if "off_s" in rec else ""))
                if got:
                    got["shuffle_bytes_bypassed"] = bypassed
                    got["shuffle_bytes_wire"] = wire
                    # never a device number, whatever RESULT["backend"]
                    got["virtual_mesh_lane"] = "8 virtual CPU devices"
                return got

            RESULT.update(mesh_lane())
            RERUN_LANES["mesh"] = {
                "match": lambda k: (k.startswith("mesh_")
                                    or k in ("shuffle_bytes_bypassed",
                                             "shuffle_bytes_wire")),
                "rerun": mesh_lane,
            }
            emit()
        except Exception as e:  # lane must never kill the headline run
            log(f"mesh lane failed: {e}")

    # --- NDS mini power-run (BASELINE config 2 breadth evidence):
    # the full 99-query suite swept once, total wall + per-query
    # recorded. SRT_BENCH_PIPELINE selects the async-pipeline lane:
    # "on" (default, srt.exec.pipeline.enabled=true), "off" (sync
    # execution), or "both" — an A/B sweep whose record carries both
    # lanes' walls plus the pipelined-vs-sync delta over the queries
    # BOTH lanes completed (budget cuts can truncate either lane).
    if left("nds power run", need=60):
        try:
            from spark_rapids_tpu.models.nds import (NDS_QUERIES,
                                                     register_nds)
            # chip lane runs the suite at 100k store_sales rows (the
            # differential-proof scale); a CPU rehearsal keeps the
            # toy scale so the sweep fits the budget
            nds_scale = int(os.environ.get(
                "SRT_BENCH_NDS_SCALE",
                100_000 if backend != "cpu" else 8000))
            nds_dir = os.path.join(os.path.dirname(data_dir),
                                   f"nds_{nds_scale}")
            pipe_mode = os.environ.get("SRT_BENCH_PIPELINE",
                                       "on").lower()
            # SRT_BENCH_ADAPTIVE=both / SRT_BENCH_FUSION=both take
            # over the NDS A/B dimension (adaptive wins when both are
            # requested; one A/B dimension per sweep keeps it readable)
            if adaptive_mode == "both":
                leg_conf, leg_dim = "srt.sql.adaptive.enabled", \
                    "adaptive"
                legs = [("on", "true"), ("off", "false")]
            elif fusion_mode == "both":
                leg_conf, leg_dim = "srt.exec.fusion.enabled", "fusion"
                legs = [("on", "true"), ("off", "false")]
            else:
                leg_conf, leg_dim = "srt.exec.pipeline.enabled", \
                    "pipeline"
                legs = {"on": [("on", "true")],
                        "off": [("off", "false")],
                        "both": [("on", "true"), ("off", "false")]}.get(
                    pipe_mode, [("on", "true")])
            RESULT["nds_pipeline_mode"] = pipe_mode
            RESULT["nds_ab_dimension"] = leg_dim
            import gc

            from spark_rapids_tpu import jit_registry as _jitreg

            # cheap-first static order (round-5 measured warm walls on
            # the CPU lane): a budget cut then truncates the heavy
            # TAIL, so queries_run is maximal for any budget — the
            # record still carries per-query walls for every query run
            nds_order = [
                "q68", "q16", "q96", "q93", "q89", "q25", "q84", "q28",
                "q9", "q24", "q54", "q63", "q88", "q10", "q8", "q64",
                "q99", "q15", "q2", "q26", "q7", "q39", "q34", "q90",
                "q3", "q42", "q29", "q19", "q73", "q48", "q30", "q37",
                "q1", "q55", "q17", "q21", "q23", "q13", "q91", "q71",
                "q43", "q52", "q85", "q95", "q33", "q41", "q82", "q79",
                "q40", "q87", "q94", "q20", "q92", "q97", "q65", "q12",
                "q32", "q69", "q31", "q45", "q6", "q27", "q50", "q81",
                "q74", "q78", "q35", "q77", "q58", "q86", "q72", "q83",
                "q61", "q59", "q46", "q56", "q76", "q60", "q36", "q11",
                "q75", "q44", "q4", "q5", "q98", "q53", "q70", "q49",
                "q62", "q66", "q18", "q22", "q14", "q38", "q51", "q80",
                "q67", "q57", "q47"]
            ordered = [q for q in nds_order if q in NDS_QUERIES] + \
                sorted(set(NDS_QUERIES) - set(nds_order))

            def run_leg(label, enabled, key_prefix, deadline=None):
                nds_sess = framework_session({leg_conf: enabled})
                register_nds(nds_sess, nds_dir, scale_rows=nds_scale)
                # drop the previous lane's in-memory executables before
                # the 70-query sweep (see the % 5 clear below); the
                # shared-program wrappers hold AOT executables jax's
                # own caches don't track, so release those too
                jax.clear_caches()
                _jitreg.release_executables()
                gc.collect()
                t0 = time.perf_counter()
                done = 0
                per_q = {}
                fuse0 = fusion_counters()

                def snapshot():
                    RESULT[f"{key_prefix}queries_run"] = done
                    RESULT["nds_scale_rows"] = nds_scale
                    RESULT[f"{key_prefix}per_query_s"] = dict(per_q)
                    RESULT[f"{key_prefix}total_s"] = round(
                        time.perf_counter() - t0, 2)
                for qid in ordered:
                    if not left(f"nds {qid} [{label}]",
                                need=20 + SERVE_RESERVE):
                        break
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        log(f"leg budget exhausted before "
                            f"nds {qid} [{label}]")
                        break
                    tq = time.perf_counter()
                    nds_sess.sql(NDS_QUERIES[qid]).collect()
                    per_q[qid] = round(time.perf_counter() - tq, 2)
                    done += 1
                    if done % 10 == 0:
                        # progressive record: a crash mid-suite still
                        # leaves the completed queries on stdout
                        snapshot()
                        emit()
                    if done % 5 == 0 and _rss_fraction() > 0.35:
                        # in-memory jit/executable caches grow without
                        # bound across 70+ distinct heavy queries and
                        # can exhaust host RAM (LLVM 'Cannot allocate
                        # memory' -> SIGSEGV); the persistent DISK
                        # compile cache keeps re-runs cheap, so when
                        # resident size nears the host's memory drop
                        # the in-memory layer — trading a little
                        # re-trace time for survival (unconditional
                        # clearing cost ~30%+ of sweep time on big-RAM
                        # boxes that never needed it)
                        nds_sess._plan_cache.clear()
                        jax.clear_caches()
                        _jitreg.release_executables()
                        gc.collect()
                snapshot()
                fuse1 = fusion_counters()
                # per-leg deltas: chains planned during this leg + the
                # fused-program jit cache's hit/miss counts (hits =
                # partitions/queries that reused a compiled program)
                RESULT[f"{key_prefix}fusion"] = {
                    "chains": fuse1["chains"] - fuse0["chains"],
                    "stages": fuse1["stages"] - fuse0["stages"],
                    "jit_hits": (fuse1["registry"]["hits"]
                                 - fuse0["registry"]["hits"]),
                    "jit_misses": (fuse1["registry"]["misses"]
                                   - fuse0["registry"]["misses"]),
                }
                log(f"nds power run [{leg_dim}={label}]: "
                    f"{done}/{len(NDS_QUERIES)} queries in "
                    f"{RESULT[f'{key_prefix}total_s']}s "
                    f"(fusion {RESULT[f'{key_prefix}fusion']})")
                emit()
                return per_q

            if len(legs) == 1:
                # single lane keeps the historical record keys
                run_leg(legs[0][0], legs[0][1], "nds_")
            else:
                walls = {}
                # split the remaining budget evenly so the first lane
                # can't starve the second — an A/B with an empty off
                # lane has no common queries and records no delta
                rem = BUDGET - (time.monotonic() - T_START) \
                    - SERVE_RESERVE
                for i, (label, enabled) in enumerate(legs):
                    share = rem / len(legs) * (i + 1)
                    walls[label] = run_leg(
                        label, enabled, f"nds_{leg_dim}_{label}_"
                        if leg_dim in ("fusion", "adaptive")
                        else f"nds_{label}_",
                        deadline=T_START + (BUDGET - rem) + share)
                # delta over the queries BOTH lanes completed — a
                # budget cut mid-lane must not skew the comparison
                common = sorted(set(walls["on"]) & set(walls["off"]))
                if common:
                    on_s = sum(walls["on"][q] for q in common)
                    off_s = sum(walls["off"][q] for q in common)
                    if leg_dim == "adaptive":
                        RESULT["nds_adaptive_common_queries"] = \
                            len(common)
                        RESULT["nds_adaptive_on_common_s"] = \
                            round(on_s, 2)
                        RESULT["nds_adaptive_off_common_s"] = \
                            round(off_s, 2)
                        # >0: adaptive saved wall; <0: it cost wall
                        RESULT["nds_adaptive_delta_pct"] = round(
                            100.0 * (off_s - on_s) / off_s, 2) \
                            if off_s else 0.0
                        delta = RESULT["nds_adaptive_delta_pct"]
                    elif leg_dim == "fusion":
                        RESULT["nds_fusion_common_queries"] = \
                            len(common)
                        RESULT["nds_fused_common_s"] = round(on_s, 2)
                        RESULT["nds_unfused_common_s"] = round(off_s, 2)
                        # >0: fusion saved wall; <0: it cost wall
                        RESULT["nds_fusion_delta_pct"] = round(
                            100.0 * (off_s - on_s) / off_s, 2) \
                            if off_s else 0.0
                        delta = RESULT["nds_fusion_delta_pct"]
                    else:
                        RESULT["nds_pipeline_common_queries"] = \
                            len(common)
                        RESULT["nds_pipelined_common_s"] = round(on_s, 2)
                        RESULT["nds_sync_common_s"] = round(off_s, 2)
                        # >0: pipelining saved wall; <0: it cost wall
                        RESULT["nds_pipeline_delta_pct"] = round(
                            100.0 * (off_s - on_s) / off_s, 2) \
                            if off_s else 0.0
                        delta = RESULT["nds_pipeline_delta_pct"]
                    log(f"nds {leg_dim} A/B over {len(common)} common "
                        f"queries: on={on_s:.2f}s off={off_s:.2f}s "
                        f"delta={delta}%")
                emit()
        except Exception as e:  # breadth stage must never kill the bench
            log(f"nds power run failed: {e}")

    # --- serving lane (SRT_BENCH_SERVE=1): sustained-QPS multi-tenant
    # window through the socket front door (tools/serve_bench.py) — 4
    # replay clients against one SqlServer for >=30s of Zipf-mixed NDS
    # traffic, recording per-tier latency quantiles, sustained QPS,
    # and the result-cache / plan-cache hit rates the gate enforces
    if os.environ.get("SRT_BENCH_SERVE", "") == "1" and \
            left("serving lane", need=120):
        try:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            from serve_bench import run_serve_bench
            serve_scale = int(os.environ.get(
                "SRT_BENCH_NDS_SCALE",
                100_000 if backend != "cpu" else 8000))
            serve_keys = run_serve_bench(
                duration_s=float(os.environ.get(
                    "SRT_BENCH_SERVE_SECONDS", 35)),
                clients=int(os.environ.get(
                    "SRT_BENCH_SERVE_CLIENTS", 4)),
                qps=float(os.environ.get("SRT_BENCH_SERVE_QPS", 8)),
                scale_rows=serve_scale,
                data_dir=os.path.join(os.path.dirname(data_dir),
                                      f"nds_{serve_scale}"),
                log=log)
            RESULT.update(serve_keys)
            emit()
        except Exception as e:  # serving lane must never kill the run
            log(f"serving lane failed: {e}")

    embed_metrics()
    embed_compile_ledger()
    gate_ok = run_perf_gate()
    dump_metrics_snapshot()
    emit(final=True)
    if not gate_ok:
        sys.exit(3)


if __name__ == "__main__":
    main()
