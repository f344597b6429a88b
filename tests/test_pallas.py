"""Fused pallas filter+aggregate path (ops/pallas_kernels.py +
exec/pallas_agg.py). The CPU lane runs the kernel in pallas interpret
mode, so these tests exercise the real kernel logic (tiling, masking,
per-tile partials) end to end, differentially against the stock XLA
path."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.expr.aggregates import (Average, Count, CountStar,
                                              Max, Min, Sum)
from spark_rapids_tpu.expr.core import Alias
from spark_rapids_tpu.expr.predicates import InSet
from spark_rapids_tpu.ops.pallas_kernels import MAX, MIN, SUM, tile_reduce
from spark_rapids_tpu.plan import overrides
from spark_rapids_tpu.plan.session import TpuSession


def test_tile_reduce_kinds():
    rng = np.random.default_rng(0)
    n = 20_000  # > 2 tiles, non-multiple tail
    x = jnp.asarray(rng.uniform(-50, 50, n))
    m = jnp.asarray((rng.integers(0, 2, n)).astype(np.uint8))

    def row_fn(blocks):
        xb, mb = blocks
        mask = mb != 0
        return [jnp.where(mask, xb, 0.0),
                mask.astype(jnp.float32),
                jnp.where(mask, xb, jnp.inf),
                jnp.where(mask, xb, -jnp.inf)]

    s, c, lo, hi = tile_reduce([x, m], row_fn, [SUM, SUM, MIN, MAX])
    ref = np.asarray(x)[np.asarray(m) != 0]
    assert np.isclose(float(s), ref.sum())
    assert float(c) == len(ref)
    assert float(lo) == ref.min()
    assert float(hi) == ref.max()


def test_tile_reduce_single_small_tile():
    x = jnp.asarray([1.0, 2.0, 3.0])
    m = jnp.asarray([1, 0, 1], dtype=jnp.uint8)
    (s,) = tile_reduce([x, m], lambda b: [jnp.where(b[1] != 0, b[0], 0.0)],
                       [SUM])
    assert float(s) == 4.0


def _metric(ctx: ExecContext, name: str) -> int:
    total = 0
    for ms in ctx.metrics.values():
        if name in ms:
            total += ms[name].value
    return total


def _collect(node, conf):
    from spark_rapids_tpu.columnar.vector import batch_to_pydict
    ctx = ExecContext(conf)
    rows = []
    for b in node.execute(ctx):
        d = batch_to_pydict(b)
        rows.extend(dict(zip(d, vals)) for vals in zip(*d.values()))
    return rows, ctx


def _run(plan, conf):
    return _collect(overrides.apply_overrides(plan, conf), conf)


@pytest.fixture
def fused_query():
    rng = np.random.default_rng(7)
    n = 4000
    data = {
        "v": rng.uniform(0, 100, n).tolist(),
        "w": rng.uniform(0, 1, n).tolist(),
        "d": rng.integers(8000, 9000, n).tolist(),
    }
    for i in range(0, n, 11):
        data["v"][i] = None

    def make(conf):
        session = TpuSession(conf)
        df = session.create_dataframe({k: list(v) for k, v in data.items()})
        return (df.filter((col("w") >= 0.25) & (col("w") < 0.75) &
                          (col("d") < 8800))
                .agg(Alias(Sum(col("v") * col("w")), "rev"),
                     Alias(CountStar(), "cnt"),
                     Alias(Count(col("v")), "cv"),
                     Alias(Min(col("v")), "mn"),
                     Alias(Max(col("v")), "mx"),
                     Alias(Average(col("v")), "av")))
    return make


def test_fused_agg_matches_xla_path(fused_query):
    on = SrtConf({"srt.sql.pallas.enabled": True})
    off = SrtConf({"srt.sql.pallas.enabled": False})
    rows_on, ctx_on = _run(fused_query(on).plan, on)
    rows_off, ctx_off = _run(fused_query(off).plan, off)
    assert _metric(ctx_on, "pallasBatches") > 0
    assert _metric(ctx_off, "pallasBatches") == 0
    (a,), (b,) = rows_on, rows_off
    assert a["cnt"] == b["cnt"] and a["cv"] == b["cv"]
    for k in ("rev", "mn", "mx", "av"):
        assert a[k] == pytest.approx(b[k], rel=1e-12), k


def test_fused_agg_empty_input():
    conf = SrtConf({})
    session = TpuSession(conf)
    df = session.create_dataframe({"v": [1.0, 2.0], "w": [0.1, 0.2]})
    q = df.filter(col("w") > 5.0).agg(Alias(Sum(col("v")), "s"),
                                      Alias(CountStar(), "n"))
    rows, _ = _run(q.plan, conf)
    assert rows == [{"s": None, "n": 0}]


def test_gate_rejects_grouped_and_string():
    conf = SrtConf({})
    session = TpuSession(conf)
    df = session.create_dataframe({
        "k": ["a", "b", "a"], "v": [1.0, 2.0, 3.0]})
    # grouped -> no pallas, still correct
    rows, ctx = _run(df.group_by("k").agg(Alias(Sum(col("v")), "s")).plan,
                     conf)
    assert _metric(ctx, "pallasBatches") == 0
    assert sorted((r["k"], r["s"]) for r in rows) == [("a", 4.0),
                                                      ("b", 2.0)]
    # string min -> gate miss, still correct
    rows, ctx = _run(df.agg(Alias(Min(col("k")), "m")).plan, conf)
    assert _metric(ctx, "pallasBatches") == 0
    assert rows == [{"m": "a"}]


def test_string_predicate_fuses(tmp_path):
    """String predicates (col='lit', IN set, startswith, IS NULL) lower
    into the byte-lane kernel family: the fused path runs AND matches
    the stock XLA path bit-for-bit on row selection."""
    rng = np.random.default_rng(3)
    n = 5000
    cats = ["alpha", "beta", "gamma", "al", None]
    data = {
        "c": [cats[i] for i in rng.integers(0, len(cats), n)],
        "v": rng.uniform(0, 100, n).tolist(),
    }

    def make(conf, pred):
        session = TpuSession(conf)
        df = session.create_dataframe({k: list(v)
                                       for k, v in data.items()})
        return df.filter(pred).agg(Alias(Sum(col("v")), "s"),
                                   Alias(CountStar(), "n"))

    from spark_rapids_tpu.expr import lit
    from spark_rapids_tpu.expr.predicates import IsNotNull
    from spark_rapids_tpu.expr.strings import StartsWith
    preds = [
        col("c") == lit("alpha"),
        InSet(col("c"), ["beta", "gamma", "nope"]),
        StartsWith(col("c"), "al"),
        IsNotNull(col("c")) & (col("v") > lit(50.0)),
    ]
    on = SrtConf({"srt.sql.pallas.enabled": True})
    off = SrtConf({"srt.sql.pallas.enabled": False})
    for pred in preds:
        rows_on, ctx_on = _run(make(on, pred).plan, on)
        rows_off, ctx_off = _run(make(off, pred).plan, off)
        assert _metric(ctx_on, "pallasBatches") > 0, repr(pred)
        (a,), (b,) = rows_on, rows_off
        assert a["n"] == b["n"], repr(pred)
        assert a["s"] == pytest.approx(b["s"], rel=1e-12), repr(pred)


def test_fused_int_sum_falls_back():
    """Integral sums must keep the exact XLA path (int64 state)."""
    conf = SrtConf({})
    session = TpuSession(conf)
    big = (1 << 40)
    df = session.create_dataframe({"v": [big, big + 1, big + 2]})
    rows, ctx = _run(df.agg(Alias(Sum(col("v")), "s")).plan, conf)
    assert _metric(ctx, "pallasBatches") == 0
    assert rows == [{"s": 3 * big + 3}]


def test_tile_group_reduce_matches_numpy():
    """Grouped one-hot-matmul sums == numpy scatter-add oracle."""
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.pallas_kernels import tile_group_reduce
    rng = np.random.default_rng(0)
    n = 40_000
    gid = rng.integers(0, 37, n).astype(np.int32)
    v1 = rng.random(n).astype(np.float32)
    v2 = (rng.random(n) * 10).astype(np.float32)
    outs = tile_group_reduce(jnp.asarray(gid),
                             [jnp.asarray(v1), jnp.asarray(v2)])
    e1 = np.zeros(1024); np.add.at(e1, gid, v1)
    e2 = np.zeros(1024); np.add.at(e2, gid, v2)
    assert np.allclose(np.asarray(outs[0]), e1, rtol=1e-4)
    assert np.allclose(np.asarray(outs[1]), e2, rtol=1e-4)


def test_tile_group_reduce_ragged_tail():
    import numpy as np
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.pallas_kernels import tile_group_reduce
    rng = np.random.default_rng(1)
    n = 8 * 1024 + 333   # forces tail padding
    gid = rng.integers(0, 5, n).astype(np.int32)
    v = rng.random(n).astype(np.float32)
    (out,) = tile_group_reduce(jnp.asarray(gid), [jnp.asarray(v)])
    e = np.zeros(1024); np.add.at(e, gid, v)
    assert np.allclose(np.asarray(out), e, rtol=1e-4)


def test_fused_minmax_nan_ordering():
    """Spark orders NaN greatest: min skips NaN (unless all-NaN), max
    returns NaN when any NaN survives the filter — on BOTH the pallas
    and the XLA lanes, and they must agree."""
    import math

    data = {"v": [5.0, float("nan"), -3.0, None, float("nan"), 12.5],
            "w": [1.0] * 6}

    def make(conf):
        session = TpuSession(conf)
        df = session.create_dataframe({k: list(v) for k, v in data.items()})
        return df.filter(col("w") > 0.0).agg(
            Alias(Min(col("v")), "mn"), Alias(Max(col("v")), "mx"))

    for conf in (SrtConf({"srt.sql.pallas.enabled": True}),
                 SrtConf({"srt.sql.pallas.enabled": False})):
        rows, _ = _run(make(conf).plan, conf)
        (r,) = rows
        assert r["mn"] == -3.0, r
        assert math.isnan(r["mx"]), r

    # all-NaN group: min and max are both NaN
    data_nan = {"v": [float("nan"), float("nan")], "w": [1.0, 1.0]}

    def make_nan(conf):
        session = TpuSession(conf)
        df = session.create_dataframe(
            {k: list(v) for k, v in data_nan.items()})
        return df.filter(col("w") > 0.0).agg(
            Alias(Min(col("v")), "mn"), Alias(Max(col("v")), "mx"))

    for conf in (SrtConf({"srt.sql.pallas.enabled": True}),
                 SrtConf({"srt.sql.pallas.enabled": False})):
        rows, _ = _run(make_nan(conf).plan, conf)
        (r,) = rows
        assert math.isnan(r["mn"]) and math.isnan(r["mx"]), r


# --- the mask lane: a predicate the kernel refuses rides into it as
# its live mask, evaluated by XLA in the aggregate's own program ---

def _mask_data(n=700):
    rng = np.random.default_rng(11)
    data = {"v": rng.uniform(-50, 100, n).tolist(),
            "w": rng.uniform(0, 1, n).tolist(),
            "d": rng.integers(0, 9, n).tolist()}
    for i in range(0, n, 7):
        data["v"][i] = None
    for i in range(3, n, 13):
        data["w"][i] = None  # a null predicate value drops the row
    for i in range(5, n, 17):
        data["d"][i] = None
    return data


def _ansi_divide():
    e = col("w") / 2.0
    e.ansi = True  # what expr/ansi.enable_ansi sets: guards raise eagerly
    return e > 0.2


def _partition_context():
    from spark_rapids_tpu.expr.misc import SparkPartitionID
    return (SparkPartitionID() >= 0) & (col("w") / 2.0 > 0.2)


_PREDS = {
    # Divide, numeric IN: refused by the kernel on every platform
    "divide": lambda: (col("w") / 2.0 > 0.2) & (col("d") < 7),
    "numeric_in": lambda: InSet(col("d"), [1, 3, 5, 8]),
    "none_pass": lambda: col("w") / 2.0 > 5.0,
    "kernel_safe": lambda: (col("w") > 0.4) & (col("v") < 80.0),
    "ansi": _ansi_divide,
    "partition_context": _partition_context,
}

_MASK_AGGS = [(Sum(col("v") * col("w")), "rev"), (Average(col("v")), "av"),
              (Min(col("v")), "mn"), (Max(col("v")), "mx"),
              (Count(col("v")), "cv"), (CountStar(), "cnt")]


def _agg_tree(data, pred, mode, nbatches=3):
    """scan -> Filter -> global aggregate, built by hand so that the
    mode is the test's: COMPLETE, or PARTIAL under FINAL. Returns the
    root, the aggregate that sits on the filter, and the filter."""
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.exec import (BatchScanExec, FilterExec,
                                       HashAggregateExec)
    from spark_rapids_tpu.exec.aggregate import COMPLETE, FINAL, PARTIAL
    n = len(data["v"])
    per = -(-n // nbatches) if n else 1
    batches = [batch_from_pydict({k: v[i:i + per] for k, v in data.items()})
               for i in range(0, n, per)]
    schema = batch_from_pydict(_mask_data(8)).schema()
    filt = FilterExec(BatchScanExec(batches, schema), pred)
    if mode == "complete":
        agg = HashAggregateExec(filt, [], _MASK_AGGS, mode=COMPLETE)
        return agg, agg, filt
    agg = HashAggregateExec(filt, [], _MASK_AGGS, mode=PARTIAL)
    return HashAggregateExec(agg, [], _MASK_AGGS, mode=FINAL,
                             input_schema=filt.output_schema), agg, filt


def _pallas_against_xla(kind, mode, rows=700):
    """Run one predicate through the pallas lane and through FilterExec
    + the stock XLA aggregate; the answers must agree. Returns the
    pallas side's (aggregate, filter, context)."""
    data = _mask_data(rows)
    root, agg, filt = _agg_tree(data, _PREDS[kind](), mode)
    ref_root, _, ref_filt = _agg_tree(data, _PREDS[kind](), mode)
    rows_on, ctx = _collect(root, SrtConf({"srt.sql.pallas.enabled": True}))
    rows_off, ctx_off = _collect(
        ref_root, SrtConf({"srt.sql.pallas.enabled": False}))
    assert _metric(ctx_off, "pallasBatches") == 0
    assert ref_filt.exec_id in ctx_off.metrics
    (a,), (b,) = rows_on, rows_off
    assert a["cnt"] == b["cnt"] and a["cv"] == b["cv"]
    for k in ("rev", "av", "mn", "mx"):
        assert a[k] == (None if b[k] is None
                        else pytest.approx(b[k], rel=1e-12)), k
    return agg, filt, ctx, a


@pytest.mark.parametrize("mode", ["complete", "partial_final"])
@pytest.mark.parametrize("kind", ["divide", "numeric_in", "none_pass",
                                  "empty_input"])
def test_mask_lane_matches_xla_path(kind, mode):
    """One pallas program a batch with the predicate as its live mask:
    the stock path's answer, and the FilterExec never runs."""
    if kind == "empty_input":
        agg, filt, ctx, row = _pallas_against_xla("divide", mode, rows=0)
        nbatches = 0
    else:
        agg, filt, ctx, row = _pallas_against_xla(kind, mode)
        nbatches = 3
    if kind in ("none_pass", "empty_input"):
        assert row["cnt"] == 0 and row["rev"] is None
    else:
        assert row["cnt"] > 0
    assert "pallas-global, filter=mask" in agg.node_description()
    assert _metric(ctx, "pallasBatches") == nbatches
    assert _metric(ctx, "pallasMaskFilterBatches") == nbatches
    # the lane streams from the filter's child: FilterExec.execute is
    # never entered, so no jit_FilterExec._filter launch
    assert filt.exec_id not in ctx.metrics


def test_kernel_safe_predicate_stays_in_the_kernel():
    agg, filt, ctx, row = _pallas_against_xla("kernel_safe", "complete")
    assert "pallas-global, filter=kernel" in agg.node_description()
    assert row["cnt"] > 0
    assert _metric(ctx, "pallasBatches") == 3
    assert _metric(ctx, "pallasMaskFilterBatches") == 0
    assert filt.exec_id not in ctx.metrics


@pytest.mark.parametrize("kind", ["ansi", "partition_context"])
def test_unabsorbable_predicate_keeps_its_filter_exec(kind):
    """ANSI guards must raise outside jit, and the lane's program does
    not thread the partition context: both keep their FilterExec, and
    the kernel aggregates its (compacted) output."""
    agg, filt, ctx, row = _pallas_against_xla(kind, "complete")
    assert "pallas-global, filter=none" in agg.node_description()
    assert row["cnt"] > 0
    assert _metric(ctx, "pallasBatches") == 3
    assert _metric(ctx, "pallasMaskFilterBatches") == 0
    assert ctx.metrics[filt.exec_id]["numOutputBatches"].value == 3
