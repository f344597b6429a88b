"""Ahead-of-time compiles for the chip that is described, not attached.

The TPU compiler is installed in the CPU sandbox: ``jit(f).lower(shapes
with a described device's sharding).compile()`` raises what the real
chip's compiler would raise (Mosaic lowering gaps, VMEM overflow,
misaligned blocks) — none of which Pallas interpret mode can see. These
are the main path's kernels at the main path's shapes. Nothing runs, so
nothing here is a result or a timing.

The topology is described inside a module-scoped fixture only: one
process at a time may load libtpu, so no import, skipif condition or
parametrize argument may touch it (see the on-chip-measurement guide,
section 2). All such tests live in this one file for the same reason.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from spark_rapids_tpu.ops import pallas_kernels as PK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT entry for a described chip is written to the persistent
    # cache but cannot be read back without one: keep these compiles
    # out of it
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_chip(monkeypatch):
    """The engine asks ``pallas_kernels.on_tpu()`` once to pick compiled
    vs interpreted kernels and float32 vs float64 lanes; this process
    still sees the CPU backend, so the test answers for the chip."""
    monkeypatch.setattr(PK, "on_tpu", lambda: True)


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


def test_tile_reduce_f32_masked(one_chip, as_on_chip):
    """q6's shape: float32 data, int32 date, uint8 validity/live masks,
    8 tiles of TILE_ROWS."""
    n = 8 * PK.TILE_ROWS

    def row_fn(blocks):
        price, disc, date, valid, live = blocks
        m = (live != 0) & (valid != 0) & (date >= 9131) & (date < 9496) \
            & (disc >= 0.05)
        return [jnp.where(m, price * disc, jnp.float32(0)),
                m.astype(jnp.float32),
                jnp.where(m, price, jnp.float32(jnp.inf))]

    def f(price, disc, date, valid, live):
        return PK.tile_reduce([price, disc, date, valid, live], row_fn,
                              [PK.SUM, PK.SUM, PK.MIN])

    text = _compile(f,
                    _shape(one_chip, (n,), jnp.float32),
                    _shape(one_chip, (n,), jnp.float32),
                    _shape(one_chip, (n,), jnp.int32),
                    _shape(one_chip, (n,), jnp.uint8),
                    _shape(one_chip, (n,), jnp.uint8))
    assert "tpu_custom_call" in text


def test_tile_reduce_padded_string(one_chip, as_on_chip):
    """The string-predicate family: a (rows, W) padded char block plus
    lengths and validity, compared against a literal in VMEM — through
    the planner's own PallasAggPlan so the lane layout under test is the
    one the engine feeds."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import (ColumnVector,
                                                  ColumnarBatch,
                                                  StringColumn)
    from spark_rapids_tpu.exec import pallas_agg
    from spark_rapids_tpu.expr import aggregates as Agg
    from spark_rapids_tpu.expr import col, lit

    cap, w = 4 * PK.TILE_ROWS, 16
    schema = [("price", dt.FLOAT32), ("flag", dt.STRING)]
    pred = (col("flag") == lit("R")) & (col("price") > lit(
        float(np.float32(10.0)), dt.FLOAT32))
    assert pallas_agg.pred_safe(pred, schema)
    plan = pallas_agg.PallasAggPlan(
        [(Agg.Sum(col("price")), "s"), (Agg.CountStar(), "n")],
        schema, pred)
    run = plan.batch_fn()

    def f(price, pvalid, offsets, chars, svalid, num_rows):
        batch = ColumnarBatch(
            [ColumnVector(price, pvalid, dt.FLOAT32),
             StringColumn(offsets, chars, svalid, pad_bucket=w)],
            ["price", "flag"], num_rows)
        return run(batch)

    text = _compile(f,
                    _shape(one_chip, (cap,), jnp.float32),
                    _shape(one_chip, (cap,), jnp.bool_),
                    _shape(one_chip, (cap + 1,), jnp.int32),
                    _shape(one_chip, (cap * 2,), jnp.uint8),
                    _shape(one_chip, (cap,), jnp.bool_),
                    _shape(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text


def test_tile_group_reduce_2048x1024(one_chip, as_on_chip):
    n = 1 << 20

    def f(gid, v0, v1):
        return PK.tile_group_reduce(gid, [v0, v1],
                                    num_buckets=PK.GROUP_BUCKETS,
                                    tile_rows=PK.GROUP_TILE_ROWS)

    text = _compile(f,
                    _shape(one_chip, (n,), jnp.int32),
                    _shape(one_chip, (n,), jnp.float32),
                    _shape(one_chip, (n,), jnp.float32))
    assert "tpu_custom_call" in text


def test_graft_entry_program(one_chip):
    """The q6-shaped filter -> partial agg -> finalize XLA program."""
    import __graft_entry__ as ge
    fn, (batch,) = ge.entry()
    shapes = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, np.shape(x), jnp.asarray(x).dtype),
        batch)
    _compile(fn, shapes)


def test_group_aggregate_int64_key(one_chip):
    """Hash-claim group-by, INT64 key + FLOAT64 sum, at a capacity that
    compiles in seconds (2^20 rows took ~2 minutes for the chip)."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import ColumnVector, ColumnarBatch
    from spark_rapids_tpu.expr import aggregates as Agg
    from spark_rapids_tpu.ops import kernels as K

    cap = 1 << 12

    def f(key, val, valid, num_rows):
        kc = ColumnVector(key, valid, dt.INT64)
        vc = ColumnVector(val, valid, dt.FLOAT64)
        batch = ColumnarBatch([kc, vc], ["k", "v"], num_rows)
        key_batch, states = K.group_aggregate(batch, [kc], [vc],
                                              [Agg.Sum(None)])
        return key_batch, states

    _compile(f,
             _shape(one_chip, (cap,), jnp.int64),
             _shape(one_chip, (cap,), jnp.float64),
             _shape(one_chip, (cap,), jnp.bool_),
             _shape(one_chip, (), jnp.int32))


def test_group_rounds_and_lane_with_string_keys(one_chip, as_on_chip):
    """Q1's shape at a capacity that compiles in seconds: two string keys read through
    ``_string_key_bytes``'s reshape-or-gather switch, the comparison rounds' ``while_loop`` under a fused
    filter's mask, then the lane's branch alone (its kernel at this row count, the representatives' keys
    from a 1024-row gather padded to the batch's capacity). The fallbacks behind them (hash claim, sort
    path) are the programs ``test_group_aggregate_int64_key`` asks for."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import (ColumnVector, ColumnarBatch,
                                                  StringColumn)
    from spark_rapids_tpu.ops import kernels as K

    cap = 1 << 12

    def f(off_a, chars_a, ok_a, off_b, chars_b, ok_b, val, num_rows, live):
        keys = [StringColumn(off_a, chars_a, ok_a, pad_bucket=8),
                StringColumn(off_b, chars_b, ok_b, pad_bucket=16)]
        batch = ColumnarBatch(keys + [ColumnVector(val, ok_a, dt.FLOAT64)],
                              ["a", "b", "v"], num_rows)
        ok, (_, _, gid, num_groups, key_rows) = K._prelude_direct(
            batch, keys, live)
        sums = PK.tile_group_reduce(jnp.minimum(gid, PK.GROUP_BUCKETS - 1),
                                    [jnp.where(live, val, 0.0)])
        return ok, sums, K._key_batch_few(keys, key_rows, cap, num_groups,
                                          PK.GROUP_BUCKETS)

    string = [_shape(one_chip, (cap + 1,), jnp.int32),
              _shape(one_chip, (cap,), jnp.uint8),
              _shape(one_chip, (cap,), jnp.bool_)]
    text = _compile(f, *string, *string,
                    _shape(one_chip, (cap,), jnp.float64),
                    _shape(one_chip, (), jnp.int32),
                    _shape(one_chip, (cap,), jnp.bool_))
    assert "tpu_custom_call" in text and "while" in text


def test_q6_mask_filter_in_the_aggregates_program(one_chip, as_on_chip):
    """Q6 as tpch_sf1 holds it: FLOAT64 money and quantity, so on the
    chip the kernel refuses the predicate and it becomes the mask XLA
    evaluates in front of tile_reduce — one program, and nothing in it
    compacts: no scatter, and no gather but tile_reduce's own pick of
    one partial row a tile (``out[::8]``, tiles x 128 lanes)."""
    import datetime

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import ColumnVector, ColumnarBatch
    from spark_rapids_tpu.exec import pallas_agg
    from spark_rapids_tpu.expr import aggregates as Agg
    from spark_rapids_tpu.expr import col, lit

    cap = 1 << 20
    schema = [("l_shipdate", dt.DATE), ("l_discount", dt.FLOAT64),
              ("l_quantity", dt.FLOAT64), ("l_extendedprice", dt.FLOAT64)]
    pred = ((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
            & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
            & (col("l_discount") >= 0.05) & (col("l_discount") <= 0.07)
            & (col("l_quantity") < 24.0))
    assert not pallas_agg.pred_safe(pred, schema)
    plan = pallas_agg.PallasAggPlan(
        [(Agg.Sum(col("l_extendedprice") * col("l_discount")), "revenue")],
        schema, pred=None, mask_pred=pred)
    assert plan.ref_names == ["l_discount", "l_extendedprice"]
    run = plan.batch_fn()

    def f(date, disc, qty, price, valid, num_rows):
        batch = ColumnarBatch(
            [ColumnVector(date, valid, dt.DATE),
             ColumnVector(disc, valid, dt.FLOAT64),
             ColumnVector(qty, valid, dt.FLOAT64),
             ColumnVector(price, valid, dt.FLOAT64)],
            [n for n, _ in schema], num_rows)
        return run(batch)

    text = _compile(f,
                    _shape(one_chip, (cap,), jnp.int32),
                    _shape(one_chip, (cap,), jnp.float64),
                    _shape(one_chip, (cap,), jnp.float64),
                    _shape(one_chip, (cap,), jnp.float64),
                    _shape(one_chip, (cap,), jnp.bool_),
                    _shape(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in text
    assert " scatter(" not in text
    tiles = cap // PK.TILE_ROWS
    for line in text.splitlines():
        if " gather(" in line:
            dims = re.search(r"= \w+\[([\d,]+)\]", line).group(1)
            assert np.prod([int(d) for d in dims.split(",")]) \
                <= tiles * PK.LANES, line
