"""Pallas TPU kernels: fused single-HBM-pass reductions.

The XLA operator pipeline materializes intermediates between filter and
aggregate: ``FilterExec`` compacts passing rows into a fresh batch
(argsort + gather = several HBM round-trips) before ``HashAggregateExec``
reduces them. For the hottest reduction shape — scan -> filter -> global
aggregate, the TPC-H q6 spine — that traffic is
the whole cost: the aggregate output is a handful of scalars.

``tile_reduce`` fuses predicate evaluation, projection, and partial
aggregation into ONE pallas kernel: each row tile is DMA'd HBM->VMEM
once, the predicate and aggregate inputs evaluate on the VPU in VMEM,
and only per-tile partial scalars are written back. Cross-tile reduction
happens outside the kernel (a few hundred elements) in float64, which
both avoids a grid-accumulator dependence and improves numerics over a
single running float32 accumulator.

This is the TPU analogue of the fused cuDF reduction kernels behind the
reference's aggregate update pass (SURVEY §2.9; GpuAggregateExec.scala
AggHelper update); kernel structure follows the row-tile grid pattern of
/opt/skills/guides/pallas_guide.md. The exec-side wiring lives in
exec/aggregate.py (_PallasAggPlan).
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_ROWS = 8 * 1024

SUM = "sum"
MIN = "min"
MAX = "max"


def on_tpu() -> bool:
    """The one platform question both lanes ask. Everything that differs
    off the chip — Pallas interpret mode, float64 lanes — follows from
    it, so a kernel can never be interpreted on a TPU backend."""
    return jax.default_backend() == "tpu"


def reduce_identity(kind: str, dtype) -> float:
    """Identity element a masked-out lane must carry."""
    if kind == SUM:
        return 0.0
    if jnp.issubdtype(dtype, jnp.floating):
        return float(jnp.inf if kind == MIN else -jnp.inf)
    info = jnp.iinfo(dtype)
    return info.max if kind == MIN else info.min


LANES = 128


def _row_block(i):
    # block i of a row-tiled (rows/128, 128) array. The zero is typed:
    # under x64 a Python 0 traces as an i64 index, which Mosaic cannot
    # return from an index map.
    return i, jnp.int32(0)


def _byte_row_block(i):
    # block i of a (W, rows/128, 128) byte-plane array: every plane, row
    # tile i
    return jnp.int32(0), i, jnp.int32(0)


def _tile_kernel(row_fn: Callable, kinds: Sequence[str], out_dtype):
    n_out = len(kinds)

    def kernel(*refs):
        in_refs, out_ref = refs[:-1], refs[-1]
        blocks = [r[...] for r in in_refs]
        vals = row_fn(blocks)
        assert len(vals) == n_out, (len(vals), n_out)
        # slot j of the partial row is selected by lane index (Mosaic has
        # no scatter); (8, 128) is the smallest legal f32 output tile, so
        # every sublane carries the row and sublane 0 is read outside.
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
        row = jnp.zeros((8, LANES), out_dtype)
        for j, (v, kind) in enumerate(zip(vals, kinds)):
            if kind == SUM:
                r = jnp.sum(v.astype(out_dtype))
            # widen BEFORE reducing: Mosaic has no sub-32-bit reductions
            elif kind == MIN:
                r = jnp.min(v.astype(out_dtype))
            else:
                r = jnp.max(v.astype(out_dtype))
            row = jnp.where(lane == jnp.int32(j), r, row)
        out_ref[...] = row

    return kernel


def tile_reduce(inputs: Sequence[jax.Array], row_fn: Callable,
                kinds: Sequence[str], out_dtype=None,
                tile_rows: int = TILE_ROWS) -> List[jax.Array]:
    """Fused masked reduction over row tiles.

    ``inputs``: same-length arrays — 1-D per-row data (column data /
    validity / live masks) or 2-D ``(rows, W)`` padded string bytes.
    Rows are laid out dense for the VPU: a tile of ``tile_rows`` rows
    reaches ``row_fn`` as a ``(tile_rows/128, 128)`` block (Mosaic
    refuses 1-D sub-32-bit blocks, and a 1-D vector would fill one
    sublane in eight), a 2-D input as ``W`` such byte planes,
    ``(W, tile_rows/128, 128)``. ``row_fn(blocks) -> [vals...]`` maps
    one tile's blocks to ``len(kinds)`` pre-masked value arrays of the
    row-block shape — excluded rows must already carry the kind's
    identity (0 for sum, +/-inf for min/max); the tail padding this
    function appends is all-zeros, so mask inputs pad to False and
    masked values pad to the identity via row_fn.

    Returns one scalar per kind: per-tile partials from the kernel,
    reduced across tiles here (sums in float64 when x64 is live).
    """
    interpret = not on_tpu()
    if out_dtype is None:
        out_dtype = jnp.float32 if on_tpu() else jnp.float64
    # 8-bit masks pack (32, 128) to a vreg: whole tiles only
    assert tile_rows % (32 * LANES) == 0, tile_rows
    n = inputs[0].shape[0]
    tiles = max(1, -(-n // tile_rows))
    padded = tiles * tile_rows
    tile_sub = tile_rows // LANES
    ins = []
    specs = []
    for a in inputs:
        if a.ndim == 2:
            # padded string chars: byte j of every row becomes one dense
            # row plane, so the kernel compares whole planes to a literal
            # byte with no lane->sublane relayout
            w = a.shape[1]
            if padded != n:
                a = jnp.pad(a, ((0, padded - n), (0, 0)))
            a = a.T.reshape(w, padded // LANES, LANES)
            specs.append(pl.BlockSpec((w, tile_sub, LANES),
                                      _byte_row_block))
        else:
            if padded != n:
                a = jnp.pad(a, (0, padded - n))
            a = a.reshape(padded // LANES, LANES)
            specs.append(pl.BlockSpec((tile_sub, LANES), _row_block))
        ins.append(a)
    assert len(kinds) <= LANES, "one (1,128) partial row per tile"

    out = pl.pallas_call(
        _tile_kernel(row_fn, kinds, out_dtype),
        grid=(tiles,),
        in_specs=specs,
        out_specs=pl.BlockSpec((8, LANES), _row_block),
        out_shape=jax.ShapeDtypeStruct((tiles * 8, LANES), out_dtype),
        interpret=interpret,
    )(*ins)
    out = out[::8]

    acc_t = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    results = []
    for j, kind in enumerate(kinds):
        col = out[:, j]
        if kind == SUM:
            results.append(jnp.sum(col.astype(acc_t)))
        elif kind == MIN:
            results.append(jnp.min(col))
        else:
            results.append(jnp.max(col))
    return results


# ---------------------------------------------------------------------------
# grouped aggregation: one-hot matmul segmented reduction (family #2)
# ---------------------------------------------------------------------------

GROUP_BUCKETS = 1024
#: smaller row tile than tile_reduce: the (tile, B) one-hot must fit
#: VMEM — 2048x1024 f32 = 8 MiB, within the ~16 MB/core budget
#: (pallas_guide.md); 8192 rows would need 32 MiB and fail Mosaic
GROUP_TILE_ROWS = 2048


def tile_group_reduce(gid: jax.Array, values: Sequence[jax.Array],
                      num_buckets: int = GROUP_BUCKETS,
                      tile_rows: int = GROUP_TILE_ROWS
                      ) -> List[jax.Array]:
    """Fused grouped SUM: one HBM pass, segmented reduction as a
    ONE-HOT MATMUL so the per-tile reduction runs on the MXU instead of
    a scatter (TPU scatters serialize; a (tile, B) one-hot against a
    (tile, V) value block is exactly the systolic array's shape). The
    XLA scatter-based path (ops/kernels.py group fns) stays the
    fallback for large key domains.

    ``gid``: int32[n] bucket ids in [0, num_buckets); masked-out rows
    (past the batch's rows, or refused by a filter the fused chain
    handed over as the aggregate's mask) must carry values == 0 (sum
    identity) — their gid may be anything in range; rows keep their
    original order, whichever prelude found the groups. ``values``: 1-D float arrays. Returns one
    float64-accumulated array of shape [num_buckets] per value column;
    the caller maps buckets back to group keys.

    Kernel structure: one GRID-LESS pallas call per row tile (the MXU
    one-hot matmul), driven by an outer ``lax.scan`` that carries the
    accumulator at the wide dtype. A sequential grid accumulating into
    a revisited output block does compile for the v5e (asked of the
    chip's compiler, PR 21), but Mosaic has no f64, so it would
    accumulate every tile in float32; the scan carry accumulates at
    float64, bounding round-off per TILE rather than per batch — the
    reason this design stays. The kernel body avoids
    jnp operator sugar with Python-int operands: under x64 those
    route through jitted jnp wrappers that type the scalar operand
    int64, and Mosaic's in-kernel i64<->i32 convert recurses forever
    (jax 0.9).
    """
    interpret = not on_tpu()
    nv = len(values)
    assert nv <= 128, "one accumulator lane column per value column"
    assert num_buckets % 8 == 0, "sublane-aligned bucket count"
    # cast OUTSIDE the kernel: Mosaic cannot lower the emulated
    # f64->f32 (or i64->i32) convert inside a TPU kernel body — it
    # recurses in _convert_element_type_lowering_rule; XLA handles the
    # emulated conversion fine in the surrounding program.
    # Off the chip (interpret mode, the CPU differential lane) lanes stay
    # float64 so exact Spark semantics are testable — same contract as
    # tile_reduce.
    lane_t = jnp.float32
    if interpret and jax.config.jax_enable_x64:
        lane_t = jnp.float64
    gid = gid.astype(jnp.int32)
    values = [v.astype(lane_t) for v in values]
    n = gid.shape[0]
    tiles = max(1, -(-n // tile_rows))
    padded = tiles * tile_rows
    if padded != n:
        # pad rows to a full tile: gid 0 with zero values (sum identity)
        gid = jnp.pad(gid, (0, padded - n))
        values = [jnp.pad(v, (0, padded - n)) for v in values]

    def kernel(gid_ref, *refs):
        val_refs, out_ref = refs[:-1], refs[-1]
        g = gid_ref[...]
        # (tile_rows, B) one-hot on the fly; MXU contracts over rows
        oh = (g[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, num_buckets), 1)
              ).astype(lane_t)
        vmat = jnp.stack(
            [v[...].astype(lane_t) for v in val_refs], axis=1)
        if nv < 128:
            vmat = jax.lax.pad(vmat, lane_t(0),
                               ((0, 0, 0), (0, 128 - nv, 0)))
        # HIGHEST: the MXU's default f32 matmul multiplies in bf16 (8
        # mantissa bits), which broke the lane's float32 promise on the
        # chip — a 3e-4 relative error on small groups (PR 21)
        out_ref[...] = jax.lax.dot_general(
            oh, vmat, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=lane_t)   # (B, 128)

    tile_call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_buckets, 128), lane_t),
        interpret=interpret,
    )
    acc_t = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    gid_t = gid.reshape(tiles, tile_rows)
    vals_t = [v.reshape(tiles, tile_rows) for v in values]

    def step(acc, xs):
        g, vs = xs
        return acc + tile_call(g, *vs).astype(acc_t), None

    acc0 = jnp.zeros((num_buckets, 128), acc_t)
    out, _ = jax.lax.scan(step, acc0, (gid_t, vals_t))
    return [out[:, j] for j in range(nv)]
