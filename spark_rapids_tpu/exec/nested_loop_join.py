"""Cartesian product + broadcast nested-loop join.

Rebuild of GpuCartesianProductExec.scala and
GpuBroadcastNestedLoopJoinExecBase.scala (SURVEY §2.4): the non-equi
join path. The reference compiles the residual condition to a cuDF AST
and evaluates it over the cross pairs; here the condition is an
ordinary Expression evaluated over a "paired batch" — a virtual batch
where every probe row is replicated across the build rows — so XLA
fuses condition evaluation with the pairing itself.

Pairing is tiled: each (probe batch x build) product evaluates in
build-row-major tiles of at most ``tile_rows`` output slots, keeping
peak HBM bounded the way the reference's nested-loop join streams
partitions.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnVector, ColumnarBatch, StringColumn,
                               choose_capacity, live_mask)
from ..expr.core import Expression
from ..jit_registry import read_int, shared_fn_jit
from ..ops import kernels as K
from .base import ExecContext, Metric, Schema, TpuExec


def _replicate_pair(probe: ColumnarBatch, build: ColumnarBatch,
                    probe_rows: int, tile_start: int, tile_cap: int,
                    build_count) -> ColumnarBatch:
    """Virtual cross-pair batch for one tile.

    Output slot j holds (probe_row, build_row) where
      flat = tile_start + j
      probe_row = flat // build_capacity ; build_row = flat % build_cap
    Live slots: probe_row < probe_rows AND build_row < build_count.
    """
    bcap = build.capacity
    j = jnp.arange(tile_cap, dtype=jnp.int32)
    flat = tile_start + j
    p_idx = flat // bcap
    b_idx = flat % bcap
    valid = (p_idx < probe_rows) & (b_idx < build_count)
    p_cols = [c.gather(jnp.clip(p_idx, 0, probe.capacity - 1), valid)
              for c in probe.columns]
    b_cols = [c.gather(jnp.clip(b_idx, 0, bcap - 1), valid)
              for c in build.columns]
    # num_rows = tile_cap: live pair slots are NOT a prefix of the tile,
    # so the whole tile stays "live" and the caller's keep-mask (which
    # includes ``valid``) does all the filtering/compaction.
    return ColumnarBatch(p_cols + b_cols, probe.names + build.names,
                         jnp.int32(tile_cap)), valid


def _tile_run_builder(condition, tile_cap):
    def run(probe, build, probe_rows, tile_start, build_count):
        paired, valid = _replicate_pair(
            probe, build, probe_rows, tile_start, tile_cap, build_count)
        if condition is not None:
            cond = condition.eval(paired)
            keep = cond.data & cond.validity & valid
        else:
            keep = valid
        keep_col = ColumnVector(keep, jnp.ones_like(keep), dt.BOOL)
        return K.filter_batch(paired, keep_col)
    return run


class BroadcastNestedLoopJoinExec(TpuExec):
    """inner/cross nested-loop join with an arbitrary condition.

    left = streamed side, right = broadcast (build) side, like the
    reference's build-side-broadcast formulation.
    """

    def __init__(self, left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None,
                 join_type: str = "inner",
                 tile_rows: int = 1 << 16):
        super().__init__(left, right)
        if join_type not in ("inner", "cross"):
            raise NotImplementedError(
                f"nested-loop join type {join_type} (planner must fall "
                "back)")
        self.condition = condition
        self.join_type = join_type
        self.tile_rows = tile_rows
        self._jit_cache = {}

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema + \
            self.children[1].output_schema

    def _tile_fn(self, tile_cap: int, probe_cap: int):
        key = (tile_cap, probe_cap)
        if key not in self._jit_cache:
            from ..expr.misc import contains_eager
            if self.condition is not None and \
                    contains_eager([self.condition]):
                self._jit_cache[key] = _tile_run_builder(self.condition,
                                                         tile_cap)
            else:
                self._jit_cache[key] = shared_fn_jit(
                    _tile_run_builder, self.condition, tile_cap)
        return self._jit_cache[key]

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        build_batches = [b for b in self.children[1].execute(ctx)
                         if read_int(b.num_rows) > 0]
        if not build_batches:
            return
        total_b = sum(read_int(b.num_rows) for b in build_batches)
        with ctx.semaphore:
            build = (build_batches[0] if len(build_batches) == 1 else
                     K.concat_batches(build_batches,
                                      choose_capacity(total_b)))
        bcap = build.capacity
        for probe in self.children[0].execute(ctx):
            n_probe = read_int(probe.num_rows)
            if n_probe == 0:
                continue
            total_slots = probe.capacity * bcap
            tile_cap = min(choose_capacity(self.tile_rows), total_slots)
            fn = self._tile_fn(tile_cap, probe.capacity)
            for start in range(0, total_slots, tile_cap):
                # skip tiles whose every probe row is dead
                if start // bcap >= n_probe:
                    break
                with ctx.semaphore:
                    out = fn(probe, build, jnp.int32(n_probe),
                             jnp.int32(start), build.num_rows)
                if read_int(out.num_rows) > 0:
                    yield out

    def node_description(self) -> str:
        c = f", cond={self.condition!r}" if self.condition is not None \
            else ""
        return f"BroadcastNestedLoopJoin[{self.join_type}{c}]"


class CartesianProductExec(BroadcastNestedLoopJoinExec):
    """CROSS JOIN (GpuCartesianProductExec): a conditionless nested
    loop."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 tile_rows: int = 1 << 16):
        super().__init__(left, right, condition=None, join_type="cross",
                         tile_rows=tile_rows)

    def node_description(self) -> str:
        return "CartesianProduct"
