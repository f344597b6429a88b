"""Basic physical operators: scan, project, filter, limit, expand,
union, range, coalesce.

Reference counterparts (SURVEY §2.4): basicPhysicalOperators.scala
(GpuProjectExec:350, GpuFilterExec:783), limit.scala, GpuExpandExec,
GpuRangeExec, GpuCoalesceBatches.scala (AbstractGpuCoalesceIterator:250).

Projection/filter evaluate the whole expression list inside one jitted
trace per (capacity, schema) so XLA fuses the expression DAG — there is
no per-expression kernel-launch loop to optimize away.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnVector, ColumnarBatch, choose_capacity,
                               live_mask)
from ..expr.core import Expression, output_name
from ..jit_registry import shared_fn_jit, shared_method_jit
from ..ops import kernels as K
from .base import ExecContext, Metric, NvtxTimer, Schema, TpuExec


class BatchScanExec(TpuExec):
    """Leaf: yields pre-built batches (in-memory table scan).

    File-format scans (parquet/csv/json) subclass the same shape in
    io/scan.py.
    """

    def __init__(self, batches: Sequence[ColumnarBatch], schema: Schema):
        super().__init__()
        self._batches = list(batches)
        self._schema = list(schema)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        yield from self._batches

    def node_description(self) -> str:
        return f"BatchScan[{len(self._batches)} batches]"


class ProjectExec(TpuExec):
    """Tiered projection (GpuProjectExec / GpuTieredProject).

    Context expressions (expr/misc.py) make this operator
    position-aware: (row_offset, partition_id) pass as traced scalars —
    one compiled program for every batch — and eager-only trees
    (input_file_name, uuid, raise_error) evaluate un-jitted."""

    _streams_child = True

    def __init__(self, child: TpuExec, exprs: Sequence[Expression]):
        super().__init__(child)
        self.exprs = list(exprs)
        in_schema = child.output_schema
        self._schema = [(output_name(e, i), e.data_type(in_schema))
                        for i, e in enumerate(self.exprs)]
        from ..expr.misc import contains_eager
        self._eager = contains_eager(self.exprs)
        self._jit = shared_method_jit(self, "_project", ("exprs", "_schema"))
        self._jit_ctx = self._project_ctx if self._eager \
            else shared_method_jit(self, "_project_ctx",
                                   ("exprs", "_schema"))

    def _project(self, batch: ColumnarBatch) -> ColumnarBatch:
        cols = [e.eval(batch) for e in self.exprs]
        return ColumnarBatch(cols, [n for n, _ in self._schema],
                             batch.num_rows)

    def _project_ctx(self, batch: ColumnarBatch, row_offset,
                     partition_id) -> ColumnarBatch:
        from ..expr.misc import traced_context
        with traced_context(row_offset, partition_id):
            return self._project(batch)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        offset = 0
        for batch in self.children[0].execute(ctx):
            with ctx.semaphore:
                out = self._jit_ctx(batch, jnp.int64(offset),
                                    jnp.int32(ctx.partition_id))
            offset += int(batch.num_rows)
            yield out

    def node_description(self) -> str:
        return f"Project[{', '.join(n for n, _ in self._schema)}]"


class FilterExec(TpuExec):
    """WHERE: compacts passing rows to the batch prefix (GpuFilterExec)."""

    _streams_child = True

    def __init__(self, child: TpuExec, condition: Expression):
        super().__init__(child)
        self.condition = condition
        from ..expr.misc import contains_eager
        # eager conditions (ANSI guards, raise_error) must evaluate
        # outside jit so data-dependent raises reach the caller
        self._jit = self._filter if contains_eager([condition]) \
            else shared_method_jit(self, "_filter", ("condition",))

    def _filter(self, batch: ColumnarBatch) -> ColumnarBatch:
        cond = self.condition.eval(batch)
        return K.filter_batch(batch, cond)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        for batch in self.children[0].execute(ctx):
            # (not yielded under the semaphore: a producer thread that
            # stands in backpressure would keep its permit)
            with ctx.semaphore:
                out = self._jit(batch)
            yield out

    def node_description(self) -> str:
        return f"Filter[{self.condition!r}]"


class LocalLimitExec(TpuExec):
    """LIMIT n within the stream (GpuLocalLimitExec, limit.scala)."""

    def __init__(self, child: TpuExec, limit: int):
        super().__init__(child)
        self.limit = limit
        # limit passed as a traced scalar: one compile per capacity
        # bucket, not one per distinct remaining-count
        self._jit = shared_fn_jit(_local_limit_builder)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        remaining = self.limit
        for batch in self.children[0].execute(ctx):
            if remaining <= 0:
                return
            with ctx.semaphore:
                out = self._jit(batch, jnp.int64(remaining))
            remaining -= int(out.num_rows)
            yield out

    def node_description(self) -> str:
        return f"LocalLimit[{self.limit}]"


def _local_limit_builder():
    return K.local_limit


class UnionExec(TpuExec):
    """UNION ALL: concatenation of child streams (GpuUnionExec)."""

    def __init__(self, *children: TpuExec):
        super().__init__(*children)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        names = [n for n, _ in self.output_schema]
        for child in self.children:
            for batch in child.execute(ctx):
                # normalize column names across the union
                yield ColumnarBatch(batch.columns, names, batch.num_rows)


class ExpandExec(TpuExec):
    """Multiple projection lists per input row — GROUPING SETS / rollup /
    cube (GpuExpandExec)."""

    def __init__(self, child: TpuExec, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str]):
        super().__init__(child)
        self.projections = [list(p) for p in projections]
        in_schema = child.output_schema
        # Unify each output column's dtype across ALL projection lists
        # (grouping sets routinely mix e.g. col and NULL literal slots)
        # and cast divergent slots, so every emitted batch matches the
        # declared schema.
        from ..expr.cast import Cast
        from ..expr.conditional import _common_type
        unified = [
            _common_type([p[i].data_type(in_schema)
                          for p in self.projections])
            for i in range(len(names))]
        for p in self.projections:
            for i, t in enumerate(unified):
                if p[i].data_type(in_schema) != t:
                    p[i] = Cast(p[i], t)
        self._schema = list(zip(names, unified))
        from ..expr.misc import contains_eager
        self._jits = [
            _expand_project_builder(p, list(names)) if contains_eager(p)
            else shared_fn_jit(_expand_project_builder, p, list(names))
            for p in self.projections]

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        for batch in self.children[0].execute(ctx):
            for jit in self._jits:
                with ctx.semaphore:
                    yield jit(batch)

    def node_description(self) -> str:
        return f"Expand[{len(self.projections)} projections]"


def _expand_project_builder(exprs, names):
    def run(batch):
        cols = [e.eval(batch) for e in exprs]
        return ColumnarBatch(cols, list(names), batch.num_rows)
    return run


class RangeExec(TpuExec):
    """SELECT id FROM range(start, end, step) (GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 batch_rows: Optional[int] = None):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows

    @property
    def output_schema(self) -> Schema:
        return [("id", dt.INT64)]

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..conf import BATCH_SIZE_ROWS
        per = self.batch_rows or ctx.conf.get(BATCH_SIZE_ROWS)
        total = max(0, -(-(self.end - self.start) // self.step))
        done = 0
        while done < total:
            n = min(per, total - done)
            cap = choose_capacity(n)
            base = self.start + done * self.step
            data = base + jnp.arange(cap, dtype=jnp.int64) * self.step
            live = live_mask(cap, n)
            col = ColumnVector(jnp.where(live, data, 0), live, dt.INT64)
            yield ColumnarBatch([col], ["id"], n)
            done += n

    def node_description(self) -> str:
        return f"Range[{self.start}, {self.end}, step={self.step}]"


class CoalesceBatchesExec(TpuExec):
    """Combine small batches up to the target size (GpuCoalesceBatches,
    AbstractGpuCoalesceIterator:250). Registers pending batches as
    spillable while accumulating, like the reference's on-deck storage."""

    _streams_child = True

    def __init__(self, child: TpuExec, target_rows: Optional[int] = None):
        super().__init__(child)
        self.target_rows = target_rows

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import time as _time

        from ..conf import BATCH_SIZE_ROWS
        from ..memory.spill import SpillableBatch, SpillPriority
        target = self.target_rows or ctx.conf.get(BATCH_SIZE_ROWS)
        # time spent blocked pulling the child: under pipelining the
        # child is a prefetcher, so this is the residual stall the
        # background producer could not hide
        wait = ctx.metrics_for(self.exec_id).setdefault(
            "coalesceWaitTime",
            Metric("coalesceWaitTime", Metric.MODERATE, "ns"))
        pending: List[SpillableBatch] = []
        pending_rows = 0

        def flush() -> Optional[ColumnarBatch]:
            nonlocal pending, pending_rows
            if not pending:
                return None
            batches = [sb.get() for sb in pending]
            if len(batches) == 1:
                out = batches[0]
            else:
                cap = choose_capacity(pending_rows)
                with ctx.semaphore:
                    out = K.concat_batches(batches, cap)
            for sb in pending:
                sb.close()
            pending, pending_rows = [], 0
            return out

        it = iter(self.children[0].execute(ctx))
        while True:
            t0 = _time.perf_counter_ns()
            try:
                batch = next(it)
            except StopIteration:
                wait.add(_time.perf_counter_ns() - t0)
                break
            wait.add(_time.perf_counter_ns() - t0)
            n = int(batch.num_rows)
            if n == 0:
                continue
            if n >= target and not pending:
                # already at target with nothing buffered: skip the
                # spill-registration + get() round-trip entirely
                yield batch
                continue
            if pending_rows + n > target and pending:
                out = flush()
                if out is not None:
                    yield out
                if n >= target:
                    yield batch
                    continue
            pending.append(SpillableBatch(batch,
                                          SpillPriority.ACTIVE_ON_DECK))
            pending_rows += n
            if pending_rows >= target:
                out = flush()
                if out is not None:
                    yield out
        out = flush()
        if out is not None:
            yield out

    def node_description(self) -> str:
        return f"CoalesceBatches[target={self.target_rows or 'conf'}]"


def sample_keep_mask(row_offset, capacity: int, fraction: float,
                     seed: int):
    """Deterministic Bernoulli keep-mask: murmur3 of the stream-global
    row position under ``seed`` compared against fraction * 2^32. The
    SAME function drives the device exec and the CPU engine, so
    fallback sampling is bit-identical (GpuSampleExec role)."""
    from ..columnar import dtypes as dt_
    from ..expr import hashing as H
    pos = jnp.arange(capacity, dtype=jnp.int64) + jnp.int64(row_offset)
    col = ColumnVector(pos, jnp.ones(capacity, jnp.bool_), dt_.INT64)
    h = H.murmur3_column(col, jnp.uint32(seed))
    threshold = jnp.uint32(min(int(fraction * (1 << 32)), (1 << 32) - 1))
    if fraction >= 1.0:
        return jnp.ones(capacity, jnp.bool_)
    return h < threshold


class SampleExec(TpuExec):
    """WHERE-style Bernoulli sampling by position hash (GpuSampleExec,
    basicPhysicalOperators.scala)."""

    def __init__(self, child: TpuExec, fraction: float, seed: int):
        super().__init__(child)
        self.fraction = fraction
        self.seed = seed
        self._jit = shared_method_jit(self, "_sample", ("fraction", "seed"))

    def _sample(self, batch: ColumnarBatch, row_offset):
        keep = sample_keep_mask(row_offset, batch.capacity,
                                self.fraction, self.seed)
        cond = ColumnVector(keep, jnp.ones_like(keep), dt.BOOL)
        return K.filter_batch(batch, cond)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        offset = 0
        for batch in self.children[0].execute(ctx):
            with ctx.semaphore:
                out = self._jit(batch, jnp.int64(offset))
            offset += int(batch.num_rows)
            yield out

    def node_description(self) -> str:
        return f"Sample[{self.fraction}, seed={self.seed}]"
