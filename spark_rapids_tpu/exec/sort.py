"""Sort exec: in-core full sort + true out-of-core k-way chunk merge.

Rebuild of GpuSortExec.scala (:86, out-of-core iterator :242) and
SortUtils.scala. Each input batch is sorted on device into a run. A
partition whose total rows fit ``srt.sql.sort.oocRowBudget`` merges
with one concat + argsort (the XLA-friendly fast path). Bigger
partitions run the out-of-core iterator: runs are split into spilled
C-row chunks, and a host-driven loop repeatedly loads the chunk whose
first row is globally smallest (device-ordered head comparison), sorts
it against the bounded carry, and emits every row that can no longer
be preceded by an unloaded row (rows ordered <= the minimum pending
chunk head — the same bound logic as the reference's out-of-core merge
pending/sorted queues). Device residency stays O(budget): one chunk +
the carry, with runs parked in the spill tier.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnVector, ColumnarBatch,
                               choose_capacity, live_mask)
from ..expr.core import Expression
from ..jit_registry import shared_fn_jit, shared_method_jit
from ..ops import kernels as K
from .base import ExecContext, Metric, Schema, TpuExec


def _concat_sort_builder(order, cap):
    """MODULE-LEVEL builder for shared_fn_jit (fusion v2, sort-prefix
    fusion): concat + key extraction + sort as ONE program — the
    out-of-core merge step (carry + chunk) and the pending-head pick
    both otherwise pay an eager concat that round-trips HBM before the
    sort launch reads it back."""
    def run(*batches):
        b = batches[0] if len(batches) == 1 \
            else K.concat_batches(list(batches), cap)
        keys = [o.expr.eval(b) for o in order]
        return K.sort_batch(b, keys,
                            [o.ascending for o in order],
                            [o.nulls_first for o in order])
    return run


def _chunk_head_builder(length, cap):
    """MODULE-LEVEL builder for shared_fn_jit: slice one C-row chunk
    out of a sorted run AND capture its head-row token (8-cap batch
    with the __run tag column) in the same program."""
    def run(run_b, start):
        piece = K.slice_batch(run_b, start, length, cap)
        head = K.slice_batch(piece, 0, 1, 8)
        tag = ColumnVector(jnp.zeros(8, jnp.int32),
                           live_mask(8, head.num_rows), dt.INT32)
        head8 = ColumnarBatch(head.columns + [tag],
                              head.names + ["__run"], head.num_rows)
        return piece, head8
    return run


def _bound_prefix_builder(order):
    """MODULE-LEVEL builder for shared_fn_jit: bound-row slice + safe-
    prefix count in one program (the fused form of
    _safe_prefix_builder — takes the sorted pending-heads batch and
    slices its first row as the bound internally)."""
    from ..parallel.partition import range_partition_ids

    def run(mb, hs):
        bb = K.slice_batch(hs, 0, 1, 8)
        keys = [o.expr.eval(mb) for o in order]
        bkeys = [o.expr.eval(bb) for o in order]
        bkeys = [c.gather(jnp.zeros(1, jnp.int32),
                          live_mask(1, bb.num_rows))
                 if hasattr(c, "chars") else
                 type(c)(c.data[:1], c.validity[:1], c.dtype)
                 for c in bkeys]
        pid = range_partition_ids(
            keys, bkeys, [o.ascending for o in order],
            [o.nulls_first for o in order])
        return jnp.sum((pid == 0) & mb.live_mask()).astype(jnp.int32)
    return run


def _safe_prefix_builder(order):
    from ..parallel.partition import range_partition_ids

    def run(mb, bb):
        keys = [o.expr.eval(mb) for o in order]
        bkeys = [o.expr.eval(bb) for o in order]
        bkeys = [c.gather(jnp.zeros(1, jnp.int32),
                          live_mask(1, bb.num_rows))
                 if hasattr(c, "chars") else
                 type(c)(c.data[:1], c.validity[:1], c.dtype)
                 for c in bkeys]
        pid = range_partition_ids(
            keys, bkeys, [o.ascending for o in order],
            [o.nulls_first for o in order])
        return jnp.sum((pid == 0) & mb.live_mask()).astype(jnp.int32)
    return run


class SortOrder:
    """(expr, ascending, nulls_first) — Catalyst SortOrder."""

    def __init__(self, expr: Expression, ascending: bool = True,
                 nulls_first: Optional[bool] = None):
        self.expr = expr
        self.ascending = ascending
        # Spark default: NULLS FIRST for ASC, NULLS LAST for DESC
        self.nulls_first = ascending if nulls_first is None else nulls_first


class SortExec(TpuExec):
    def __init__(self, child: TpuExec, order: Sequence[SortOrder],
                 global_sort: bool = True):
        super().__init__(child)
        self.order = list(order)
        self.global_sort = global_sort
        from ..expr.misc import contains_eager
        # eager sort keys (ANSI guards) evaluate outside jit
        self._eager_keys = contains_eager([o.expr for o in self.order])
        self._jit_sort = self._sort_one if self._eager_keys \
            else shared_method_jit(self, "_sort_one", ("order",))
        self._fused_cache = {}

    # --- sort-prefix fusion (fusion v2) ---

    def _sort_fusion_on(self, ctx: ExecContext) -> bool:
        from ..conf import FUSION_ENABLED, FUSION_SORT
        return (not self._eager_keys
                and ctx.conf.get(FUSION_ENABLED)
                and ctx.conf.get(FUSION_SORT))

    def _fused_concat_sort(self, cap: int):
        """One-program concat+key-extraction+sort at ``cap`` slots."""
        key = ("concat_sort", cap)
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = shared_fn_jit(_concat_sort_builder, self.order, cap)
            from .fused import FUSION_STATS
            FUSION_STATS["sorts"] += 1
            self._fused_cache[key] = fn
        return fn

    def _fused_chunk_head(self, length: int, cap: int):
        key = ("chunk_head", length, cap)
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = shared_fn_jit(_chunk_head_builder, length, cap)
            self._fused_cache[key] = fn
        return fn

    def _fused_bound_prefix(self):
        key = "bound_prefix"
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = shared_fn_jit(_bound_prefix_builder, self.order)
            self._fused_cache[key] = fn
        return fn

    def _sort_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        key_cols = [o.expr.eval(batch) for o in self.order]
        return K.sort_batch(batch, key_cols,
                            [o.ascending for o in self.order],
                            [o.nulls_first for o in self.order])

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def required_child_distributions(self):
        from ..plan.distribution import (OrderedDistribution,
                                         UnspecifiedDistribution)
        if self.global_sort:
            return [OrderedDistribution(self.order)]
        return [UnspecifiedDistribution()]

    @property
    def output_partitioning(self):
        from ..plan.distribution import RangePartitioning
        if self.global_sort:
            child = self.children[0].output_partitioning
            return RangePartitioning(self.order, child.num_partitions)
        return self.children[0].output_partitioning

    def _sort_partition(self, ctx: ExecContext,
                        stream) -> Iterator[ColumnarBatch]:
        """Buffer one partition (spillable) and sort it: one concat +
        sort when it fits the in-core budget, the out-of-core chunk
        merge (GpuSortExec.scala:242) when it does not."""
        from ..conf import SORT_OOC_ROWS
        from ..memory.spill import SpillableBatch, SpillPriority
        runs: List[SpillableBatch] = []
        total = 0
        max_run = 0
        try:
            from ..memory.retry import with_retry_no_split
            for batch in stream:
                if int(batch.num_rows) == 0:
                    continue
                total += int(batch.num_rows)
                max_run = max(max_run, batch.capacity)
                runs.append(with_retry_no_split(
                    lambda b=batch: SpillableBatch(
                        b, SpillPriority.ACTIVE_ON_DECK)))
            if not runs:
                return
            budget = max(ctx.conf.get(SORT_OOC_ROWS), max_run)
            if total <= budget:
                cap = choose_capacity(total)
                batches = [sb.get() for sb in runs]
                with ctx.semaphore:
                    if self._sort_fusion_on(ctx) and 1 < len(batches) <= 16:
                        # concat + key extraction + sort as one program
                        # (each batch count is its own signature, so
                        # bound the fan-in; bigger sets concat eagerly,
                        # and a lone batch reuses the shared sort program)
                        yield self._fused_concat_sort(cap)(*batches)
                    else:
                        merged = (batches[0] if len(batches) == 1
                                  else K.concat_batches(batches, cap))
                        yield self._jit_sort(merged)
                return
            yield from self._ooc_merge(ctx, runs, budget)
        finally:
            for sb in runs:
                sb.close()

    # --- out-of-core merge ------------------------------------------------

    def _head_row(self, batch: ColumnarBatch, run_idx: int
                  ) -> ColumnarBatch:
        """First row of a (sorted) device batch + a __run tag column,
        in an 8-capacity batch — the merge loop's pending-head token."""
        head = K.slice_batch(batch, 0, 1, 8)
        tag = ColumnVector(jnp.full(8, run_idx, jnp.int32),
                           live_mask(8, head.num_rows), dt.INT32)
        return ColumnarBatch(head.columns + [tag],
                             head.names + ["__run"], head.num_rows)

    def _dead_head(self, like: ColumnarBatch) -> ColumnarBatch:
        z = K.slice_batch(like, 0, 0, 8)
        return ColumnarBatch(z.columns, z.names, jnp.int32(0))

    def _ooc_merge(self, ctx: ExecContext, runs, budget: int
                   ) -> Iterator[ColumnarBatch]:
        """Bounded-memory k-way merge of spilled sorted runs.

        Each run is sorted and split into spilled C-row chunks with
        C = budget // (2*k); every chunk's HEAD ROW is captured at
        split time (tiny, stays device-resident). When k is too large
        for the bound (C would hit its floor), runs cascade: groups of
        runs merge into longer spilled runs first, so the final pass
        always satisfies carry <= k*C <= budget/2."""
        from ..memory.retry import with_retry_no_split
        from ..memory.spill import SpillableBatch, SpillPriority
        k = len(runs)
        floor_c = 256
        max_k = max(2, budget // (2 * floor_c))
        # 1. sort + split every input run
        split: List[Tuple[List, List]] = []   # (chunk sbs, chunk heads)
        for sb in runs:
            with ctx.semaphore:
                run = with_retry_no_split(
                    lambda sb=sb: self._jit_sort(sb.get()))
            sb.close()
            split.append(self._split_run(ctx, run, budget,
                                         max(min(k, max_k), 2)))
        # 2. cascade while too many runs for the residency bound:
        # groups merge into one longer run whose emitted pieces are
        # re-split to C-row chunks (pieces can be up to budget-sized)
        while len(split) > max_k:
            group, split = split[:max_k], split[max_k:]
            combined_chunks: List = []
            combined_heads: List = []
            for piece in self._merge_chunklists(ctx, group, budget):
                parts, hlist = self._split_run(ctx, piece, budget,
                                               max_k)
                combined_chunks.extend(parts)
                combined_heads.extend(hlist)
            split.append((combined_chunks, combined_heads))
        yield from self._merge_chunklists(ctx, split, budget)

    def _split_run(self, ctx: ExecContext, run: ColumnarBatch,
                   budget: int, k: int):
        """Split a sorted device run into spilled C-row chunks plus
        their (device-resident, 8-cap) head rows."""
        from ..memory.retry import with_retry_no_split
        from ..memory.spill import SpillableBatch, SpillPriority
        C = max(256, budget // (2 * k))
        chunk_cap = choose_capacity(C)
        n = int(run.num_rows)
        parts, part_heads = [], []
        fused = self._fused_chunk_head(C, chunk_cap) \
            if self._sort_fusion_on(ctx) else None
        for start in range(0, max(n, 1), C):
            with ctx.semaphore:
                if fused is not None:
                    # chunk slice + head-row token in one program
                    piece, head = fused(run, jnp.int32(start))
                    part_heads.append(head)
                else:
                    piece = K.slice_batch(run, start, jnp.int32(C),
                                          chunk_cap)
                    part_heads.append(self._head_row(piece, 0))
            parts.append(with_retry_no_split(
                lambda p=piece: SpillableBatch(
                    p, SpillPriority.ACTIVE_ON_DECK)))
        return parts, part_heads

    def _merge_chunklists(self, ctx: ExecContext, split, budget: int
                          ) -> Iterator[ColumnarBatch]:
        """Merge k chunklists ((spilled chunks, head rows) per run).

        Loop invariant: every emitted row orders <= the first row of
        every unloaded chunk, so the concatenation of emitted batches
        is globally sorted. The carry holds rows that may still be
        preceded by unloaded rows; per run at most one chunk of rows
        can be parked there, so carry <= k*C <= budget/2 and device
        residency stays O(budget)."""
        from ..memory.retry import with_retry_no_split
        m = ctx.metrics_for(self.exec_id)
        peak_m = m.setdefault("sortOocPeakRows",
                              Metric("sortOocPeakRows", Metric.DEBUG))
        k = len(split)
        chunks = [parts for parts, _ in split]
        all_heads = []
        for ri, (_, hlist) in enumerate(split):
            # re-tag heads with this merge's run index
            all_heads.append([
                ColumnarBatch(h.columns[:-1] + [ColumnVector(
                    jnp.full(8, ri, jnp.int32), h.columns[-1].validity,
                    dt.INT32)], h.names, h.num_rows) for h in hlist])
        next_chunk = [0] * k
        heads: List[Optional[ColumnarBatch]] = [
            hl[0] if hl else None for hl in all_heads]
        schema_like = next(h for h in heads if h is not None)
        carry: Optional[ColumnarBatch] = None

        def pending() -> List[ColumnarBatch]:
            return [h if h is not None else self._dead_head(schema_like)
                    for h in heads]

        fuse = self._sort_fusion_on(ctx)

        def pick_heads() -> ColumnarBatch:
            """Sorted pending-heads batch — fused concat+sort when on
            (one program), eager concat + sort launch otherwise."""
            with ctx.semaphore:
                if fuse:
                    return self._fused_concat_sort(8 * k)(*pending())
                hb = K.concat_batches(pending(), 8 * k)
                return self._jit_sort_heads(hb)

        try:
            while True:
                live_heads = [h for h in heads if h is not None]
                if not live_heads:
                    if carry is not None and int(carry.num_rows) > 0:
                        yield carry
                    return
                # pick the run whose pending chunk head is smallest
                # (device comparison — exact sort semantics)
                hs = pick_heads()
                r = int(hs.column("__run").data[0])
                i = next_chunk[r]
                chunk = with_retry_no_split(chunks[r][i].get)
                chunks[r][i].close()
                next_chunk[r] += 1
                heads[r] = all_heads[r][next_chunk[r]] \
                    if next_chunk[r] < len(chunks[r]) else None
                # merge the chunk into the carry and emit the safe
                # prefix (rows ordered <= every pending head); pure
                # compute over already-held batches, so RetryOOM just
                # re-runs it after a synchronous spill

                def merge_step(carry=carry, chunk=chunk):
                    with ctx.semaphore:
                        if carry is None:
                            return self._jit_sort(chunk)
                        cap = choose_capacity(
                            int(carry.num_rows) + int(chunk.num_rows))
                        if fuse:
                            return self._fused_concat_sort(cap)(
                                carry, chunk)
                        return self._jit_sort(K.concat_batches(
                            [carry, chunk], cap))
                merged = with_retry_no_split(merge_step)
                peak_m.set(max(peak_m.value, int(merged.num_rows)))
                live_heads = [h for h in heads if h is not None]
                if not live_heads:
                    carry = merged
                    continue
                hs = pick_heads()
                with ctx.semaphore:
                    if fuse:
                        # bound-row slice + prefix count, one program
                        n_le = self._fused_bound_prefix()(merged, hs)
                    else:
                        bound = K.slice_batch(hs, 0, 1, 8)
                        n_le = self._jit_safe_prefix(merged, bound)
                n = int(n_le)
                if n > 0:
                    with ctx.semaphore:
                        out = K.slice_batch(merged, 0, jnp.int32(n),
                                            choose_capacity(n))
                        rest = int(merged.num_rows) - n
                        carry = K.slice_batch(
                            merged, jnp.int32(n),
                            jnp.int32(max(rest, 0)),
                            choose_capacity(max(rest, 1)))
                    yield out
                else:
                    carry = merged
        finally:
            for parts in chunks:
                for p in parts:
                    p.close()

    def _jit_sort_heads(self, hb: ColumnarBatch) -> ColumnarBatch:
        # same registry key as _jit_sort (identical program; the trace
        # cache keys on the head batch's own structure)
        return self._jit_sort(hb)

    def _jit_safe_prefix(self, merged: ColumnarBatch,
                         bound: ColumnarBatch):
        """Count of merged rows ordering <= the bound row (they form a
        prefix of the sorted batch; range_partition_ids shares the sort
        comparator exactly, so 'strictly after bound' == unsafe)."""
        if not hasattr(self, "_safe_prefix_fn"):
            from ..expr.misc import contains_eager
            if contains_eager([o.expr for o in self.order]):
                self._safe_prefix_fn = _safe_prefix_builder(self.order)
            else:
                self._safe_prefix_fn = shared_fn_jit(
                    _safe_prefix_builder, self.order)
        return self._safe_prefix_fn(merged, bound)

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        if not self.global_sort:
            for batch in self.children[0].execute(ctx):
                if int(batch.num_rows) == 0:
                    continue
                with ctx.semaphore:
                    yield self._jit_sort(batch)
            return
        # Global sort over a range-partitioned child: sorting each
        # partition and emitting in partition order is globally sorted
        # (partition i's rows all precede partition i+1's).
        for part in self.children[0].execute_partitioned(ctx):
            yield from self._sort_partition(ctx, part)

    def node_description(self) -> str:
        keys = ", ".join(
            f"{o.expr!r} {'ASC' if o.ascending else 'DESC'}"
            for o in self.order)
        return f"Sort[{keys}]{'' if self.global_sort else ' (local)'}"


class TopNExec(TpuExec):
    """ORDER BY + LIMIT n fused (GpuTopN, limit.scala): keeps only the
    top n rows per batch, then a final n-way selection — bounds memory
    without the full-sort concat."""

    def __init__(self, child: TpuExec, order: Sequence[SortOrder], limit: int):
        super().__init__(child)
        self.order = list(order)
        self.limit = limit
        from ..expr.misc import contains_eager
        self._jit_topn = self._topn \
            if contains_eager([o.expr for o in self.order]) \
            else shared_method_jit(self, "_topn", ("order", "limit"))
        shrink_cap = choose_capacity(self.limit)
        self._jit_shrink = lambda b: K.repack_to(b, shrink_cap)

    def _topn(self, batch: ColumnarBatch) -> ColumnarBatch:
        key_cols = [o.expr.eval(batch) for o in self.order]
        sorted_b = K.sort_batch(batch, key_cols,
                                [o.ascending for o in self.order],
                                [o.nulls_first for o in self.order])
        return K.local_limit(sorted_b, self.limit)

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        partials: List[ColumnarBatch] = []
        total = 0
        # Each partial holds <= limit live rows; compact it down to the
        # limit's capacity bucket so retained memory is O(batches*limit),
        # not O(batches*input_capacity).
        part_cap = choose_capacity(self.limit)
        for batch in self.children[0].execute(ctx):
            if int(batch.num_rows) == 0:
                continue
            with ctx.semaphore:
                part = self._jit_topn(batch)
                if part.capacity > part_cap:
                    part = self._jit_shrink(part)
            partials.append(part)
            total += int(part.num_rows)
        if not partials:
            return
        cap = choose_capacity(max(total, self.limit))
        with ctx.semaphore:
            merged = (partials[0] if len(partials) == 1
                      else K.concat_batches(partials, cap))
            yield self._jit_topn(merged)

    def node_description(self) -> str:
        return f"TopN[{self.limit}]"
