"""Hash aggregate exec.

Rebuild of GpuHashAggregateExec (GpuAggregateExec.scala:1711; AggHelper
:175; merge iterator :711). Same staged structure as the reference:

  PARTIAL  : per input batch, raw rows -> packed per-group state batch
  (exchange: hash-partition packed partials by the group keys —
   inserted by the planner, GpuShuffleExchangeExecBase role)
  FINAL    : per partition, concat partials, merge states, finalize
  COMPLETE : both phases in one node (single-stage plans)

The kernel is sort-based (ops/kernels.py group_aggregate/group_merge)
rather than cuDF's hash groupby — sorting composes with XLA's static
shapes. Partial results are registered as spillable between the phases,
mirroring the reference's spillable agg buffers; a merge pass too big
for one batch falls back to split-and-retry via the memory framework.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnVector, ColumnarBatch, choose_capacity,
                               live_mask)
from ..expr.aggregates import AggregateFunction
from ..expr.core import Expression, make_result, output_name
from ..jit_registry import named_jit, shared_fn_jit, shared_method_jit
from ..ops import kernels as K
from .base import ExecContext, Metric, NvtxTimer, Schema, TpuExec


def _key_bucket_split_builder(key_names, num_parts):
    def run(batch, p):
        return K.bucket_compact(
            batch, [batch.column(n) for n in key_names], num_parts, p)
    return run

PARTIAL = "partial"
FINAL = "final"
COMPLETE = "complete"

#: counters of the grouped Pallas lane's programs, from the ``flags``
#: each launch returns (``K.group_aggregate_pallas``): operator Metric
#: -> key of the query record's ``phases`` (plan/session.py). A batch is
#: counted in ``pallasBatches`` when the one-hot kernel took it, and in
#: exactly one of the other two by how its groups were found.
LANE_COUNTERS = {"pallasBatches": "pallas_batches",
                 "groupsResolvedDirect": "groups_direct_batches",
                 "groupsHashClaimed": "groups_hash_claim_batches"}


def count_lane_flags(metrics: dict, flags: list) -> None:
    """Settles the ``flags`` of a stream's grouped-lane launches into
    ``LANE_COUNTERS`` (one device read each, after the stream: no
    per-batch sync)."""
    if not flags:
        return
    import numpy as np
    lane, direct = np.sum([np.asarray(f) for f in flags], axis=0)
    for name, n in (("pallasBatches", lane),
                    ("groupsResolvedDirect", direct),
                    ("groupsHashClaimed", len(flags) - direct)):
        metrics.setdefault(name, Metric(name, Metric.DEBUG)).add(int(n))


def _state_col_name(agg_index: int, state_name: str) -> str:
    return f"__agg{agg_index}__{state_name}"


def make_agg_result(data, validity, out_t: dt.DType):
    """Finalized aggregate -> output column. Decimal aggregates with
    128-bit states finalize to (hi, lo) limb tuples, string min/max to
    a StringColumn; everything else is a plain lane array."""
    from ..columnar.nested import ListColumn
    from ..columnar.vector import StringColumn
    if isinstance(data, (StringColumn, ListColumn)):
        return data.with_validity(data.validity & validity)
    if isinstance(data, tuple):
        from ..columnar import decimal128 as d128
        hi, lo = data
        validity = validity & d128.d128_fits_precision(hi, lo,
                                                       out_t.precision)
        return d128.build_decimal_column(hi, lo, validity, out_t)
    return make_result(data, validity, out_t)


class HashAggregateExec(TpuExec):
    """groupBy(keys).agg(fns) over the child stream.

    ``agg_exprs``: [(AggregateFunction, output_name)]. Aggregate inputs
    are the function's child expressions evaluated against the original
    (pre-partial) input schema. For ``mode=FINAL`` the child produces
    packed partial batches, so the original schema must be supplied via
    ``input_schema``.
    """

    def __init__(self, child: TpuExec, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[Tuple[AggregateFunction, str]],
                 mode: str = COMPLETE, input_schema: Optional[Schema] = None):
        super().__init__(child)
        self.mode = mode
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        in_schema = input_schema if input_schema is not None \
            else child.output_schema
        self.input_schema = in_schema
        self._key_names = [output_name(e, i)
                           for i, e in enumerate(self.group_exprs)]
        key_schema = [(n, e.data_type(in_schema))
                      for n, e in zip(self._key_names, self.group_exprs)]
        self._result_schema = (
            key_schema +
            [(name, fn.data_type(in_schema))
             for fn, name in self.agg_exprs])
        self._state_schemas = [fn.state_schema(in_schema)
                               for fn, _ in self.agg_exprs]
        self._packed_schema = list(key_schema)
        for i, sschema in enumerate(self._state_schemas):
            for sname, stype in sschema:
                self._packed_schema.append((_state_col_name(i, sname), stype))
        agg_fields = ("group_exprs", "agg_exprs", "_key_names",
                      "_state_schemas", "_result_schema", "_packed_schema")
        from ..expr.misc import contains_eager
        self._eager = contains_eager(
            list(self.group_exprs) + [fn for fn, _ in self.agg_exprs])
        if self._eager:
            # ANSI guards / eager nodes inside keys or aggregate inputs
            # need un-jitted evaluation to raise
            self._jit_update = self._update
            self._jit_merge = self._merge_finalize
        else:
            self._jit_update = shared_method_jit(self, "_update",
                                                 agg_fields)
            self._jit_merge = shared_method_jit(self, "_merge_finalize",
                                                agg_fields)
        self._split_cache = {}
        from . import pallas_agg
        self._pallas_gate = pallas_agg.pallas_eligible(self)
        self._pallas_grouped_gate = pallas_agg.grouped_eligible(self)
        self._pallas_cache = {}

    @property
    def output_schema(self) -> Schema:
        return self._packed_schema if self.mode == PARTIAL \
            else self._result_schema

    def required_child_distributions(self):
        from ..plan.distribution import (AllTuples, ClusteredDistribution,
                                         UnspecifiedDistribution)
        if self.mode != FINAL:
            return [UnspecifiedDistribution()]
        if not self.group_exprs:
            return [AllTuples()]
        from ..expr.core import col
        return [ClusteredDistribution([col(n) for n in self._key_names])]

    @property
    def output_partitioning(self):
        # grouping keys survive both phases under their output names, so
        # the child's partitioning (hash on those names) still holds.
        if self.mode == FINAL and self.group_exprs:
            return self.children[0].output_partitioning
        from ..plan.distribution import SinglePartition, UnknownPartitioning
        if self.mode == FINAL:
            return SinglePartition()
        return UnknownPartitioning(1)

    # --- phase 1: partial aggregation of one raw batch ---
    def _eval_update_inputs(self, batch: ColumnarBatch):
        key_cols = [e.eval(batch) for e in self.group_exprs]
        agg_in = [fn.children[0].eval(batch) if fn.children else None
                  for fn, _ in self.agg_exprs]
        return key_cols, agg_in

    def _update(self, batch: ColumnarBatch, row_offset,
                live=None) -> ColumnarBatch:
        """``live``: the rows that count, where a fused chain hands its
        filter over as a mask (exec/fused.py); the batch's prefix
        otherwise."""
        key_cols, agg_in = self._eval_update_inputs(batch)
        key_batch, states = K.group_aggregate(
            batch, key_cols, agg_in, [fn for fn, _ in self.agg_exprs],
            row_offset=row_offset, live=live)
        return self._pack(key_batch, states, key_batch.num_rows,
                          batch.capacity)

    def _pack(self, key_batch: ColumnarBatch, states: List[dict],
              num_groups, cap: int) -> ColumnarBatch:
        """Flatten state dicts into columns so partials flow as batches
        (and therefore through spill + shuffle untouched)."""
        cols: List[ColumnVector] = []
        names: List[str] = []
        lm = live_mask(cap, num_groups)
        for kc, name in zip(key_batch.columns, self._key_names):
            cols.append(kc)
            names.append(name)
        from ..columnar.nested import ListColumn
        from ..columnar.vector import StringColumn
        for i, ((fn, _), sschema) in enumerate(
                zip(self.agg_exprs, self._state_schemas)):
            for sname, stype in sschema:
                arr = states[i][sname]
                if isinstance(arr, (StringColumn, ListColumn)):
                    # Column-valued state (string min/max): the column
                    # itself is the buffer; validity carries "seen"
                    cols.append(arr.with_validity(arr.validity & lm))
                elif arr.dtype == jnp.bool_:
                    cols.append(ColumnVector(arr & lm, lm, stype))
                else:
                    data = jnp.where(lm, arr, jnp.zeros((), arr.dtype))
                    cols.append(ColumnVector(data, lm, stype))
                names.append(_state_col_name(i, sname))
        return ColumnarBatch(cols, names, num_groups)

    def _unpack(self, batch: ColumnarBatch):
        from ..columnar.nested import ListColumn
        from ..columnar.vector import StringColumn
        key_cols = [batch.column(n) for n in self._key_names]
        states = []
        for i, sschema in enumerate(self._state_schemas):
            d = {}
            for sname, _ in sschema:
                c = batch.column(_state_col_name(i, sname))
                d[sname] = c if isinstance(c, (StringColumn, ListColumn)) \
                    else c.data
            states.append(d)
        return key_cols, states

    # --- FINAL-merge fusion (fusion v2, planner-armed) ---

    #: list of fused-away upstream ProjectExecs (top-down order) when
    #: plan/overrides.py armed merge fusion on this FINAL aggregate;
    #: None keeps the stock eager-concat + _jit_merge path
    _merge_fusion = None

    def arm_merge_fusion(self, projs) -> None:
        """plan/overrides.py hook: compile this FINAL aggregate's merge
        pass together with the concat of its partition's partials (and
        any projection prefix the planner absorbed) into one jitted
        program (exec/fused.py _fused_merge_builder)."""
        self._merge_fusion = list(projs)
        self._fused_merge_cache = {}
        from .fused import FUSION_STATS
        FUSION_STATS["chains"] += 1
        FUSION_STATS["stages"] += len(projs) + 1
        FUSION_STATS["final_aggs"] += 1

    def _fused_merge_fn(self, cap: int, with_prefix: bool = True):
        from .fused import fused_final_merge_fn
        key = (cap, with_prefix)
        fn = self._fused_merge_cache.get(key)
        if fn is None:
            projs = list(reversed(self._merge_fusion)) \
                if with_prefix else []
            fn = fused_final_merge_fn(self, projs, cap)
            self._fused_merge_cache[key] = fn
        return fn

    def _apply_merge_prefix(self, ctx: ExecContext,
                            batch: ColumnarBatch) -> ColumnarBatch:
        """Fused-away projection prefix applied eagerly — used where
        the merge path must bucket by group key BEFORE merging (the
        re-partition fallback's bucket split reads post-projection key
        columns)."""
        for p in reversed(self._merge_fusion):
            with ctx.semaphore:
                batch = p._jit(batch)
        return batch

    def _run_merge(self, ctx: ExecContext, batches, cap: int,
                   with_prefix: bool = True) -> ColumnarBatch:
        """Merge one held batch list: the fused concat+prefix+merge
        program when armed (argument count bounded by
        srt.exec.fusion.finalAgg.maxMergeInputs — past it an eager
        pre-concat feeds the single-input program), the stock eager
        concat + _jit_merge otherwise. Bit-identical either way: the
        fused program is the literal composition of the same traced
        functions."""
        if self._merge_fusion is None:
            merged_in = (batches[0] if len(batches) == 1
                         else K.concat_batches(batches, cap))
            return self._jit_merge(merged_in)
        from ..conf import FUSION_MERGE_MAX_INPUTS
        if len(batches) > ctx.conf.get(FUSION_MERGE_MAX_INPUTS):
            batches = [K.concat_batches(batches, cap)]
        return self._fused_merge_fn(cap, with_prefix)(*batches)

    # --- phase 2: merge partials + finalize ---
    def _merge_finalize(self, batch: ColumnarBatch) -> ColumnarBatch:
        key_cols, states = self._unpack(batch)
        key_batch, merged, num_groups = K.group_merge(
            batch, key_cols, states, [fn for fn, _ in self.agg_exprs])
        if not self.group_exprs:
            # Global aggregate: always exactly one output row, even on
            # empty input (Spark semantics: count()=0, sum()=null).
            num_groups = jnp.maximum(num_groups, 1)
        cap = batch.capacity
        lm = live_mask(cap, num_groups)
        out_cols: List[ColumnVector] = [
            kc for kc in key_batch.columns]
        for i, (fn, name) in enumerate(self.agg_exprs):
            data, ok = fn.finalize(merged[i])
            out_cols.append(make_agg_result(
                data, ok & lm,
                self._result_schema[len(self._key_names) + i][1]))
        names = [n for n, _ in self._result_schema]
        return ColumnarBatch(out_cols, names, num_groups)

    # --- grouped pallas lane (one-hot MXU matmul partials) ---
    def _update_pallas(self, batch: ColumnarBatch, row_offset, live=None):
        """_update with the grouped pallas lane compiled in: a batch of
        <= 1024 groups, found by comparison rounds or hash claim, takes
        the one-hot MXU kernel, everything else the stock scatter/sort
        path — one traced program, lax.cond dispatch. Returns (packed,
        flags): ``K.group_aggregate_pallas``'s ``int32[2]``, lane taken
        and groups found by the rounds."""
        key_cols, agg_in = self._eval_update_inputs(batch)
        key_batch, states, flags = K.group_aggregate_pallas(
            batch, key_cols, agg_in, [fn for fn, _ in self.agg_exprs],
            row_offset=row_offset,
            max_capacity=getattr(self, "_pallas_max_cap", 1 << 24),
            live=live)
        return self._pack(key_batch, states, key_batch.num_rows,
                          batch.capacity), flags

    def _grouped_pallas_fn(self, ctx: ExecContext):
        """The jitted grouped-lane update, or None (gate miss, either
        pallas conf off, or off the chip without the test force).
        srt.sql.pallas.enabled is the master switch owning the
        f32-tile deviation contract; groupedAgg.enabled scopes this
        lane alone. Every condition is static: a lane chosen here
        runs, and a Mosaic refusal raises out of the query."""
        from ..conf import PALLAS_ENABLED, PALLAS_GROUPED_ENABLED
        from . import pallas_agg
        if self._eager or not self._pallas_grouped_gate \
                or not ctx.conf.get(PALLAS_ENABLED) \
                or not ctx.conf.get(PALLAS_GROUPED_ENABLED) \
                or not pallas_agg.grouped_lane_on():
            return None
        fn = self._pallas_cache.get("grouped_update")
        if fn is None:
            from ..conf import PALLAS_GROUP_MAX_CAPACITY
            self._pallas_max_cap = int(
                ctx.conf.get(PALLAS_GROUP_MAX_CAPACITY))
            agg_fields = ("group_exprs", "agg_exprs", "_key_names",
                          "_state_schemas", "_result_schema",
                          "_packed_schema", "_pallas_max_cap")
            fn = self._pallas_cache["grouped_update"] = shared_method_jit(
                self, "_update_pallas", agg_fields)
        return fn

    def _partial_stream(self, ctx: ExecContext, agg_time: Metric
                        ) -> Iterator[ColumnarBatch]:
        row_offset = 0
        grouped_fn = self._grouped_pallas_fn(ctx)
        used_flags: List = []
        for batch in self.children[0].execute(ctx):
            if int(batch.num_rows) == 0:
                continue
            with ctx.semaphore, NvtxTimer(agg_time, "agg.update"):
                if grouped_fn is not None:
                    partial, used = grouped_fn(batch,
                                               jnp.int64(row_offset))
                    # no per-batch sync: flags settle with the stream
                    used_flags.append(used)
                else:
                    partial = self._jit_update(batch,
                                               jnp.int64(row_offset))
            row_offset += int(batch.num_rows)
            yield partial
        count_lane_flags(ctx.metrics_for(self.exec_id), used_flags)

    def _merge_partition(self, ctx: ExecContext, partials,
                         agg_time: Metric) -> Iterator[ColumnarBatch]:
        """Concat + merge one partition's packed partials; yields one
        batch normally, several when the merge set exceeds
        srt.sql.agg.mergePartitionRows and gets re-partitioned by key
        hash (disjoint key buckets merge independently — the
        reference's re-partition fallback, GpuAggregateExec.scala:711)."""
        from ..conf import AGG_MERGE_PARTITION_ROWS
        from ..memory.retry import with_retry_no_split
        from ..memory.spill import SpillableBatch, SpillPriority
        held: List = []
        total = 0
        try:
            for p in partials:
                if int(p.num_rows) == 0:
                    continue
                total += int(p.num_rows)
                held.append(with_retry_no_split(
                    lambda b=p: SpillableBatch(
                        b, SpillPriority.ACTIVE_ON_DECK)))
            if not held:
                if not self.group_exprs:
                    yield self._empty_global_result()
                return
            threshold = ctx.conf.get(AGG_MERGE_PARTITION_ROWS)
            if total > threshold and self.group_exprs:
                yield from self._repartition_merge(ctx, held, total,
                                                   threshold, agg_time)
                return
            cap = choose_capacity(max(total, 1))

            def merge_all():
                batches = [sb.get() for sb in held]
                with ctx.semaphore, NvtxTimer(agg_time, "agg.merge"):
                    return self._run_merge(ctx, batches, cap)
            # RetryOOM mid-merge: spill + re-run (the merge is a pure
            # function of the held spillables — RmmRapidsRetryIterator
            # withRetryNoSplit contract)
            yield with_retry_no_split(merge_all)
        finally:
            for sb in held:
                sb.close()

    def _split_fn(self, num_parts: int):
        """jit'd group-key hash bucket filter over packed partials
        (ops/kernels.py bucket_compact — same primitive the
        sub-partition join uses)."""
        if num_parts not in self._split_cache:
            self._split_cache[num_parts] = shared_fn_jit(
                _key_bucket_split_builder, list(self._key_names), num_parts)
        return self._split_cache[num_parts]

    def _repack(self, ctx: ExecContext, batch: ColumnarBatch
                ) -> ColumnarBatch:
        """Shrink a compacted bucket to its tight capacity (compact
        keeps the source capacity; without this the fallback would
        inflate the merge set ~P times)."""
        n = int(batch.num_rows)
        cap = choose_capacity(max(n, 8))
        if cap >= batch.capacity:
            return batch
        with ctx.semaphore:
            return K.repack_to(batch, cap)

    def _repartition_merge(self, ctx: ExecContext, held, total: int,
                           threshold: int, agg_time: Metric
                           ) -> Iterator[ColumnarBatch]:
        m = ctx.metrics_for(self.exec_id)
        parts_m = m.setdefault("aggMergePartitions",
                               Metric("aggMergePartitions", Metric.DEBUG))
        P = max(2, -(-total // max(threshold, 1)))
        parts_m.add(P)
        split = self._split_fn(P)
        from ..memory.spill import SpillableBatch, SpillPriority
        # bucket every partial once; buckets spill while waiting
        buckets: List[List[SpillableBatch]] = [[] for _ in range(P)]
        bucket_rows = [0] * P
        try:
            for sb in held:
                batch = sb.get()
                if self._merge_fusion:
                    # the bucket split reads post-projection key
                    # columns, so an absorbed projection prefix must
                    # land before bucketing (merge_bucket then runs the
                    # prefix-free fused program)
                    batch = self._apply_merge_prefix(ctx, batch)
                for p in range(P):
                    with ctx.semaphore:
                        sub = split(batch, jnp.int32(p))
                    n = int(sub.num_rows)
                    if n:
                        sub = self._repack(ctx, sub)
                        bucket_rows[p] += n
                        from ..memory.retry import with_retry_no_split
                        buckets[p].append(with_retry_no_split(
                            lambda b=sub: SpillableBatch(
                                b, SpillPriority.ACTIVE_ON_DECK)))
                sb.close()
            for p in range(P):
                if not buckets[p]:
                    continue
                cap = choose_capacity(bucket_rows[p])

                def merge_bucket(p=p, cap=cap):
                    batches = [b.get() for b in buckets[p]]
                    with ctx.semaphore, NvtxTimer(agg_time,
                                                  "agg.merge"):
                        return self._run_merge(ctx, batches, cap,
                                               with_prefix=False)
                from ..memory.retry import with_retry_no_split
                yield with_retry_no_split(merge_bucket)
                for b in buckets[p]:
                    b.close()
                buckets[p] = []
        finally:
            for bs in buckets:
                for b in bs:
                    b.close()

    def _child_partitions(self, ctx: ExecContext):
        """Child partition streams; with AQE on and an exchange child,
        small reduce partitions group together before the merge
        (CoalesceShufflePartitions over the FINAL aggregate)."""
        from .exchange import ShuffleExchangeExec
        child = self.children[0]
        if not self.preserve_partitioning and \
                isinstance(child, ShuffleExchangeExec):
            # decision delegated to plan/adaptive.py (byte-target aware,
            # cached on the exchange, shared with the eager stage
            # executor); cluster-safe: computed from gathered GLOBAL
            # statistics, so every worker derives the same groups and
            # streams its own contiguous block of them
            from ..plan.adaptive import stage_groups
            groups = stage_groups(ctx, child)
            if groups is not None:
                return child.execute_partition_groups(ctx, groups)
        return child.execute_partitioned(ctx)

    def execute_partitioned(self, ctx: ExecContext):
        """A FINAL grouped aggregate ADVERTISES its child exchange's
        hash partitioning (output_partitioning above), so partition-wise
        consumers (a co-partitioned join) must see one output partition
        per child partition — the default whole-stream yield made the
        advertisement a lie: a join zipping this against a real
        N-partition exchange raised 'partition counts differ' (or worse
        under same-count coalescing). Found by the SF1 run (q11/q74:
        the build side outgrew adaptive broadcast at 3M rows and the
        zip path engaged)."""
        if self.mode != FINAL or not self.group_exprs:
            yield self.execute(ctx)
            return
        m = ctx.metrics_for(self.exec_id)
        agg_time = m.setdefault("aggTime", Metric("aggTime",
                                                  Metric.MODERATE, "ns"))
        for part in self._final_merge_partitions(ctx, agg_time):
            # partitioned consumers bypass execute(): account here
            yield self._measure_stream(ctx, part)

    def _final_merge_partitions(self, ctx: ExecContext, agg_time):
        """One merged output stream per child partition — the single
        source of truth for FINAL grouped merging (both consumption
        paths flatten this)."""
        for part in self._child_partitions(ctx):
            yield self._merge_partition(ctx, part, agg_time)

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        m = ctx.metrics_for(self.exec_id)
        agg_time = m.setdefault("aggTime", Metric("aggTime", Metric.MODERATE,
                                                  "ns"))
        if self.mode in (PARTIAL, COMPLETE):
            fused = self._pallas_stream_or_none(ctx, agg_time)
            if fused is not None:
                yield from fused
                return
        if self.mode == PARTIAL:
            yield from self._partial_stream(ctx, agg_time)
            return
        if self.mode == FINAL:
            if self.group_exprs:
                # same loop the partitioned consumers use — but through
                # the UNMEASURED core: execute() wraps this method with
                # the output accounting already
                for part in self._final_merge_partitions(ctx, agg_time):
                    yield from part
                return
            saw_any = False
            for part in self._child_partitions(ctx):
                for out in self._merge_partition(ctx, part, agg_time):
                    saw_any = True
                    yield out
            if not saw_any and \
                    (ctx.cluster is None or ctx.cluster.owns_first()):
                # cluster mode: exactly ONE worker emits the global
                # empty-input row (count()=0, sum()=null)
                yield self._empty_global_result()
            return
        # COMPLETE: partial + merge fused in one stage
        yield from self._merge_partition(
            ctx, self._partial_stream(ctx, agg_time), agg_time)

    # --- fused pallas path (global aggregates over simple numerics) ---
    def _pallas_filter(self):
        """(source, kind, predicate): what the global pallas lane
        streams from, and where a FilterExec child's predicate goes —
        ``kernel`` when the kernel may evaluate it (``pred_safe``),
        ``mask`` when XLA evaluates it in front of the kernel in the
        same program, ``none`` when there is no filter to absorb (no
        FilterExec child, or one whose predicate must stay outside jit
        or needs the partition context: that FilterExec runs as ever).
        Decided from the predicate's nodes and dtypes and the platform."""
        from ..expr.misc import fusion_blocked
        from . import pallas_agg
        from .basic import CoalesceBatchesExec, FilterExec
        node = self.children[0]
        while isinstance(node, CoalesceBatchesExec):
            node = node.children[0]
        if isinstance(node, FilterExec):
            if pallas_agg.pred_safe(node.condition, self.input_schema):
                return node.children[0], "kernel", node.condition
            if not fusion_blocked([node.condition]):
                return node.children[0], "mask", node.condition
        return self.children[0], "none", None

    def _pallas_stream_or_none(self, ctx: ExecContext, agg_time: Metric):
        """Fused filter+aggregate via ops/pallas_kernels.tile_reduce —
        one program per batch, no filtered intermediate. None keeps the
        stock XLA path (static gate miss or conf off); past that the
        kernel runs or its compile error propagates."""
        from ..conf import PALLAS_ENABLED
        from . import pallas_agg
        if not self._pallas_gate or not ctx.conf.get(PALLAS_ENABLED):
            return None
        source, kind, cond = self._pallas_filter()
        key = id(cond)
        entry = self._pallas_cache.get(key)
        if entry is None:
            plan = pallas_agg.PallasAggPlan(
                self.agg_exprs, self.input_schema,
                pred=cond if kind == "kernel" else None,
                mask_pred=cond if kind == "mask" else None)
            # a closure over this exec's plan: private, but named and
            # launched like a shared program
            entry = self._pallas_cache[key] = (plan, named_jit(
                plan.batch_fn(), "HashAggregateExec._pallas_stream"))
        plan, fn = entry

        def stream():
            m = ctx.metrics_for(self.exec_id)
            pb = m.setdefault("pallasBatches",
                              Metric("pallasBatches", Metric.DEBUG))
            mb = m.setdefault("pallasMaskFilterBatches",
                              Metric("pallasMaskFilterBatches",
                                     Metric.DEBUG))
            totals = plan.init_totals()
            saw = False
            for batch in source.execute(ctx):
                if int(batch.num_rows) == 0:
                    continue
                saw = True
                with ctx.semaphore, NvtxTimer(agg_time, "agg.pallas"):
                    partials = fn(batch)
                plan.combine(totals, partials)
                pb.add(1)
                if kind == "mask":
                    mb.add(1)
            if not saw:
                if self.mode == COMPLETE:
                    yield self._empty_global_result()
                return
            packed = self._pack(ColumnarBatch([], [], jnp.int32(1)),
                                plan.states(totals), jnp.int32(1), 8)
            if self.mode == PARTIAL:
                yield packed
            else:
                with ctx.semaphore:
                    yield self._jit_merge(packed)
        return stream()

    def _empty_global_result(self) -> ColumnarBatch:
        cap = 8
        in_schema = self.input_schema
        cols = []
        for i, (fn, name) in enumerate(self.agg_exprs):
            zero_states = {}
            for sname, stype in self._state_schemas[i]:
                if stype == dt.STRING:
                    from ..columnar.vector import StringColumn
                    zero_states[sname] = StringColumn(
                        jnp.zeros(cap + 1, jnp.int32),
                        jnp.zeros(8, jnp.uint8),
                        jnp.zeros(cap, jnp.bool_), pad_bucket=8)
                    continue
                if isinstance(stype, dt.ArrayType):
                    from ..columnar.nested import ListColumn
                    from ..columnar.vector import ColumnVector
                    et = stype.element_type
                    zero_states[sname] = ListColumn(
                        jnp.zeros(cap + 1, jnp.int32),
                        ColumnVector(jnp.zeros(8, et.physical),
                                     jnp.zeros(8, jnp.bool_), et),
                        jnp.ones(cap, jnp.bool_), et)
                    continue
                phys = stype.physical
                zero_states[sname] = jnp.zeros(cap, phys)
            data, ok = fn.finalize(zero_states)
            lm = live_mask(cap, 1)
            cols.append(make_agg_result(data, ok & lm,
                                        fn.data_type(in_schema)))
        return ColumnarBatch(cols, [n for _, n in self.agg_exprs], 1)

    def node_description(self) -> str:
        aggs = ", ".join(f"{fn.name} as {n}" for fn, n in self.agg_exprs)
        keys = ", ".join(self._key_names)
        # the statically chosen aggregate lane (the pallas conf switches
        # are read at execute time and can still keep the XLA path)
        from . import pallas_agg
        lane = ""
        if self._pallas_gate:
            lane = f" (pallas-global, filter={self._pallas_filter()[1]})"
        elif self._pallas_grouped_gate and pallas_agg.grouped_lane_on():
            lane = " (pallas-grouped)"
        return (f"HashAggregate[{self.mode}, keys=({keys}), "
                f"aggs=({aggs})]{lane}")
