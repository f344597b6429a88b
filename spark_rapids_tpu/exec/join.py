"""Join execs: shuffled/broadcast hash joins with overflow retry.

Rebuild of the reference's join stack (SURVEY §2.4):
GpuShuffledHashJoinExec.scala:90, GpuHashJoin.scala:104
(HashJoinIterator:440, gather-map based), GpuBroadcastHashJoinExecBase,
GpuSubPartitionHashJoin (oversized build sides). The kernel
(ops/kernels.py join_gather_maps) reports the true required output size;
when it exceeds the static output capacity the exec re-runs with the
reported size's capacity bucket (so the second attempt always fits) —
the TPU equivalent of the reference's SplitAndRetryOOM join contract.
_MAX_GROWTH_STEPS is a safety net against a kernel under-reporting, not
a working-set bound. Build sides above srt.sql.join.subPartitionRows
are hash-split into sub-partitions and joined pair-wise
(GpuSubPartitionHashJoin.scala): both sides are bucketed by the SAME
key hash so matching rows co-locate, each sub-build is spillable while
idle, and every probe row lands in exactly one bucket (outer-join
preservation holds per bucket).
"""

from __future__ import annotations

import time
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import ColumnarBatch, choose_capacity
from ..expr.core import Expression
from ..jit_registry import shared_fn_jit
from ..ops import kernels as K
from ..obs.trace import annotate
from .base import ExecContext, Metric, Schema, TpuExec

# Join types (Catalyst names)
INNER = "inner"
LEFT_OUTER = "left_outer"
RIGHT_OUTER = "right_outer"
FULL_OUTER = "full_outer"
LEFT_SEMI = "left_semi"
LEFT_ANTI = "left_anti"
CROSS = "cross"

# Output capacity growth is bounded: past this many doublings the probe
# batch gets split instead (GpuSubPartitionHashJoin analogue).
_MAX_GROWTH_STEPS = 4


# --- module-level jit builders (shared process-wide via jit_registry:
# every join over the same keys/type/capacity reuses one traced fn) ---

#: how a pair is joined, decided from the build side's data when it is
#: built (``_HashJoinBase._build_side``): through a direct-address table
#: of a unique integer key, or through the sorted hash index of
#: ``K.join_gather_maps``
LOOKUP = "lookup"
HASH = "hash"

#: operator metrics of the join execs (docs/OBSERVABILITY.md, "Joins"):
#: name -> (level, unit, key of the query record's ``phases`` that
#: ``plan/session.py`` sums it into; None for a gauge)
JOIN_COUNTERS = {
    "joinBuildTime": (Metric.MODERATE, "ns", "join_build_ns"),
    "lookupJoinBatches": (Metric.MODERATE, "", "lookup_join_batches"),
    "hashJoinBatches": (Metric.MODERATE, "", "hash_join_batches"),
    "joinOutCapacity": (Metric.DEBUG, "", None),
    "joinCapacityRelaunches": (Metric.MODERATE, "",
                               "join_capacity_relaunches"),
    "joinReadbacks": (Metric.MODERATE, "", "join_readbacks"),
}

_LOOKUP_KINDS = {INNER: "inner", LEFT_OUTER: "left", RIGHT_OUTER: "left",
                 LEFT_SEMI: "semi", LEFT_ANTI: "anti"}


class BuildSide(NamedTuple):
    """What a join computed from its build batch, once: ``mode`` and the
    arrays every pair's program takes after ``(probe, build)`` —
    ``(table, kmin)`` for LOOKUP, ``(hashes_sorted, order)`` for HASH.
    ``table_size``: the leading slots of ``table`` that span the keys (a
    capacity bucket; the table is built once at the largest span a
    lookup accepts, and a pair's program gathers from its head)."""
    mode: str
    aux: tuple
    table_size: int = 0


def _join_run_builder(join_type, probe_keys, build_keys, out_capacity,
                      mode, table_size=0):
    """``run(probe, build, *aux) -> (out, total)``; ``aux`` is the
    ``BuildSide.aux`` of ``mode``."""
    if mode == LOOKUP:
        kind = _LOOKUP_KINDS[join_type]

        def run_lookup(probe, build, table, kmin):
            return K.lookup_join(probe, build, probe_keys[0].eval(probe),
                                 table[:table_size], kmin, out_capacity,
                                 kind)
        return run_lookup

    def run(probe, build, *index):
        pk = [e.eval(probe) for e in probe_keys]
        bk = [e.eval(build) for e in build_keys]
        index = index or None
        if join_type in (LEFT_SEMI, LEFT_ANTI):
            out, total = K.semi_anti_join(
                probe, bk, pk, build.live_mask(),
                anti=(join_type == LEFT_ANTI),
                scratch_capacity=out_capacity, build_index=index)
        elif join_type == INNER:
            out, total = K.inner_join(probe, build, pk, bk, out_capacity,
                                      index)
        else:  # LEFT_OUTER / RIGHT_OUTER: probe is preserved side
            out, total = K.left_join(probe, build, pk, bk, out_capacity,
                                     index)
        return out, total
    return run


def _hash_index_builder(build_keys):
    def run(build):
        return K.build_hash_index([e.eval(build) for e in build_keys],
                                  build.live_mask())
    return run


def _lookup_table_builder(build_keys, size):
    def run(build):
        return K.build_lookup_table(build_keys[0].eval(build),
                                    build.live_mask(), size)
    return run


def _lookup_count_builder(join_type, probe_keys, table_size):
    kind = _LOOKUP_KINDS[join_type]

    def run(probe, table, kmin):
        return K.lookup_count(probe, probe_keys[0].eval(probe),
                              table[:table_size], kmin, kind)
    return run


def _bucket_split_builder(exprs, num_parts):
    def run(batch, p):
        return K.bucket_compact(
            batch, [e.eval(batch) for e in exprs], num_parts, p)
    return run


def _chunk_slice_builder(length, cap):
    def run(b, s):
        return K.slice_batch(b, s, length, cap)
    return run


def _bloom_build_builder(exprs, num_bits):
    from ..ops import bloom as B

    def mk(b):
        return B.build_bloom([e.eval(b) for e in exprs],
                             b.live_mask(), num_bits)
    return mk


def _bloom_probe_builder(exprs):
    from ..columnar.vector import ColumnVector
    from ..ops import bloom as B

    def probe_fn(bits_, b):
        keep = B.might_contain(bits_, [e.eval(b) for e in exprs])
        cond = ColumnVector(keep, jnp.ones_like(keep), dt.BOOL)
        return K.filter_batch(b, cond)
    return probe_fn


class _HashJoinBase(TpuExec):
    """Shared machinery: build-side materialization + per-probe-batch
    gather-map join with capacity retry."""

    #: armed by exec/fused.py FusedHashJoinExec (plan/overrides.py
    #: fusion pass): when set, the per-pair join program is the fused
    #: join+suffix program; ALL orchestration around it (broadcast
    #: demotion, skew splits, sub-partitioning, bloom, DPP, growth
    #: retries) stays in this class unchanged
    _fusion = None

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str = INNER,
                 build_side: str = "right",
                 condition: Optional[Expression] = None):
        super().__init__(left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.build_side = build_side
        self.condition = condition
        if join_type in (LEFT_SEMI, LEFT_ANTI):
            if build_side != "right":
                raise ValueError("semi/anti joins build the right side")
        elif join_type == LEFT_OUTER:
            if build_side != "right":
                raise ValueError(
                    "left outer requires build=right (probe preserves left)")
        elif join_type == RIGHT_OUTER:
            if build_side != "left":
                raise ValueError(
                    "right outer requires build=left (probe preserves right)")
        elif join_type not in (INNER,):
            raise NotImplementedError(
                f"join type {join_type!r} not supported on TPU yet "
                "(planner must fall back)")
        self._jit_cache = {}
        #: output-capacity bucket the pairs of this join have needed so
        #: far (``_pair_capacity``): measured, never configured. Kept
        #: across re-runs of a cached plan — it is only where a pair's
        #: first launch starts; the overflow contract still decides.
        self._cap_hint = 0

    @property
    def output_schema(self) -> Schema:
        left_s = self.children[0].output_schema
        right_s = self.children[1].output_schema
        if self.join_type in (LEFT_SEMI, LEFT_ANTI):
            return left_s
        return left_s + right_s

    # --- build side ---
    def _concat_build(self, ctx: ExecContext,
                      stream) -> Optional[ColumnarBatch]:
        sized = [(b, self._read(ctx, b.num_rows)[0]) for b in stream]
        batches = [b for b, n in sized if n > 0]
        if not batches:
            return None
        total = sum(n for _, n in sized)
        if len(batches) == 1:
            # the row count it was just read for, on the host from here
            b = batches[0]
            return ColumnarBatch(b.columns, b.names, total)
        with ctx.semaphore:
            return K.concat_batches(batches, choose_capacity(total))

    def _key_cols(self, batch: ColumnarBatch, exprs):
        return [e.eval(batch) for e in exprs]

    def _eager_keys(self) -> bool:
        from ..expr.misc import contains_eager
        return contains_eager(list(self._probe_key_exprs)
                              + list(self._build_key_exprs))

    def _shared(self, key, builder, *args):
        """``builder(*args)`` jitted once per instance under ``key``,
        shared process-wide (registry) across joins with equal keys /
        type. Eager keys (ANSI guards) evaluate un-jitted."""
        if key not in self._jit_cache:
            self._jit_cache[key] = builder(*args) if self._eager_keys() \
                else shared_fn_jit(builder, *args)
        return self._jit_cache[key]

    def _join_fn(self, out_capacity: int, side: BuildSide):
        """The per-pair program: jit per output capacity and the build
        side's mode (and table size)."""
        return self._shared(
            (out_capacity, side.mode, side.table_size), _join_run_builder,
            self.join_type, self._probe_key_exprs, self._build_key_exprs,
            out_capacity, side.mode, side.table_size)

    # --- counters, host reads ---
    def _counter(self, ctx: ExecContext, name: str) -> Metric:
        level, unit, _ = JOIN_COUNTERS[name]
        return ctx.metrics_for(self.exec_id).setdefault(
            name, Metric(name, level, unit))

    def _read(self, ctx: ExecContext, *scalars):
        """Host values of device scalars, read together: ONE round trip,
        counted in ``joinReadbacks``. Values already on the host cost
        nothing and count nothing."""
        if all(isinstance(x, int) for x in scalars):
            return scalars
        self._counter(ctx, "joinReadbacks").add(1)
        return tuple(int(x) for x in jax.device_get(scalars))

    # --- build side: computed once per build batch ---
    def _lookup_side(self, ctx: ExecContext, build: ColumnarBatch
                     ) -> Optional[BuildSide]:
        """A LOOKUP build side where ``build``'s data allows one: a
        single integer key on both sides whose live values are unique
        and span no more than the largest batch capacity bucket this
        query runs at (or the build's own). Read from the data: one
        program (key range, table and slots taken together) and one
        host read, once per build."""
        from ..conf import BATCH_SIZE_ROWS
        if len(self._build_key_exprs) != 1 or self._eager_keys():
            return None
        probe_child, build_child = (self.children if self.build_side ==
                                    "right" else self.children[::-1])
        probe_type = self._probe_key_exprs[0].data_type(
            probe_child.output_schema)
        build_type = self._build_key_exprs[0].data_type(
            build_child.output_schema)
        if probe_type != build_type or not build_type.is_integral:
            return None
        bound = max(choose_capacity(ctx.conf.get(BATCH_SIZE_ROWS)),
                    build.capacity)
        make = self._shared(("lookup_table", bound), _lookup_table_builder,
                            self._build_key_exprs, bound)
        with ctx.semaphore:
            table, kmin, *stats = make(build)
            lo, hi, n_keys, n_distinct = self._read(ctx, kmin, *stats)
        span = hi - lo + 1  # exact: the host's integers
        if span > bound or n_distinct != n_keys:
            return None  # too sparse, or a key value held by two rows
        return BuildSide(LOOKUP, (table, kmin),
                         choose_capacity(max(span, 1)))

    def _build_side(self, ctx: ExecContext, build: ColumnarBatch,
                    lookup: bool = True) -> BuildSide:
        """The lookup table (where ``lookup`` lets it be tried), or else
        the sorted hash index, of ``build``. Kept on the query's context
        under this join while the same batch is probed: every probe
        batch of a broadcast join, every probe bucket of a sub-partition,
        finds it there."""
        held = ctx.join_builds.get(self.exec_id)
        if held is not None and held[0] is build:
            return held[1]
        t0 = time.perf_counter_ns()
        with annotate("join.build"):
            side = self._lookup_side(ctx, build) if lookup else None
            if side is None:
                if self._build_key_exprs:
                    index = self._shared("hash_index", _hash_index_builder,
                                         self._build_key_exprs)
                    with ctx.semaphore:
                        side = BuildSide(HASH, tuple(index(build)))
                else:
                    side = BuildSide(HASH, ())
        self._counter(ctx, "joinBuildTime").add(
            time.perf_counter_ns() - t0)
        ctx.join_builds[self.exec_id] = (build, side)
        return side

    # --- output capacity: follows the matches, not the probe ---
    def _pair_capacity(self, ctx: ExecContext, probe: ColumnarBatch,
                       side: BuildSide) -> int:
        """Capacity of a pair's first launch. A lookup's: the bucket its
        earlier pairs needed and, before any has run, the pair's own
        match count. The hash join's, as ever: the probe's rows (every
        probe row matching about one build row)."""
        if side.mode == LOOKUP:
            if not self._cap_hint:
                count = self._shared(
                    ("lookup_count", side.table_size),
                    _lookup_count_builder, self.join_type,
                    self._probe_key_exprs, side.table_size)
                with ctx.semaphore:
                    total = count(probe, *side.aux)
                self._note_total(side, self._read(ctx, total)[0])
            # a unique build key: no more rows than the probe holds
            return min(self._cap_hint, probe.capacity)
        n_probe, = self._read(ctx, probe.num_rows)
        return choose_capacity(max(n_probe, 16))

    def _note_total(self, side: BuildSide, total: int) -> None:
        if side.mode == LOOKUP:
            self._cap_hint = max(self._cap_hint,
                                 choose_capacity(max(total, 16)))

    def _count_pair(self, ctx: ExecContext, side: BuildSide,
                    out_cap: int) -> None:
        self._counter(ctx, "lookupJoinBatches" if side.mode == LOOKUP
                      else "hashJoinBatches").add(1)
        self._counter(ctx, "joinOutCapacity").set(out_cap)

    @property
    def _probe_key_exprs(self):
        return self.left_keys if self.build_side == "right" \
            else self.right_keys

    @property
    def _build_key_exprs(self):
        return self.right_keys if self.build_side == "right" \
            else self.left_keys

    def _probe_stream(self, ctx: ExecContext):
        probe_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        return probe_child.execute(ctx)

    def _build_stream(self, ctx: ExecContext):
        build_child = self.children[1] if self.build_side == "right" \
            else self.children[0]
        return build_child.execute(ctx)

    def _reorder_columns(self, out: ColumnarBatch) -> ColumnarBatch:
        """Kernel output is probe-then-build; plan output is left-then-
        right."""
        if self.build_side == "right" or self.join_type in (LEFT_SEMI,
                                                            LEFT_ANTI):
            return out
        n_right = len(self.children[1].output_schema)
        cols = out.columns[n_right:] + out.columns[:n_right]
        names = out.names[n_right:] + out.names[:n_right]
        return ColumnarBatch(cols, names, out.num_rows)

    def _empty_result(self, probe_stream, ctx) -> Iterator[ColumnarBatch]:
        """Build side empty: inner/semi produce nothing; left-outer and
        anti pass probe rows with null build columns. An armed fusion
        runs its absorbed suffix over the passthrough batches (the
        unfused plan's filter/project/agg would see them too)."""
        stream = self._empty_result_core(probe_stream, ctx)
        if self._fusion is not None and \
                self._fusion._exec_state is not None:
            stream = self._fusion.suffix_fallback(ctx, stream)
        yield from stream

    def _empty_result_core(self, probe_stream, ctx
                           ) -> Iterator[ColumnarBatch]:
        jt = self.join_type
        if jt in (INNER, LEFT_SEMI):
            return
        build_schema = (self.children[1].output_schema
                        if self.build_side == "right"
                        else self.children[0].output_schema)
        for probe in probe_stream:
            if jt == LEFT_ANTI:
                yield probe
                continue
            # left outer with empty build: null-extend
            cap = probe.capacity
            from ..columnar.vector import ColumnVector, StringColumn
            null_cols = []
            for name, t in build_schema:
                if t == dt.STRING:
                    null_cols.append(StringColumn(
                        jnp.zeros(cap + 1, jnp.int32),
                        jnp.zeros(128, jnp.uint8),
                        jnp.zeros(cap, jnp.bool_)))
                else:
                    phys = t.physical
                    null_cols.append(ColumnVector(
                        jnp.zeros(cap, phys), jnp.zeros(cap, jnp.bool_), t))
            out = ColumnarBatch(
                list(probe.columns) + null_cols,
                probe.names + [n for n, _ in build_schema], probe.num_rows)
            yield self._reorder_columns(out)

    def _join_pair(self, ctx: ExecContext, probe: ColumnarBatch,
                   build: ColumnarBatch, retries: Metric
                   ) -> ColumnarBatch:
        """One probe batch against one build batch, with capacity
        growth retry. One host read a launch: the required size and the
        output's row count together, so the batch that leaves carries
        its row count on the host."""
        from ..conf import JOIN_GROWTH_STEPS
        max_steps = ctx.conf.get(JOIN_GROWTH_STEPS)
        side = self._build_side(ctx, build)
        out_cap = self._pair_capacity(ctx, probe, side)
        for step in range(max_steps + 1):
            with ctx.semaphore:
                out, total = self._join_fn(out_cap, side)(
                    probe, build, *side.aux)
            total, n_out = self._read(ctx, total, out.num_rows)
            if total <= out_cap:
                self._note_total(side, total)
                self._count_pair(ctx, side, out_cap)
                return self._reorder_columns(
                    ColumnarBatch(out.columns, out.names, n_out))
            retries.add(1)
            self._counter(ctx, "joinCapacityRelaunches").add(1)
            out_cap = choose_capacity(total)
        raise RuntimeError(
            f"join expansion {total} exceeded capacity after "
            f"{max_steps} growth steps")

    def _join_batches(self, ctx: ExecContext, probe: ColumnarBatch,
                      build: ColumnarBatch, retries: Metric
                      ) -> Iterator[ColumnarBatch]:
        """One probe batch against one build batch. Unfused: a single
        capacity-retried gather-map join. When a FusedHashJoinExec
        armed this node, the pair runs through the fused join+suffix
        program with per-batch split-and-retry instead (possibly
        several output batches, or none when an absorbed filter drops
        everything)."""
        if self._fusion is not None and \
                self._fusion._exec_state is not None:
            yield from self._fusion.fused_pairs(ctx, probe, build,
                                                retries)
            return
        yield self._join_pair(ctx, probe, build, retries)

    def _split_fn(self, num_parts: int, side: str):
        """jit'd key-hash bucket filter (ops/kernels.py bucket_compact):
        (batch, p) -> rows of bucket p, same capacity."""
        key = ("split", num_parts, side)
        if key not in self._jit_cache:
            exprs = self._probe_key_exprs if side == "probe" \
                else self._build_key_exprs
            from ..expr.misc import contains_eager
            if contains_eager(exprs):
                self._jit_cache[key] = _bucket_split_builder(exprs,
                                                             num_parts)
            else:
                self._jit_cache[key] = shared_fn_jit(
                    _bucket_split_builder, exprs, num_parts)
        return self._jit_cache[key]

    def _repack(self, ctx: ExecContext, batch: ColumnarBatch
                ) -> ColumnarBatch:
        """Shrink a compacted batch to its tight capacity bucket —
        compact() preserves the source capacity, so without this the
        sub-partition machinery would multiply, not bound, memory."""
        n = int(batch.num_rows)
        cap = choose_capacity(max(n, 8))
        if cap >= batch.capacity:
            return batch
        with ctx.semaphore:
            return K.repack_to(batch, cap)

    def _sub_partition_join(self, ctx: ExecContext, probe_stream,
                            build_holder: List[ColumnarBatch], threshold: int
                            ) -> Iterator[ColumnarBatch]:
        """GpuSubPartitionHashJoin: bucket BOTH sides by the same key
        hash, then join bucket-pairs so each sub-build is materialized
        once. ``build_holder`` transfers ownership of the concatenated
        build (the caller's reference is dropped so it can be freed as
        soon as bucketing finishes). An inner-join bucket still over
        budget (single hot key defeats key hashing) is row-chunked;
        other join types record the skew and run the bucket whole."""
        from ..memory.spill import SpillableBatch, SpillPriority
        m = ctx.metrics_for(self.exec_id)
        retries = m.setdefault("joinOverflowRetries",
                               Metric("joinOverflowRetries", Metric.DEBUG))
        parts_m = m.setdefault("joinSubPartitions",
                               Metric("joinSubPartitions", Metric.DEBUG))
        skew_m = m.setdefault("joinSubPartitionSkew",
                              Metric("joinSubPartitionSkew", Metric.DEBUG))
        build = build_holder.pop()
        P = max(2, -(-int(build.num_rows) // max(threshold, 1)))
        parts_m.add(P)
        sub_builds: List[Optional[SpillableBatch]] = []
        split_b = self._split_fn(P, "build")
        for p in range(P):
            with ctx.semaphore:
                sub = split_b(build, jnp.int32(p))
            if int(sub.num_rows) == 0:
                sub_builds.append(None)
                continue
            sub = self._repack(ctx, sub)
            from ..memory.retry import with_retry_no_split
            sub_builds.append(with_retry_no_split(
                lambda s=sub: SpillableBatch(
                    s, SpillPriority.ACTIVE_ON_DECK)))
        del build, sub

        # bucket the whole probe stream first, so each sub-build is
        # unspilled exactly once (not once per probe batch)
        split_p = self._split_fn(P, "probe")
        probe_buckets: List[List[SpillableBatch]] = [[] for _ in range(P)]
        try:
            for probe in probe_stream:
                if int(probe.num_rows) == 0:
                    continue
                for p in range(P):
                    with ctx.semaphore:
                        sub = split_p(probe, jnp.int32(p))
                    if int(sub.num_rows) == 0:
                        continue
                    sub = self._repack(ctx, sub)
                    from ..memory.retry import with_retry_no_split
                    probe_buckets[p].append(with_retry_no_split(
                        lambda s=sub: SpillableBatch(
                            s, SpillPriority.ACTIVE_ON_DECK)))
            for p in range(P):
                if not probe_buckets[p]:
                    continue
                sb = sub_builds[p]
                if sb is None:
                    for psb in probe_buckets[p]:
                        yield from self._empty_result(
                            iter([psb.get()]), ctx)
                        psb.close()
                    probe_buckets[p] = []
                    continue
                from ..memory.retry import with_retry_no_split
                bucket_build = with_retry_no_split(sb.get)
                n_build = int(bucket_build.num_rows)
                if n_build > threshold:
                    skew_m.add(1)
                if n_build > threshold and self.join_type == INNER:
                    # hot-key bucket: arbitrary row chunks are correct
                    # for inner joins (matches are a disjoint union)
                    chunks = -(-n_build // threshold)
                    chunk_cap = choose_capacity(threshold)
                    ck = ("chunk", bucket_build.capacity, chunk_cap)
                    if ck not in self._jit_cache:
                        self._jit_cache[ck] = shared_fn_jit(
                            _chunk_slice_builder, threshold, chunk_cap)
                    for ci in range(chunks):
                        with ctx.semaphore:
                            chunk = self._jit_cache[ck](
                                bucket_build, jnp.int32(ci * threshold))
                        self._build_side(ctx, chunk, lookup=False)
                        for psb in probe_buckets[p]:
                            yield from self._join_batches(
                                ctx, psb.get(), chunk, retries)
                else:
                    # a bucket of a build too large for one batch keeps
                    # the general path: no lookup is tried on it
                    self._build_side(ctx, bucket_build, lookup=False)
                    for psb in probe_buckets[p]:
                        yield from self._join_batches(
                            ctx, psb.get(), bucket_build, retries)
                for psb in probe_buckets[p]:
                    psb.close()
                probe_buckets[p] = []
                sb.close()
                sub_builds[p] = None
        finally:
            # the last bucket's build side: not to outlive its batch
            ctx.join_builds.pop(self.exec_id, None)
            for sb in sub_builds:
                if sb is not None:
                    sb.close()
            for bucket in probe_buckets:
                for psb in bucket:
                    psb.close()

    def _bloom_prefilter(self, ctx: ExecContext, probe_stream,
                         build: ColumnarBatch):
        """Runtime bloom join filter (GpuBloomFilterAggregate /
        GpuBloomFilterMightContain role): drop probe rows whose keys
        cannot be in the build side BEFORE the gather-map join. Sound
        only where dropped probe rows produce no output — inner and
        left-semi."""
        from ..conf import JOIN_BLOOM_ENABLED, JOIN_BLOOM_MIN_PROBE_ROWS
        from ..ops import bloom as B
        if not ctx.conf.get(JOIN_BLOOM_ENABLED) or \
                self.join_type not in (INNER, LEFT_SEMI) or \
                not (self.left_keys or self.right_keys):
            return probe_stream
        from ..conf import JOIN_BLOOM_BITS_PER_KEY
        min_rows = ctx.conf.get(JOIN_BLOOM_MIN_PROBE_ROWS)
        num_bits = B.choose_num_bits(
            int(build.num_rows), ctx.conf.get(JOIN_BLOOM_BITS_PER_KEY))
        eager = self._eager_keys()
        bkey = ("bloom_build", num_bits)
        if bkey not in self._jit_cache:
            self._jit_cache[bkey] = _bloom_build_builder(
                self._build_key_exprs, num_bits) if eager else \
                shared_fn_jit(_bloom_build_builder,
                              self._build_key_exprs, num_bits)
        with ctx.semaphore:
            bits = self._jit_cache[bkey](build)
        pkey = ("bloom_probe", num_bits)
        if pkey not in self._jit_cache:
            self._jit_cache[pkey] = _bloom_probe_builder(
                self._probe_key_exprs) if eager else \
                shared_fn_jit(_bloom_probe_builder, self._probe_key_exprs)
        m = ctx.metrics_for(self.exec_id)
        dropped = m.setdefault("bloomFilteredRows",
                               Metric("bloomFilteredRows", Metric.DEBUG))

        def filtered():
            for probe in probe_stream:
                n = int(probe.num_rows)
                if n < min_rows:
                    yield probe
                    continue
                with ctx.semaphore:
                    out = self._jit_cache[pkey](bits, probe)
                dropped.add(n - int(out.num_rows))
                yield out
        return filtered()

    # set True on broadcast joins: their build side fully materializes
    # BEFORE the probe's first scan file opens, so its keys can prune
    # partitioned probe scans (shuffled joins run the probe map phase
    # first — too late to prune)
    _dpp_capable = False

    def _dpp_scans(self, node, name: str):
        """Partitioned FileSourceScanExecs below ``node`` that column
        ``name`` passes through UNCHANGED (conservative walk — any node
        that might rename/compute the column stops the descent)."""
        from ..io.scan import FileSourceScanExec
        from .basic import (CoalesceBatchesExec, FilterExec, LocalLimitExec,
                            ProjectExec)
        from .pipeline import PrefetchExec
        if isinstance(node, FileSourceScanExec):
            if any(k == name for k, _ in node.scan.partition_schema):
                yield node
            return
        if isinstance(node, ProjectExec):
            from ..expr.core import Alias, ColumnRef
            for e, (out_name, _) in zip(node.exprs, node.output_schema):
                if out_name != name:
                    continue
                inner = e.children[0] if isinstance(e, Alias) else e
                if isinstance(inner, ColumnRef) and inner.name == name:
                    yield from self._dpp_scans(node.children[0], name)
                return
            return
        if isinstance(node, (FilterExec, CoalesceBatchesExec,
                             LocalLimitExec, PrefetchExec)):
            yield from self._dpp_scans(node.children[0], name)
            return
        from .fused import FusedPipelineExec
        if isinstance(node, FusedPipelineExec):
            # see through the fusion wrapper via the original chain —
            # the stage nodes keep their unfused child links, so the
            # usual Project/Filter pass-through rules apply unchanged
            yield from self._dpp_scans(node.stages[-1], name)
            return
        # unknown/multi-child operator: don't assume pass-through

    def _dpp_targets(self, ctx: ExecContext):
        """What runtime partition pruning applies to in this execution:
        ``(probe key name, build key expression, partitioned probe-side
        scans of that key)`` per key pair; nothing where the join cannot
        prune."""
        from ..conf import DPP_ENABLED
        from ..expr.core import ColumnRef
        if not self._dpp_capable or not ctx.conf.get(DPP_ENABLED):
            return []
        if self.join_type not in (INNER, LEFT_SEMI):
            # outer/anti joins PRESERVE unmatched probe rows — pruning
            # their files would drop them
            return []
        probe_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        targets = []
        for pk, bk in zip(self._probe_key_exprs, self._build_key_exprs):
            if not isinstance(pk, ColumnRef):
                continue
            scans = list(self._dpp_scans(probe_child, pk.name))
            if scans:
                targets.append((pk.name, bk, scans))
        return targets

    def _runtime_partition_prune(self, ctx: ExecContext,
                                 build: ColumnarBatch) -> None:
        """Runtime DPP (GpuSubqueryBroadcastExec:1-299 +
        GpuDynamicPruningExpression role): the materialized build
        side's distinct join-key values become a partition-value filter
        on probe-side partitioned scans."""
        for name, bk, scans in self._dpp_targets(ctx):
            kcol = bk.eval(build)
            vals, mask = kcol.to_numpy(int(build.num_rows))
            keys = {v.item() if hasattr(v, "item") else v
                    for v, ok in zip(vals, mask) if ok}
            m = ctx.metrics_for(self.exec_id)
            m.setdefault("dppFilters",
                         Metric("dppFilters", Metric.MODERATE)).add(
                len(scans))
            for s in scans:
                f = dict(s.runtime_part_filter or {})
                f[name] = keys
                s.runtime_part_filter = f

    def _join_partition(self, ctx: ExecContext, probe_stream,
                        build_stream) -> Iterator[ColumnarBatch]:
        """Join one (probe partition, build partition) pair."""
        from ..conf import JOIN_SUB_PARTITION_ROWS
        m = ctx.metrics_for(self.exec_id)
        retries = m.setdefault("joinOverflowRetries",
                               Metric("joinOverflowRetries", Metric.DEBUG))
        build = self._concat_build(ctx, build_stream)
        if build is None:
            yield from self._empty_result(probe_stream, ctx)
            return
        self._runtime_partition_prune(ctx, build)
        threshold = ctx.conf.get(JOIN_SUB_PARTITION_ROWS)
        n_rows, = self._read(ctx, build.num_rows)
        keyed = bool(self.left_keys or self.right_keys)
        sub = n_rows > threshold and keyed
        if not sub and keyed:
            # adaptive byte cap: a build side whose MEASURED bytes
            # exceed srt.sql.adaptive.maxBroadcastJoinBytes joins
            # sub-partitioned even when its row count looks benign
            # (wide rows defeat the row threshold) — the single hash
            # table is bounded either way
            from ..conf import ADAPTIVE_MAX_BROADCAST_BYTES
            if ctx.conf.get(ADAPTIVE_MAX_BROADCAST_BYTES) > 0:
                from ..memory.spill import batch_nbytes
                from ..plan.adaptive import broadcast_oversize_slices
                slices = broadcast_oversize_slices(
                    ctx, self, n_rows, batch_nbytes(build))
                if slices:
                    threshold = max(-(-n_rows // slices), 1)
                    sub = True
        if sub or self._build_side(ctx, build).mode != LOOKUP:
            # (a lookup is one gather a probe row: nothing there for a
            # bloom filter, a hash and a compaction a probe row, to save)
            probe_stream = self._bloom_prefilter(ctx, probe_stream, build)
        if sub:
            holder = [build]
            del build
            yield from self._sub_partition_join(ctx, probe_stream, holder,
                                                threshold)
            return
        for probe in probe_stream:
            if self._read(ctx, probe.num_rows)[0] == 0:
                continue
            yield from self._join_batches(ctx, probe, build, retries)

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from .pipeline import sources_started
        with sources_started(ctx, self):
            yield from self._join_partition(ctx, self._probe_stream(ctx),
                                            self._build_stream(ctx))


class ShuffledHashJoinExec(_HashJoinBase):
    """Hash join where both sides arrive co-partitioned on the join keys
    (GpuShuffledHashJoinExec.scala:90): the planner exchanges both
    children into the same hash partitioning; each partition pair joins
    independently (the distributed join decomposition)."""

    def required_child_distributions(self):
        from ..plan.distribution import (ClusteredDistribution,
                                         UnspecifiedDistribution)
        if not self.left_keys:
            return [UnspecifiedDistribution(), UnspecifiedDistribution()]
        return [ClusteredDistribution(self.left_keys),
                ClusteredDistribution(self.right_keys)]

    @property
    def output_partitioning(self):
        # rows stay in their partition; the probe side's placement holds
        probe = self.children[0] if self.build_side == "right" \
            else self.children[1]
        return probe.output_partitioning

    def _demoted_broadcast_streams(self, ctx: ExecContext):
        """Execution body of the joinStrategy demotion decided by
        plan/adaptive.py (the AQE decision the reference takes via
        GpuQueryStagePrepOverrides + Spark's DynamicJoinSelection): the
        measured-small build side streams whole as a broadcast-style
        single stream and the probe-side exchange is BYPASSED entirely
        (its map phase never runs). Returns (probe_stream,
        build_stream)."""
        build_child = self.children[1] if self.build_side == "right" \
            else self.children[0]
        probe_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        counts, _ = build_child.materialized_stats(ctx)
        m = ctx.metrics_for(self.exec_id)
        m.setdefault("adaptiveBroadcastJoins",
                     Metric("adaptiveBroadcastJoins",
                            Metric.MODERATE)).add(1)

        def build_stream():
            if ctx.cluster is not None:
                # broadcast semantics: EVERY worker needs the FULL
                # build side — fetch all reduce partitions from all
                # peers (materialized_stats' gather already
                # synchronized the map writes; `allowed` restricts
                # reads to the maps that won speculation)
                from ..parallel.transport import fetch_all_partitions
                peers = ctx.cluster.peers
                allowed = build_child._allowed_by_endpoint(ctx)
                resolver = ctx.cluster.resolve_endpoint
                for reduce_id in range(len(counts)):
                    yield from fetch_all_partitions(
                        peers, build_child.shuffle_id, reduce_id,
                        endpoint_resolver=resolver, allowed=allowed)
                return
            for part in build_child.execute_partitioned(ctx):
                yield from part
        # the probe exchange's CHILD streams directly: its shuffle work
        # is skipped (never registered, nothing to unregister); in
        # cluster mode that child is this worker's scan shard, which is
        # exactly the broadcast-join probe distribution
        return probe_child.children[0].execute(ctx), build_stream()

    def _zipped_partitions(self, ctx: ExecContext, decision):
        """Pairwise (probe, build) partition streams. zip_longest (not
        zip) so both child generators are driven to exhaustion in order
        — an exchange unregisters its shuffle in a finally that must run
        only after its last partition has been consumed. When the
        adaptive decision regrouped partitions, ONE grouping applies to
        both sides (keys stay aligned) and skewed groups read the probe
        side in map-id slices."""
        import itertools
        l, r = self.children[0], self.children[1]
        if decision.mode == "partitioned" and \
                decision.out_groups is not None:
            if decision.n_skewed:
                m = ctx.metrics_for(self.exec_id)
                m.setdefault(
                    "skewedJoinPartitions",
                    Metric("skewedJoinPartitions",
                           Metric.MODERATE)).add(decision.n_skewed)
            probe_is_left = self.build_side == "right"
            probe_x, build_x = (l, r) if probe_is_left else (r, l)
            probe_parts = probe_x.execute_partition_groups(
                ctx, decision.out_groups, map_mod=decision.probe_mod)
            build_parts = build_x.execute_partition_groups(
                ctx, decision.build_groups)
            for pp, bp in itertools.zip_longest(probe_parts,
                                                build_parts):
                yield (pp, bp)
            return
        left_parts = l.execute_partitioned(ctx)
        right_parts = r.execute_partitioned(ctx)
        for lp, rp in itertools.zip_longest(left_parts, right_parts):
            if lp is None or rp is None:
                raise RuntimeError(
                    "join children partition counts differ")
            yield ((lp, rp) if self.build_side == "right" else (rp, lp))

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        for part in self.execute_partitioned(ctx):
            yield from part

    def execute_partitioned(self, ctx: ExecContext):
        # the rules live in plan/adaptive.py; the decision is cached on
        # this node (the eager stage executor may have attached it
        # already), cluster-safe by construction — a pure function of
        # globally gathered statistics
        from ..plan.adaptive import join_decision
        decision = join_decision(ctx, self)
        if decision.mode == "broadcast_build":
            probe_stream, build_stream = \
                self._demoted_broadcast_streams(ctx)
            yield self._join_partition(ctx, probe_stream, build_stream)
            return
        for probe, build in self._zipped_partitions(ctx, decision):
            yield self._join_partition(ctx, probe, build)

    def node_description(self) -> str:
        return (f"ShuffledHashJoin[{self.join_type}, "
                f"build={self.build_side}]")


class BroadcastHashJoinExec(_HashJoinBase):
    """Hash join with a broadcast build side
    (GpuBroadcastHashJoinExecBase.scala): the build child is a
    BroadcastExchangeExec; the probe side streams through unexchanged.
    Under a mesh the build side is replicated to every device
    (all_gather)."""

    _dpp_capable = True

    def required_child_distributions(self):
        from ..plan.distribution import (BroadcastDistribution,
                                         UnspecifiedDistribution)
        if self.build_side == "right":
            return [UnspecifiedDistribution(), BroadcastDistribution()]
        return [BroadcastDistribution(), UnspecifiedDistribution()]

    @property
    def output_partitioning(self):
        probe = self.children[0] if self.build_side == "right" \
            else self.children[1]
        return probe.output_partitioning

    def execute_partitioned(self, ctx: ExecContext):
        """The advertised partitioning is the PROBE side's, so a
        partition-wise consumer (a co-partitioned join above) must see
        one joined output partition per probe partition — the build
        side is the same broadcast table for every one of them. The
        whole-stream default made the advertisement a lie (SF1 q11/q74:
        'join children partition counts differ' one join up).

        The build concats ONCE (each _join_partition then no-ops its
        single-batch concat) and runtime partition pruning runs BEFORE
        a probe side it can prune starts executing — the first pull on
        a probe exchange drains its scans, after which a prune is too
        late. A probe side it cannot prune may have been scanning since
        the join started (``start_sources``)."""
        from .pipeline import sources_started
        probe_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        with sources_started(ctx, self):
            build = self._concat_build(ctx, self._build_stream(ctx))
            if build is not None:
                self._runtime_partition_prune(ctx, build)
            for probe in probe_child.execute_partitioned(ctx):
                if build is None:
                    yield self._measure_stream(
                        ctx, self._empty_result(probe, ctx))
                else:
                    yield self._measure_stream(
                        ctx, self._join_partition(ctx, probe,
                                                  iter([build])))

    def start_sources(self, ctx: ExecContext) -> List[str]:
        """Both children's: the build side is drained first and the
        probe side pulled only then, so started here they run beside
        each other. Held back: a probe side whose partitioned scans the
        build's keys are about to prune (``_runtime_partition_prune``
        sets their file filter once the build is drained; a scan that
        has started has listed its files)."""
        probe_child, build_child = (self.children if self.build_side ==
                                    "right" else self.children[::-1])
        started = build_child.start_sources(ctx)
        if not self._dpp_targets(ctx):
            started = started + probe_child.start_sources(ctx)
        return started

    def node_description(self) -> str:
        return (f"BroadcastHashJoin[{self.join_type}, "
                f"build={self.build_side}]")
