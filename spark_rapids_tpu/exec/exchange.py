"""Exchange execs: shuffle (hash/round-robin/range) and broadcast.

Rebuild of GpuShuffleExchangeExecBase.scala (:167,
prepareBatchShuffleDependency :277) + GpuHashPartitioningBase /
GpuRangePartitioner + GpuBroadcastExchangeExec.scala:352 (SURVEY §2.7):
each incoming batch is split on-device into the target partitions
(parallel/partition.py — the cudf Table.partition equivalent), the
per-partition slices become shuffle blocks via the manager
(device-cached or serialized host blocks), and the read side streams one
reduce partition's blocks back.

These nodes are *planned*: overrides.ensure_distribution inserts them
wherever a parent operator's required distribution (aggregate merge
clustering, join co-partitioning, global-sort ordering) is not satisfied
by its child — Spark's EnsureRequirements over our exec tree.

Under a device mesh the same partitioning feeds the all-to-all
collective instead (parallel/shuffle.py shuffle_exchange) — that path
compiles into the SPMD program and never touches this manager
(plan/mesh_executor.py lowers these nodes to collectives).

A single-process exchange (no cluster) whose blocks stay device batches
cuts nothing its reader would only put back together. A hash or
round-robin map batch is held WHOLE with the rows and bytes each reduce
partition would receive (one count program, shapes for the bytes), so
AQE's statistics are the split's; a reader that takes every partition
as one group streams the held batches, and any other read first cuts
each into the same slices: one program that, knowing every partition's
rows, gathers each slice straight to its own capacity (``cut_batch``)
instead of every partition at the batch's. A range exchange
whose rows fit one batch takes the range split with every row in
partition 0 (no sample, no bounds), and a one-partition exchange
writes its batches as they are. ``shuffleWholeBatches`` /
``shuffleSplitBatches`` count which way each map batch went.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnVector, ColumnarBatch, StringColumn,
                               choose_capacity)
from ..conf import BATCH_SIZE_ROWS, SHUFFLE_PARTITIONS
from ..expr.core import Expression
from ..jit_registry import read_back, read_int, shared_fn_jit
from ..memory.spill import batch_nbytes
from ..ops import kernels as K
from ..parallel.partition import (PartitionedBatch, cut_batch,
                                  cut_carries, hash_partition_ids,
                                  partition_batch, partition_counts,
                                  range_partition_ids,
                                  round_robin_partition_ids,
                                  string_from_padded)
from ..parallel.shuffle_manager import (WHOLE, ShuffleManager,
                                        shuffle_manager)
from .base import (ExecContext, Metric, NvtxTimer, Schema, TpuExec,
                   count_compaction)

_SHUFFLE_IDS = itertools.count(1)
_IDS_LOCK = threading.Lock()


def next_shuffle_id() -> int:
    with _IDS_LOCK:
        return next(_SHUFFLE_IDS)


def seed_shuffle_ids(base: int) -> None:
    """Restart the local shuffle-id counter at ``base``.

    Shuffle ids are allocated during per-worker plan translation, so
    peers agree on them only if their counters start from the same
    point. A process-lifetime counter breaks the moment membership is
    elastic: a worker that joins (or REjoins) mid-session has built
    fewer exchanges than the veterans, its ids lag theirs, and the
    cluster deadlocks with every worker waiting at a differently-keyed
    stage barrier. The driver therefore ships a fresh ``sid_base``
    with every attempt and workers re-seed before translating."""
    global _SHUFFLE_IDS
    with _IDS_LOCK:
        _SHUFFLE_IDS = itertools.count(base)


def partition_slice(pb: PartitionedBatch, i: int) -> ColumnarBatch:
    """Extract partition i of a PartitionedBatch as a standalone batch."""
    S = pb.slot_capacity
    cols = []
    for spec, dtype in zip(pb.columns, pb.dtypes):
        if isinstance(dtype, dt.ArrayType):
            from ..parallel.partition import list_from_packed
            lens, valid, cdata, cok, e_counts = spec
            cols.append(list_from_packed(lens[i], valid[i], cdata[i],
                                         cok[i], e_counts[i],
                                         dtype.element_type))
        elif dtype == dt.STRING:
            padded, lens, valid = spec
            cols.append(string_from_padded(padded[i], lens[i], valid[i]))
        elif isinstance(dtype, dt.DecimalType) and dtype.is_wide:
            from ..columnar.decimal128 import Decimal128Column
            hi, lo, valid = spec
            cols.append(Decimal128Column(hi[i], lo[i], valid[i], dtype))
        else:
            data, valid = spec
            cols.append(ColumnVector(data[i], valid[i], dtype))
    return ColumnarBatch(cols, pb.names, pb.counts[i])


def _partition_slices(pb: PartitionedBatch, num_parts: int):
    return [partition_slice(pb, i) for i in range(num_parts)]


def _range_partition_builder(orders, num_parts):
    def run(batch: ColumnarBatch, bnds):
        keys = [o.expr.eval(batch) for o in orders]
        pids = range_partition_ids(
            keys, bnds, [o.ascending for o in orders],
            [o.nulls_first for o in orders])
        return _partition_slices(partition_batch(batch, pids, num_parts),
                                 num_parts)
    return run


def _hash_partition_builder(key_exprs, num_parts):
    def run(batch: ColumnarBatch):
        keys = [e.eval(batch) for e in key_exprs]
        pids = hash_partition_ids(keys, num_parts)
        return _partition_slices(partition_batch(batch, pids, num_parts),
                                 num_parts)
    return run


def _rr_partition_builder(num_parts):
    def run(batch: ColumnarBatch):
        pids = round_robin_partition_ids(batch.capacity, num_parts)
        return _partition_slices(partition_batch(batch, pids, num_parts),
                                 num_parts)
    return run


def _hash_cut_builder(key_exprs, num_parts, capacities):
    """A held batch's cut (``_split_held``): the partitions
    ``_hash_partition_builder`` assigns, each gathered straight to its
    capacity in ``capacities`` (``cut_batch``): the same slices as that
    program's repacked, without its ``num_parts`` x capacity layout."""
    def run(batch: ColumnarBatch):
        keys = [e.eval(batch) for e in key_exprs]
        return cut_batch(batch, hash_partition_ids(keys, num_parts),
                         num_parts, capacities)
    return run


def _rr_cut_builder(num_parts, capacities):
    """``_hash_cut_builder`` for a round-robin exchange."""
    def run(batch: ColumnarBatch):
        return cut_batch(batch, round_robin_partition_ids(batch.capacity,
                                                          num_parts),
                         num_parts, capacities)
    return run


def _hash_count_builder(key_exprs, num_parts):
    """Live rows per hash partition of one batch, int32[num_parts]: the
    counts of ``_hash_partition_builder``'s slices, without the slices."""
    def run(batch: ColumnarBatch):
        keys = [e.eval(batch) for e in key_exprs]
        return partition_counts(batch, hash_partition_ids(keys, num_parts),
                                num_parts)
    return run


def _rr_counts(num_rows: int, num_parts: int) -> List[int]:
    """Rows per round-robin partition of a batch whose live rows are
    its first ``num_rows`` slots (``round_robin_partition_ids``)."""
    return [len(range(r, num_rows, num_parts)) for r in range(num_parts)]


#: exchange metric -> key of the query record's ``phases``: map batches
#: that reached their reader whole, and map batches cut into partitions
EXCHANGE_COUNTERS = {"shuffleWholeBatches": "exchange_whole_batches",
                     "shuffleSplitBatches": "exchange_split_batches"}

#: ((batch structure, partitions, leaf shapes), slice capacity) -> bytes
_SLICE_BYTES: dict = {}


def _slice_bytes(fn, batch: ColumnarBatch, rows: Sequence[int]) -> List[int]:
    """What ``write_map_output`` records for each slice the partition
    program ``fn`` cuts from ``batch`` (its device bytes, ``batch_nbytes``)
    given the rows each receives, from shapes alone: nothing is cut or
    launched. Every partition program cuts slices of the batch's
    capacity, and ``compact_for_transfer`` repacks a slice to its rows'
    capacity where that saves enough."""
    leaves, tree = jax.tree_util.tree_flatten(batch)
    sig = (tree, len(rows), tuple((getattr(x, "shape", None),
                                   getattr(x, "dtype", None))
                                  for x in leaves))
    if len(_SLICE_BYTES) > 4096:
        _SLICE_BYTES.clear()
    out = []
    for n in rows:
        if n == 0:
            out.append(0)
            continue
        cap = K.transfer_capacity(n, batch.capacity)
        nb = _SLICE_BYTES.get((sig, cap))
        if nb is None:
            piece = fn.eval_shape(batch)[0]
            if cap != batch.capacity:
                piece = jax.eval_shape(
                    lambda p, c=cap: K.slice_batch(p, 0, p.num_rows, c),
                    piece)
            nb = _SLICE_BYTES[(sig, cap)] = batch_nbytes(piece)
        out.append(nb)
    return out


class ShuffleExchangeExec(TpuExec):
    """Repartitioning through the ShuffleManager.

    ``key_exprs`` non-empty -> hash partitioning; empty + ``sort_orders``
    -> range partitioning (sample child, compute bounds, partition by
    bound search); both empty -> round-robin (or a single-partition
    concentrator when num_partitions == 1).
    """

    def __init__(self, child: TpuExec,
                 key_exprs: Sequence[Expression],
                 num_partitions: Optional[int] = None,
                 manager: Optional[ShuffleManager] = None,
                 sort_orders: Optional[Sequence] = None):
        super().__init__(child)
        self.key_exprs = list(key_exprs)
        self.sort_orders = list(sort_orders) if sort_orders else []
        if self.key_exprs and self.sort_orders:
            raise ValueError("hash keys and range orders are exclusive")
        self.num_partitions = num_partitions
        self.manager = manager
        self.shuffle_id = next_shuffle_id()
        self._written = False
        self._jit_cache = {}
        self._global_counts = None
        self._global_stats = None
        #: speculation outcome from the driver barrier: None, or
        #: {"allowed": {worker_id: (map_ids...)}} restricting which
        #: peer blocks readers may consume (first-result-wins dedup)
        self._winners = None
        self._barrier_done = False
        self._own_map_ids: List[int] = []
        #: map ids whose batch the manager holds whole (not yet cut)
        self._held_ids: List[int] = []
        self._split_lock = threading.Lock()

    def reset_for_rerun(self) -> None:
        super().reset_for_rerun()
        # fresh shuffle id: the previous run's blocks are owned by the
        # old id (and may already be cleaned up)
        self.shuffle_id = next_shuffle_id()
        self._written = False
        self._global_counts = None
        self._global_stats = None
        self._winners = None
        self._barrier_done = False
        self._own_map_ids = []
        self._held_ids = []

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    @property
    def output_partitioning(self):
        from ..plan.distribution import (HashPartitioning, RangePartitioning,
                                         SinglePartition, UnknownPartitioning)
        n = self.num_partitions or 1
        if self.sort_orders:
            return RangePartitioning(self.sort_orders, n)
        if self.key_exprs:
            return HashPartitioning(self.key_exprs, n)
        if n == 1:
            return SinglePartition()
        return UnknownPartitioning(n)

    def _effective_parts(self, ctx: ExecContext) -> int:
        return self.num_partitions or ctx.conf.get(SHUFFLE_PARTITIONS)

    def _partition_fn(self, num_parts: int, bounds=None):
        """Jitted batch -> [partition batches]. The slice-out of every
        partition lives INSIDE the jit: partitioning plus N slices is
        one XLA program per batch structure instead of hundreds of
        eager dispatches per map batch. Shared process-wide via the jit
        registry: every exchange over the same keys/orders and fan-out
        reuses one traced fn."""
        key = (num_parts, bounds is not None)
        if key not in self._jit_cache:
            from ..expr.misc import contains_eager
            eager = contains_eager(
                list(self.key_exprs)
                + [o.expr for o in self.sort_orders])
            if self.sort_orders:
                self._jit_cache[key] = _range_partition_builder(
                    self.sort_orders, num_parts) if eager else \
                    shared_fn_jit(_range_partition_builder,
                                  self.sort_orders, num_parts)
            elif self.key_exprs:
                self._jit_cache[key] = _hash_partition_builder(
                    self.key_exprs, num_parts) if eager else \
                    shared_fn_jit(_hash_partition_builder,
                                  self.key_exprs, num_parts)
            else:
                self._jit_cache[key] = shared_fn_jit(
                    _rr_partition_builder, num_parts)
        return self._jit_cache[key]

    def _holds_whole(self, ctx: ExecContext, mgr, n_parts: int) -> bool:
        """Whether the map side keeps each batch whole and cuts it only
        for a reader that asks for partitions apart: a single-process hash
        or round-robin exchange of several partitions whose manager keeps
        device batches, over keys a program can evaluate."""
        if ctx.cluster is not None or n_parts < 2 or self.sort_orders \
                or not mgr.holds_on_device(local_ok=True):
            return False
        from ..expr.misc import contains_eager
        return not contains_eager(self.key_exprs)

    def _partition_rows(self, batch: ColumnarBatch,
                        n_parts: int) -> List[int]:
        """Rows each reduce partition receives from ``batch``: one small
        program and one read for hash keys, arithmetic for round-robin."""
        if not self.key_exprs:
            return _rr_counts(read_int(batch.num_rows), n_parts)
        key = ("count", n_parts)
        if key not in self._jit_cache:
            self._jit_cache[key] = shared_fn_jit(
                _hash_count_builder, self.key_exprs, n_parts)
        counts, = read_back(self._jit_cache[key](batch))
        return [int(c) for c in counts]

    def _counter(self, ctx: ExecContext, name: str) -> Metric:
        return ctx.metrics_for(self.exec_id).setdefault(
            name, Metric(name, Metric.MODERATE))

    # --- range bounds (GpuRangePartitioner.sketch: sample to the
    # driver, sort, take quantile bounds) ---
    def _sample_rows(self, ctx: ExecContext,
                     batches: List[ColumnarBatch],
                     num_parts: int) -> List[tuple]:
        """Host-side sample row tuples of the sort keys."""
        from ..conf import RANGE_SAMPLE_SIZE
        orders = self.sort_orders
        per_part = ctx.conf.get(RANGE_SAMPLE_SIZE)
        per_batch = max(1, (num_parts * per_part)
                        // max(len(batches), 1))
        samples: List[tuple] = []  # row tuples of physical values
        for b in batches:
            n = read_int(b.num_rows)
            take = min(n, per_batch)
            if take == 0:
                continue
            with ctx.semaphore:
                keys = [o.expr.eval(b) for o in orders]
            # host copies of the first `take` live rows
            cols = []
            for kc in keys:
                vals, mask = kc.to_numpy(take)
                cols.append((vals, mask))
            for i in range(take):
                samples.append(tuple(
                    (None if not cols[k][1][i] else cols[k][0][i])
                    for k in range(len(orders))))
        return samples

    def _compute_bounds(self, ctx: ExecContext,
                        batches: List[ColumnarBatch], num_parts: int):
        """Sample the buffered child, return per-key bound Columns with
        (num_parts - 1) rows, device-resident. Under a cluster context
        the local sketch all-gathers through the driver first
        (GpuRangePartitioner.sketch sends samples to the driver), so
        every worker derives IDENTICAL bounds and range partitions stay
        globally consistent."""
        orders = self.sort_orders
        pre = (ctx.cluster.bounds_for(self.shuffle_id)
               if ctx.cluster is not None else None)
        if pre is not None:
            # stage-level retry of a REUSED range exchange: the renamed
            # blocks were cut with the previous attempt's bounds, so the
            # freshly re-executed shards must use the SAME bounds — and
            # every worker takes this shortcut consistently (skipping
            # the sample gather without deadlock), because the driver
            # only marks a position reusable after verifying every
            # survivor holds the job's record.
            bounds_rows = [tuple(r) for r in pre]
            ctx.cluster.record_bounds(self.shuffle_id, bounds_rows)
            return self._bounds_device_cols(bounds_rows)
        samples = self._sample_rows(ctx, batches, num_parts)
        if ctx.cluster is not None:
            gathered = ctx.cluster.gather(("bounds", self.shuffle_id),
                                          samples)
            samples = [t for lst in gathered if lst for t in lst]
        if not samples:
            samples = [tuple(None for _ in orders)]

        def sort_key(row):
            parts = []
            for v, o in zip(row, orders):
                null_rank = 0 if o.nulls_first else 2
                if v is None:
                    parts.append((null_rank if o.ascending else 2 - null_rank,
                                  0))
                else:
                    key = str(v) if isinstance(v, (bytes, str)) else v
                    # `-key` is not defined for str/bool/date/Decimal
                    # sample values; flip comparisons instead
                    parts.append((1, key if o.ascending
                                  else _InvertedKey(key)))
            return parts
        samples.sort(key=sort_key)
        # quantile bounds: num_parts-1 cut rows
        bounds_rows = []
        m = len(samples)
        for i in range(1, num_parts):
            bounds_rows.append(samples[min(m - 1, (i * m) // num_parts)])
        if ctx.cluster is not None:
            # remember the cut rows: a stage-level retry that reuses
            # this exchange's blocks must partition with the same bounds
            ctx.cluster.record_bounds(self.shuffle_id, bounds_rows)
        return self._bounds_device_cols(bounds_rows)

    def _bounds_device_cols(self, bounds_rows):
        orders = self.sort_orders
        # build device columns for the bounds; capacity == bound count
        # exactly (range_partition_ids treats every slot as a bound).
        # Sampled non-string values are already physical lanes (the
        # to_numpy copy is raw), so primitive bounds are built directly
        # rather than through column_from_numpy's python-value coercion.
        in_schema = self.children[0].output_schema
        bound_cols = []
        cap = len(bounds_rows)
        from ..columnar.vector import column_from_numpy
        for k, o in enumerate(orders):
            ktype = o.expr.data_type(in_schema)
            mask = np.array([r[k] is not None for r in bounds_rows],
                            dtype=bool)
            if ktype == dt.STRING:
                values = np.array([r[k] for r in bounds_rows], dtype=object)
                bound_cols.append(column_from_numpy(values, cap,
                                                    dtype=ktype, mask=mask))
            else:
                phys = np.dtype(ktype.physical)
                data = np.array([0 if r[k] is None else r[k]
                                 for r in bounds_rows], dtype=phys)
                bound_cols.append(ColumnVector(jnp.asarray(data),
                                               jnp.asarray(mask), ktype))
        return bound_cols, len(bounds_rows)

    def _write(self, ctx: ExecContext) -> None:
        """Map phase: drain the child, write all blocks. Idempotent."""
        if self._written:
            return
        self._written = True
        # per-run drain budget: the planner counts how many tree edges
        # drain this exchange (a subtree shared by the two halves of a
        # full-outer union drains twice); blocks free on the LAST drain
        self._consumers = getattr(self, "_planned_consumers", 1)
        mgr = self.manager or shuffle_manager()
        n_parts = self._effective_parts(ctx)
        mgr.register_shuffle(self.shuffle_id, n_parts)
        m = ctx.metrics_for(self.exec_id)
        part_time = m.setdefault("partitionTime",
                                 Metric("partitionTime", Metric.MODERATE,
                                        "ns"))
        write_rows = m.setdefault("shuffleWriteRows",
                                  Metric("shuffleWriteRows",
                                         Metric.ESSENTIAL))
        write_bytes = m.setdefault("shuffleBytesWritten",
                                   Metric("shuffleBytesWritten",
                                          Metric.ESSENTIAL, "B"))
        # per-attempt map-id namespace: a stage retry renames the prior
        # attempt's surviving blocks into this shuffle id, so freshly
        # re-executed shards must not collide with their map ids
        map_id = ctx.cluster.map_id_base if ctx.cluster is not None else 0
        push_route = self._push_route(ctx, mgr, n_parts)
        buddy = self._buddy_endpoint(ctx)
        bypassed_before = getattr(mgr, "bypassed_bytes", 0)
        if self.sort_orders:
            # buffer spillable, sample bounds, then partition
            from ..memory.spill import SpillableBatch, SpillPriority
            held = []
            try:
                from ..memory.retry import with_retry_no_split
                total = 0
                for batch in self.children[0].execute(ctx):
                    n = read_int(batch.num_rows)
                    if n == 0:
                        continue
                    total += n
                    held.append(with_retry_no_split(
                        lambda b=batch: SpillableBatch(
                            K.compact_for_transfer(b),
                            SpillPriority.ACTIVE_ON_DECK)))
                batches = with_retry_no_split(
                    lambda: [sb.get() for sb in held])
                # rows that fit one batch take the range split that puts
                # every row in partition 0 (any bounds above every key):
                # the sort above reads them as one, and nothing samples
                whole = ctx.cluster is None and (
                    n_parts == 1 or total <= ctx.conf.get(BATCH_SIZE_ROWS))
                if not whole:
                    bounds, n_bounds = self._compute_bounds(ctx, batches,
                                                            n_parts)
                    fn = self._partition_fn(n_parts, bounds=True)
                counter = self._counter(
                    ctx, "shuffleWholeBatches" if whole
                    else "shuffleSplitBatches")
                for batch in batches:
                    t0 = time.perf_counter_ns()

                    def write_one(batch=batch, map_id=map_id):
                        # replay-safe: block writes overwrite by
                        # (shuffle, map, reduce)
                        if whole:
                            parts = [batch]
                        else:
                            with ctx.semaphore:
                                # per-slice compaction: each slice
                                # carries the full input capacity
                                # (static worst-case skew bound) but
                                # typically holds ~1/P of the rows
                                parts = [K.compact_for_transfer(p)
                                         for p in fn(batch, bounds)]
                        return mgr.write_map_output(
                            self.shuffle_id, map_id, parts,
                            local_ok=ctx.cluster is None)
                    write_bytes.add(with_retry_no_split(write_one))
                    counter.add(1)
                    part_time.add(time.perf_counter_ns() - t0)
                    write_rows.add(read_int(batch.num_rows))
                    if push_route is not None:
                        mgr.push_map_output(self.shuffle_id, map_id,
                                            push_route,
                                            who=self._push_who(ctx))
                    if buddy is not None:
                        mgr.replicate_map_output(self.shuffle_id,
                                                 map_id, buddy,
                                                 who=self._push_who(ctx))
                    self._own_map_ids.append(map_id)
                    map_id += 1
            finally:
                for sb in held:
                    sb.close()
            self._finish_write(ctx, mgr, push_route, bypassed_before,
                               buddy=buddy)
            return
        self._own_map_ids.extend(
            self._run_map_loop(ctx, mgr, n_parts, map_id,
                               self.children[0], push_route=push_route,
                               buddy=buddy))
        self._finish_write(ctx, mgr, push_route, bypassed_before,
                           buddy=buddy)

    def _push_route(self, ctx: ExecContext, mgr,
                    n_parts: int) -> Optional[dict]:
        """reduce partition -> owning endpoint, when push-based shuffle
        applies to this exchange: cluster mode, the manager's push path
        on, and the planner's ``_push_ok`` tag present (overrides tags
        every planned shuffle exchange; hand-built plans opt in
        explicitly). Routing is BEST-EFFORT — AQE may later coalesce or
        skew-split partitions across different readers, in which case a
        mispredicted push just idles in a segment nobody reads and the
        pull path serves the real reader."""
        if (ctx.cluster is None
                or not getattr(mgr, "push_enabled", False)
                or not getattr(self, "_push_ok", False)):
            return None
        try:
            return ctx.cluster.partition_owners(n_parts)
        except Exception:
            return None  # no assignment info: pull covers everything

    @staticmethod
    def _push_who(ctx: ExecContext) -> str:
        """Stable sender label for the ``push.send`` fault site, so a
        chaos plan can address exactly one worker's push path (ports
        are random; worker ids are not)."""
        return (f"w={ctx.cluster.worker_id}"
                if ctx.cluster is not None else "w=local")

    def _buddy_endpoint(self, ctx: ExecContext) -> Optional[str]:
        """Replication target for this worker's completed map output
        under k=2 shuffle durability: the next peer in ring order.
        None when replication is off, local mode, or there is no
        distinct peer to hold the copy."""
        from ..conf import SHUFFLE_REPLICATION_FACTOR
        if (ctx.cluster is None
                or ctx.conf.get(SHUFFLE_REPLICATION_FACTOR) < 2):
            return None
        peers = ctx.cluster.peers
        if len(peers) < 2:
            return None
        return peers[(ctx.cluster.worker_id + 1) % len(peers)]

    @staticmethod
    def _replica_targets(ctx: ExecContext) -> Optional[dict]:
        """origin endpoint -> its ring buddy, handed to the fetch path
        as a last-resort fallback. Always populated in multi-worker
        clusters — with replication off (or an incomplete replica set)
        the buddy answers "no coverage" and the reader falls back to
        the normal stage-retry path, so the only cost is one extra
        round-trip on an already-failing fetch."""
        if ctx.cluster is None:
            return None
        peers = ctx.cluster.peers
        n = len(peers)
        if n < 2:
            return None
        return {peers[i]: peers[(i + 1) % n] for i in range(n)}

    def _finish_write(self, ctx: ExecContext, mgr, push_route,
                      bypassed_before: int, buddy=None) -> None:
        """Map phase epilogue: drain in-flight pushes BEFORE the stage
        barrier can release readers, and report bytes that took the
        zero-copy local channel. With a replication buddy, the replica
        manifest publishes AFTER the drain (so it only ever vouches for
        blocks that actually landed) and BEFORE the barrier report (so
        any map id a reader can learn about is covered)."""
        if push_route is not None or buddy is not None:
            mgr.drain_pushes()
        if buddy is not None:
            mgr.publish_replica_manifest(self.shuffle_id, buddy)
        bypassed = getattr(mgr, "bypassed_bytes", 0) - bypassed_before
        if bypassed > 0:
            m = ctx.metrics_for(self.exec_id)
            m.setdefault("shuffleBytesBypassed",
                         Metric("shuffleBytesBypassed",
                                Metric.ESSENTIAL, "B")).add(bypassed)

    def record_mesh_exchange(self, ctx: ExecContext, nbytes: int,
                             resident: bool) -> None:
        """Mesh-lane byte accounting for this exchange's stage boundary.

        On the SPMD stage path nothing is serialized: the child stage's
        output is handed to the consumer program device-resident, so
        every boundary byte lands in ``shuffleBytesBypassed`` (it
        bypassed the serialized shuffle write path this class's
        ``_write`` implements — ``shuffleBytesWritten`` stays 0 on mesh
        runs, which is exactly the "device-resident stages dominate"
        signal the bench gate checks). Bytes that additionally rode an
        in-program collective (a true repartition: non-resident hash /
        range / round-robin all_to_all, single-partition all_gather)
        are ALSO counted as ``shuffleBytesWire`` — ICI traffic, not a
        write. A resident exchange contributes bypassed bytes only.
        """
        if nbytes <= 0:
            return
        m = ctx.metrics_for(self.exec_id)
        m.setdefault("shuffleBytesBypassed",
                     Metric("shuffleBytesBypassed",
                            Metric.ESSENTIAL, "B")).add(nbytes)
        if not resident:
            m.setdefault("shuffleBytesWire",
                         Metric("shuffleBytesWire",
                                Metric.ESSENTIAL, "B")).add(nbytes)

    def _run_map_loop(self, ctx: ExecContext, mgr, n_parts: int,
                      map_id: int, child: TpuExec,
                      push_route: Optional[dict] = None,
                      buddy: Optional[str] = None) -> List[int]:
        """Drain ``child``, partition every batch, write blocks under
        ascending map ids from ``map_id``; returns the ids written.
        Shared by the normal (non-range) map phase and speculative
        re-execution of a straggler's shard, which runs a re-sharded
        clone of the stage subtree under a disjoint map-id namespace."""
        m = ctx.metrics_for(self.exec_id)
        part_time = m.setdefault("partitionTime",
                                 Metric("partitionTime", Metric.MODERATE,
                                        "ns"))
        write_rows = m.setdefault("shuffleWriteRows",
                                  Metric("shuffleWriteRows",
                                         Metric.ESSENTIAL))
        write_bytes = m.setdefault("shuffleBytesWritten",
                                   Metric("shuffleBytesWritten",
                                          Metric.ESSENTIAL, "B"))
        from ..memory.retry import with_retry_no_split
        hold = self._holds_whole(ctx, mgr, n_parts)
        # one local partition: the compacted batch is that partition
        one = ctx.cluster is None and n_parts == 1
        whole_m = self._counter(ctx, "shuffleWholeBatches")
        split_m = self._counter(ctx, "shuffleSplitBatches")
        written: List[int] = []
        for batch in child.execute(ctx):
            if read_int(batch.num_rows) == 0:
                continue
            t0 = time.perf_counter_ns()

            def write_one(batch=batch, map_id=map_id):
                # partition + block write re-runs cleanly on RetryOOM:
                # blocks are keyed (shuffle, map, reduce) so a replay
                # overwrites, never duplicates
                with ctx.semaphore:
                    b = K.compact_for_transfer(batch)
                    if hold:
                        rows = self._partition_rows(b, n_parts)
                    elif not one:
                        fn = self._partition_fn(n_parts)
                        parts = [K.compact_for_transfer(p)
                                 for p in fn(b)]
                if hold:
                    wrote = mgr.hold_map_output(
                        self.shuffle_id, map_id, b, rows,
                        _slice_bytes(self._partition_fn(n_parts), b, rows),
                        local_ok=True)
                else:
                    wrote = mgr.write_map_output(
                        self.shuffle_id, map_id, [b] if one else parts,
                        local_ok=ctx.cluster is None)
                return read_int(b.num_rows), wrote
            rows_written, bytes_written = with_retry_no_split(write_one)
            part_time.add(time.perf_counter_ns() - t0)
            write_rows.add(rows_written)
            write_bytes.add(bytes_written)
            if hold:
                self._held_ids.append(map_id)
            else:
                (whole_m if one else split_m).add(1)
            if push_route is not None:
                # eager push at map completion: this map's blocks start
                # uploading to their reducers while the next batch is
                # still computing
                mgr.push_map_output(self.shuffle_id, map_id, push_route,
                                    who=self._push_who(ctx))
            if buddy is not None:
                mgr.replicate_map_output(self.shuffle_id, map_id, buddy,
                                         who=self._push_who(ctx))
            written.append(map_id)
            map_id += 1
        return written

    def run_speculative_maps(self, ctx: ExecContext,
                             map_id_base: int) -> List[int]:
        """Speculative map execution entry: run THIS exchange's map
        phase under an explicit map-id namespace, bypassing the
        ``_written`` idempotence latch and the barrier. The cluster's
        speculate callback invokes it on a clone of the stage subtree
        re-sharded to the straggler's logical ids, with ``shuffle_id``
        pointed at the live shuffle — blocks land in this worker's
        store and win or lose at the driver's first-result-wins
        commit."""
        if self.sort_orders:
            raise RuntimeError(
                "range exchanges are not speculation-eligible")
        mgr = self.manager or shuffle_manager()
        n_parts = self._effective_parts(ctx)
        mgr.register_shuffle(self.shuffle_id, n_parts)
        push_route = self._push_route(ctx, mgr, n_parts)
        buddy = self._buddy_endpoint(ctx)
        written = self._run_map_loop(ctx, mgr, n_parts, map_id_base,
                                     self.children[0],
                                     push_route=push_route, buddy=buddy)
        if push_route is not None or buddy is not None:
            # speculative pushes drain before the result reports: the
            # winners filter applies at segment-index granularity, so a
            # losing worker's pushed entries are simply never consumed
            mgr.drain_pushes()
        if buddy is not None:
            # re-publish: the manifest must cover the speculative maps
            # before their ids can reach the driver's commit
            mgr.publish_replica_manifest(self.shuffle_id, buddy)
        return written

    def _release(self, mgr) -> None:
        """One consumer finished a full drain. Shared subtrees (the two
        halves of a full-outer union both reference this instance) mean
        multiple drains per run; only the last one frees the blocks —
        an eager unregister would break the sibling's re-read (the
        round-4 FULL OUTER JOIN + AQE KeyError)."""
        self._consumers = getattr(self, "_consumers", 1) - 1
        if self._consumers <= 0:
            mgr.unregister_shuffle(self.shuffle_id)

    # kept for existing callers/tests
    def write(self, ctx: ExecContext) -> None:
        self._write(ctx)

    def read_partition(self, ctx: ExecContext,
                       reduce_id: int) -> Iterator[ColumnarBatch]:
        mgr = self.manager or shuffle_manager()
        self._write(ctx)
        self._split_held(ctx, mgr)
        yield from mgr.read_partition(self.shuffle_id, reduce_id)

    def _cut_fn(self, num_parts: int, capacities: tuple):
        """Jitted held batch -> its slices at ``capacities``
        (``_hash_cut_builder``), one program per capacities."""
        key = ("cut", num_parts, capacities)
        if key not in self._jit_cache:
            self._jit_cache[key] = shared_fn_jit(
                _hash_cut_builder, self.key_exprs, num_parts, capacities) \
                if self.key_exprs else shared_fn_jit(
                    _rr_cut_builder, num_parts, capacities)
        return self._jit_cache[key]

    def _split_held(self, ctx: ExecContext, mgr) -> None:
        """Cut every held map batch into the slices the map side writes
        when it does not hold, before any read of partitions apart: the
        rows each partition receives were recorded when the batch was
        held, so one program gathers each slice straight to the capacity
        ``compact_for_transfer`` would repack it to (a batch with a list
        column takes the partition program and the repacks). Their rows
        and bytes are the ones recorded."""
        from ..columnar.nested import ListColumn
        with self._split_lock:
            held, self._held_ids = self._held_ids, []
            if not held:
                return
            from ..memory.retry import with_retry_no_split
            n_parts = mgr.num_partitions(self.shuffle_id)
            fn = self._partition_fn(n_parts)
            detail = mgr.map_output_statistics(
                self.shuffle_id, map_ids=set(held)).detail
            split_m = self._counter(ctx, "shuffleSplitBatches")
            metrics = ctx.metrics_for(self.exec_id)
            for map_id in held:
                rows = [detail.get((map_id, r), (0, 0))[0]
                        for r in range(n_parts)]

                def cut(batch, rows=rows):
                    if any(isinstance(c, ListColumn) for c in batch.columns):
                        count_compaction(metrics, False)
                        with ctx.semaphore:
                            return [K.compact_for_transfer(p, num_rows=n)
                                    if n else None
                                    for p, n in zip(fn(batch), rows)]
                    count_compaction(metrics, cut_carries(batch, n_parts))
                    caps = tuple(K.transfer_capacity(n, batch.capacity)
                                 if n else 0 for n in rows)
                    with ctx.semaphore:
                        return self._cut_fn(n_parts, caps)(batch)
                with_retry_no_split(lambda m=map_id, c=cut: mgr.split_held(
                    self.shuffle_id, m, c))
                split_m.add(1)

    # --- AQE surface (GpuCustomShuffleReaderExec analogue) ---
    def _cluster_barrier(self, ctx: ExecContext):
        """Speculation-aware driver barrier, once per run: reports this
        worker's own map ids and exact per-(map, reduce) sizes, may run
        speculative work for a straggler inside the call, and caches
        the winners verdict that filters every subsequent read and
        stats gather (first-result-wins dedup). With speculation off
        the driver keeps its plain all-or-nothing barrier and the
        verdict is None (no filtering)."""
        if self._barrier_done:
            return self._winners
        mgr = self.manager or shuffle_manager()
        detail = mgr.map_output_statistics(
            self.shuffle_id, map_ids=set(self._own_map_ids)).detail
        def leaf_stage(node) -> bool:
            return all(not isinstance(c, ShuffleExchangeExec)
                       and leaf_stage(c) for c in node.children)

        # only leaf map stages are speculation-eligible: a re-run of a
        # subtree with its own exchange would need a nested barrier,
        # and range exchanges gather bounds cooperatively
        self._winners = ctx.cluster.barrier(
            self.shuffle_id, getattr(self, "_cluster_pos", -1),
            detail=detail,
            spec_ok=not self.sort_orders and leaf_stage(self))
        self._barrier_done = True
        return self._winners

    def _allowed_by_endpoint(self, ctx: ExecContext):
        """Winners verdict -> per-peer-endpoint allowed map-id sets for
        the fetch filter. None when no speculation verdict exists (all
        blocks are authoritative)."""
        winners = self._winners
        if not winners or winners.get("allowed") is None:
            return None
        peers = ctx.cluster.peers
        allowed = winners["allowed"]
        return {peers[w]: set(allowed.get(w, ()))
                for w in range(len(peers))}

    def materialized_stats(self, ctx: ExecContext):
        """Write the map side (idempotent) and return
        ``(rows, bytes)`` lists per reduce partition — the
        MapOutputStatistics AQE decisions read.

        Cluster mode: a speculation-aware barrier resolves which maps
        won, then each worker's WINNING local stats all-gather through
        the driver and sum, so every worker computes IDENTICAL global
        statistics (the fix for round-2's divergent-coalescing bug —
        decisions must be a pure function of global state, never of
        local map outputs)."""
        mgr = self.manager or shuffle_manager()
        self._write(ctx)
        if ctx.cluster is None:
            st = mgr.map_output_statistics(self.shuffle_id)
            return st.rows_by_reduce, st.bytes_by_reduce
        if self._global_stats is not None:
            return self._global_stats
        winners = self._cluster_barrier(ctx)
        mine: Optional[set] = set(self._own_map_ids)
        if winners and winners.get("allowed") is not None:
            mine = set(winners["allowed"].get(
                ctx.cluster.worker_id, ()))
        st = mgr.map_output_statistics(self.shuffle_id, map_ids=mine)
        gathered = ctx.cluster.gather(
            ("aqe_stats", self.shuffle_id),
            (st.rows_by_reduce, st.bytes_by_reduce))
        n = st.num_partitions
        rows = [sum(g[0][i] for g in gathered if g) for i in range(n)]
        nbytes = [sum(g[1][i] for g in gathered if g) for i in range(n)]
        self._global_stats = (rows, nbytes)
        self._global_counts = rows
        return self._global_stats

    def materialized_row_counts(self, ctx: ExecContext) -> List[int]:
        """Rows per reduce partition (the byte-blind legacy accessor;
        kept for existing callers — materialized_stats is the AQE
        surface)."""
        return self.materialized_stats(ctx)[0]

    @staticmethod
    def coalesce_groups(counts: List[int], min_rows: int,
                        byte_counts: Optional[List[int]] = None,
                        target_bytes: int = 0) -> List[List[int]]:
        """Greedy adjacent grouping: each group closes on reaching
        min_rows OR, when measured byte sizes are supplied,
        target_bytes — whichever lands first (the last group may reach
        neither). CoalesceShufflePartitions' strategy generalized from
        rows to measured bytes."""
        groups: List[List[int]] = []
        cur: List[int] = []
        acc = 0
        acc_b = 0
        for i, c in enumerate(counts):
            cur.append(i)
            acc += c
            if byte_counts is not None and i < len(byte_counts):
                acc_b += byte_counts[i]
            if acc >= min_rows or (target_bytes > 0
                                   and byte_counts is not None
                                   and acc_b >= target_bytes):
                groups.append(cur)
                cur, acc, acc_b = [], 0, 0
        if cur:
            if groups:
                groups[-1].extend(cur)
            else:
                groups.append(cur)
        return groups

    def _fetch_metrics_cb(self, ctx: ExecContext):
        """Per-source read attribution: segment (pushed + consolidated
        locally), local (self-endpoint short-circuit, no socket), or
        remote (pulled over the wire)."""
        m = ctx.metrics_for(self.exec_id)
        counters = {
            kind: m.setdefault(name, Metric(name, Metric.MODERATE))
            for kind, name in (("segment", "shuffleSegmentBlocksRead"),
                               ("local", "shuffleLocalBlocksRead"),
                               ("remote", "shuffleRemoteBlocksRead"))}
        fetched = m.setdefault("shuffleBytesFetched",
                               Metric("shuffleBytesFetched",
                                      Metric.MODERATE, "B"))

        def on_block(kind: str, nbytes: int) -> None:
            counters[kind].add(1)
            if kind == "remote":
                fetched.add(nbytes)
        return on_block

    def _maybe_prefetch(self, ctx: ExecContext, factory, name: str):
        """Read-side pipelining (RapidsShuffleIterator fetch-ahead
        role): pull one reduce partition's block stream — fetch,
        checksum verify, deserialize — on a background producer so it
        overlaps the consumer's reduce compute. Gated on the conf AND
        the planner's ``_pipeline_ok`` safety tag; off = the plain
        synchronous generator. The producer for partition i starts only
        when the consumer requests partition i, so ``ctx.partition_id``
        advances strictly behind the consumer."""
        from .pipeline import pipeline_enabled, prefetch_batches
        if not pipeline_enabled(ctx, self):
            return factory()
        mgr = self.manager or shuffle_manager()
        # locality bypass may hand LIVE manager-owned batches through
        # this stream — don't re-wrap them as spillables (double
        # memory accounting; a queue discard would close a batch the
        # manager still serves to replays)
        stage = not (ctx.cluster is None
                     and getattr(mgr, "push_enabled", False)
                     and getattr(mgr, "local_bypass", False))
        return prefetch_batches(ctx, self, factory, name=name, stage=stage)

    def execute_partition_groups(self, ctx: ExecContext,
                                 groups: List[List[int]],
                                 map_mod: Optional[dict] = None):
        """One iterator per partition GROUP (a disjoint union of hash
        partitions keeps keys clustered, so group-wise consumers stay
        correct). ``map_mod``: {group_index: (s, S)} restricts that
        group's reads to map outputs with map_id % S == s — the skew
        split primitive (GpuCustomShuffleReaderExec's skewed partition
        specs slice a reduce partition by map ranges the same way).

        Cluster mode: ``groups`` must be identical on every worker (a
        pure function of the gathered global stats); this worker then
        streams only its contiguous block of GROUPS, fetching each
        partition from all peers."""
        mgr = self.manager or shuffle_manager()
        self._write(ctx)
        m = ctx.metrics_for(self.exec_id)
        m.setdefault("adaptiveCoalescedPartitions",
                     Metric("adaptiveCoalescedPartitions",
                            Metric.MODERATE)).add(
            max(mgr.num_partitions(self.shuffle_id) - len(groups), 0))
        if ctx.cluster is not None:
            from ..parallel.transport import fetch_all_partitions
            self._cluster_barrier(ctx)
            allowed = self._allowed_by_endpoint(ctx)
            peers = ctx.cluster.peers
            resolver = ctx.cluster.resolve_endpoint
            dsid = getattr(self, "_downstream_sid", None)
            on_block = self._fetch_metrics_cb(ctx)

            def remote_group(gi, g):
                mm = (map_mod or {}).get(gi)
                for reduce_id in g:
                    ctx.partition_id = reduce_id
                    yield from fetch_all_partitions(
                        peers, self.shuffle_id, reduce_id, map_mod=mm,
                        endpoint_resolver=resolver, allowed=allowed,
                        manager=mgr, metrics_cb=on_block,
                        replicas=self._replica_targets(ctx))
            for gi in ctx.cluster.assigned(len(groups), dsid):
                yield self._maybe_prefetch(
                    ctx, lambda _gi=gi: remote_group(_gi, groups[_gi]),
                    f"shuffle-g{gi}")
            return

        n_parts = mgr.num_partitions(self.shuffle_id)
        if self._held_ids and not map_mod and len(groups) == 1 \
                and sorted(groups[0]) == list(range(n_parts)):
            # one group of every partition: the held map batches, in map
            # order, are its rows in the order the slices' concatenation
            # gives them within each key
            self._counter(ctx, "shuffleWholeBatches").add(
                len(self._held_ids))

            def read_whole():
                ctx.partition_id = groups[0][0]
                yield from mgr.read_partition(self.shuffle_id, WHOLE)
            try:
                yield self._maybe_prefetch(ctx, read_whole, "shuffle-g0")
            finally:
                self._release(mgr)
            return
        self._split_held(ctx, mgr)

        def read_group(gi, g):
            mm = (map_mod or {}).get(gi)
            for reduce_id in g:
                ctx.partition_id = reduce_id
                yield from mgr.read_partition(self.shuffle_id,
                                              reduce_id, map_mod=mm)
        try:
            for gi, g in enumerate(groups):
                yield self._maybe_prefetch(
                    ctx, lambda _gi=gi, _g=g: read_group(_gi, _g),
                    f"shuffle-g{gi}")
        finally:
            self._release(mgr)

    def execute_partitioned(self, ctx: ExecContext):
        """One iterator per reduce partition, in partition order.
        AQE coalescing is CONSUMER-driven (execute_partition_groups):
        a consumer with two partitioned inputs must apply the SAME
        grouping to both, so the exchange never groups on its own.

        Under a cluster context (parallel/cluster.py), the map side
        writes LOCAL blocks, a driver barrier makes every worker's maps
        visible, and only this worker's contiguous block of reduce
        partitions streams back — each partition fetched from ALL peers
        over the shuffle transport (RapidsShuffleIterator role)."""
        mgr = self.manager or shuffle_manager()
        self._write(ctx)
        n_parts = mgr.num_partitions(self.shuffle_id)
        if ctx.cluster is not None:
            from ..parallel.transport import fetch_all_partitions
            self._cluster_barrier(ctx)
            allowed = self._allowed_by_endpoint(ctx)
            peers = ctx.cluster.peers
            resolver = ctx.cluster.resolve_endpoint
            dsid = getattr(self, "_downstream_sid", None)
            on_block = self._fetch_metrics_cb(ctx)

            def remote_read(reduce_id):
                ctx.partition_id = reduce_id
                yield from fetch_all_partitions(
                    peers, self.shuffle_id, reduce_id,
                    endpoint_resolver=resolver, allowed=allowed,
                    manager=mgr, metrics_cb=on_block,
                    replicas=self._replica_targets(ctx))
            for reduce_id in ctx.cluster.assigned(n_parts, dsid):
                yield self._maybe_prefetch(
                    ctx, lambda rid=reduce_id: remote_read(rid),
                    f"shuffle-p{reduce_id}")
            # no unregister here: PEERS fetch this worker's blocks until
            # the whole job completes — the driver's post-job reset (or
            # failure-path reset) frees them (cluster.py _run_once)
            return

        self._split_held(ctx, mgr)

        def local_read(reduce_id):
            ctx.partition_id = reduce_id
            yield from mgr.read_partition(self.shuffle_id, reduce_id)
        try:
            for reduce_id in range(n_parts):
                yield self._maybe_prefetch(
                    ctx, lambda rid=reduce_id: local_read(rid),
                    f"shuffle-p{reduce_id}")
        finally:
            self._release(mgr)

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        """Single-stream execution: write all map outputs, then stream
        partitions in order (partition boundaries preserved for
        downstream partition-wise operators)."""
        for part in self.execute_partitioned(ctx):
            yield from part

    def node_description(self) -> str:
        if self.sort_orders:
            keys = "range: " + ", ".join(repr(o.expr)
                                         for o in self.sort_orders)
        else:
            keys = ", ".join(repr(e) for e in self.key_exprs) or "round-robin"
        n = self.num_partitions or "conf"
        return f"ShuffleExchange[{keys}, parts={n}]"


class _InvertedKey:
    """Order-reversing wrapper for any comparable host sample value
    (bool/date/Decimal have no unary minus; numpy bools raise on it)."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, other):
        return other.v < self.v

    def __eq__(self, other):
        return self.v == other.v


class BroadcastExchangeExec(TpuExec):
    """Materialize the child into one batch replicated to every consumer
    (GpuBroadcastExchangeExec.scala:352 doExecuteBroadcast:467). In
    single-process execution this is a concat; under a mesh it lowers to
    an all_gather (parallel/shuffle.py all_gather_batch)."""

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._materialized: Optional[ColumnarBatch] = None

    def reset_for_rerun(self) -> None:
        super().reset_for_rerun()
        self._materialized = None

    @property
    def output_schema(self) -> Schema:
        return self.children[0].output_schema

    @property
    def output_partitioning(self):
        from ..plan.distribution import BroadcastPartitioning
        return BroadcastPartitioning()

    def materialize(self, ctx: ExecContext) -> Optional[ColumnarBatch]:
        if self._materialized is None:
            m = ctx.metrics_for(self.exec_id)
            bt = m.setdefault("broadcastTime",
                              Metric("broadcastTime", Metric.MODERATE, "ns"))
            from .pipeline import pipeline_enabled, prefetch_batches
            if pipeline_enabled(ctx, self):
                # drain the child through a background producer: decode
                # and upload of batch N+1 overlap the consumer's
                # accumulation of batch N
                stream = prefetch_batches(
                    ctx, self, lambda: self.children[0].execute(ctx),
                    name="broadcast")
            else:
                stream = self.children[0].execute(ctx)
            with NvtxTimer(bt, "broadcast.build"):
                batches = [b for b in stream
                           if read_int(b.num_rows) > 0]
                if not batches:
                    return None
                total = sum(read_int(b.num_rows) for b in batches)
                with ctx.semaphore:
                    self._materialized = (
                        batches[0] if len(batches) == 1
                        else K.concat_batches(batches,
                                              choose_capacity(total)))
        return self._materialized

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        out = self.materialize(ctx)
        if out is not None:
            yield out

    def start_sources(self, ctx: ExecContext) -> List[str]:
        """The producer ``materialize`` drains, started now, where the
        build side is streaming operators over one source (a filtered,
        projected dimension): scan and programs run on that thread
        beside the join's other side. Any other build side (an
        aggregate, a join, an exchange beneath) is only handed the
        call: it runs, as ever, while ``materialize`` drains it, so no
        two operator subtrees of a query execute side by side."""
        from .pipeline import pipeline_enabled, start_early
        if self._materialized is not None:
            return []
        source = self.children[0]
        while source._streams_child:
            source = source.children[0]
        if source.children or not pipeline_enabled(ctx, self):
            return self.children[0].start_sources(ctx)
        return start_early(ctx, self,
                           lambda: self.children[0].execute(ctx),
                           name="broadcast")

    def node_description(self) -> str:
        return "BroadcastExchange"
