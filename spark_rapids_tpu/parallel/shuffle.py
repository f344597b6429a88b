"""SPMD shuffle: hash-partition exchange as an ICI all-to-all.

The reference's shuffle is p2p-RPC-shaped: a catalog of device-resident
blocks served over UCX ActiveMessages with bounce buffers
(RapidsShuffleClient.scala:169, UCX.scala:104-115). A TPU pod's ICI is
SPMD-program-shaped, so shuffle is reformulated (SURVEY §7 hard-part #5)
as a collective: every shard packs its rows into a dense
``(num_shards, slot)`` layout by destination (partition.py), one
``lax.all_to_all`` swaps the blocks, and each shard flattens what it
received. XLA schedules the transfer over ICI links; no host round-trip,
no serialization — the columnar buffers themselves are the wire format
(strings travel as fixed-width byte lanes).

Sharded batches cross the shard_map boundary in **stacked** form: every
leaf gains a leading ``num_shards`` dim (``stack_shards``), the mesh
sharding splits that dim, and each shard squeezes its slice back to a
plain ColumnarBatch. This keeps ragged string buffers and the scalar
``num_rows`` well-defined per shard — a plain row-sharding of a string
column's (offsets, chars) pair would not be meaningful.

Not every hash exchange needs the collective at all: when the child's
``output_partitioning`` is already HashPartitioning on the same expr
sequence, rows are on their target shard and the mesh lowering skips
``shuffle_exchange`` entirely (the MESH face of the push-shuffle v2
locality bypass — ``plan/mesh_executor.py:_hash_colocated``, the
``MeshColocationBypass`` event, docs/SHUFFLE.md). The placement
contract that makes this sound: every exchange routes with
``pmod(murmur3(keys), num_shards)`` against the mesh size, so identical
key exprs imply identical placement.

``distributed_aggregate`` is the flagship distributed pipeline: local
partial aggregation, key-hash all-to-all of the *partial states* (far
smaller than raw rows — same motivation as the reference's partial-then-
merge split, GpuAggregateExec.scala:711), then a final local merge. Key
disjointness after the exchange makes shard-local merges globally correct.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..columnar.vector import ColumnVector, ColumnarBatch, StringColumn
from ..jit_registry import named_jit
from .mesh import DATA_AXIS
from .partition import (PartitionedBatch, flatten_partitions,
                        hash_partition_ids, partition_batch,
                        string_from_padded)


def stack_shards(batches: Sequence[ColumnarBatch]):
    """Stack per-shard batches into one pytree with leading shard dim.

    All shards must share schema and capacities (pad to a common capacity
    bucket first). The result is placed with ``P("data")`` on the leading
    dim so each mesh shard holds exactly its own slice.
    """
    norm = [ColumnarBatch(b.columns, b.names,
                          jnp.asarray(b.num_rows, jnp.int32))
            for b in batches]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *norm)


def unstack_shards(stacked) -> List[ColumnarBatch]:
    """Host-side inverse of ``stack_shards``."""
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], stacked)
            for i in range(n)]


def _squeeze_shard(stacked) -> ColumnarBatch:
    """Inside shard_map: drop the leading (now length-1) shard dim."""
    return jax.tree_util.tree_map(lambda x: x[0], stacked)


def _expand_shard(batch: ColumnarBatch):
    return jax.tree_util.tree_map(lambda x: x[None], batch)


def all_to_all_partitions(pb: PartitionedBatch,
                          axis: str = DATA_AXIS) -> PartitionedBatch:
    """Exchange partition blocks across the mesh axis (inside shard_map).

    Block p on shard s is sent to shard p; afterwards block p on shard s
    holds what shard p sent to s. Counts ride along so receivers know the
    live prefix of each block.
    """
    def x2x(a):
        return lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    cols = jax.tree_util.tree_map(x2x, pb.columns)
    counts = x2x(pb.counts)
    return PartitionedBatch(cols, pb.names, pb.dtypes, counts,
                            pb.slot_capacity)


def shuffle_exchange(batch: ColumnarBatch, key_names: Sequence[str],
                     num_shards: int,
                     slot_capacity: Optional[int] = None,
                     axis: str = DATA_AXIS) -> ColumnarBatch:
    """One shard's view of the shuffle: partition, all_to_all, flatten.

    Call inside ``shard_map``. Output capacity is
    ``num_shards * slot_capacity`` with rows compacted to a live prefix.
    """
    key_cols = [batch.column(n) for n in key_names]
    pids = hash_partition_ids(key_cols, num_shards)
    pb = partition_batch(batch, pids, num_shards, slot_capacity)
    recv = all_to_all_partitions(pb, axis)
    return flatten_partitions(recv)


def all_gather_batch(batch: ColumnarBatch, num_shards: int,
                     axis: str = DATA_AXIS) -> ColumnarBatch:
    """Gather every shard's live rows into one compacted batch.

    Inside shard_map. The broadcast-join build-side primitive: per-shard
    capacity C becomes one batch of capacity num_shards*C (the analogue of
    GpuBroadcastExchangeExec's host-collected broadcast batch,
    GpuBroadcastExchangeExec.scala:352 — here it stays on device and
    rides ICI).
    """
    cap = batch.capacity
    n = num_shards
    counts = lax.all_gather(jnp.asarray(batch.num_rows, jnp.int32), axis)
    pos = jnp.arange(n * cap, dtype=jnp.int32)
    src, within = pos // cap, pos % cap
    slot_valid = within < jnp.take(counts, src)
    order = jnp.argsort(~slot_valid, stable=True).astype(jnp.int32)
    keep = jnp.take(slot_valid, order)
    total = jnp.sum(counts).astype(jnp.int32)

    def ag(a):
        return lax.all_gather(a, axis, axis=0, tiled=True)

    cols = []
    for c in batch.columns:
        if isinstance(c, StringColumn):
            padded = jnp.take(ag(c.padded()), order, axis=0)
            lens = jnp.where(keep, jnp.take(ag(c.lengths()), order), 0)
            valid = keep & jnp.take(ag(c.validity), order)
            cols.append(string_from_padded(padded, lens, valid,
                                           char_capacity=n * c.char_capacity))
        else:
            from ..columnar.decimal128 import Decimal128Column
            valid = keep & jnp.take(ag(c.validity), order)
            if isinstance(c, Decimal128Column):
                hi = jnp.take(ag(c.hi), order)
                lo = jnp.take(ag(c.lo), order)
                cols.append(Decimal128Column(
                    jnp.where(valid, hi, jnp.zeros((), jnp.int64)),
                    jnp.where(valid, lo, jnp.zeros((), jnp.uint64)),
                    valid, c.dtype))
                continue
            data = jnp.take(ag(c.data), order)
            cols.append(ColumnVector(
                jnp.where(valid, data, jnp.zeros((), data.dtype)),
                valid, c.dtype))
    return ColumnarBatch(cols, batch.names, total)


def distributed_aggregate(agg_exec, mesh: Mesh,
                          slot_capacity: Optional[int] = None):
    """Build the jitted SPMD aggregate step for a HashAggregateExec.

    Returns ``step(stacked_batches) -> stacked result`` compiled over
    ``mesh``: each shard partial-aggregates its local rows, partial
    states are exchanged by key hash, and each shard merge-finalizes its
    disjoint key range. Unstacking and concatenating the result shards
    yields the global aggregate.
    """
    n = mesh.shape[DATA_AXIS]
    key_names = agg_exec._key_names

    def shard_step(stacked):
        batch = _squeeze_shard(stacked)
        partial_states = agg_exec._update(batch, jnp.int64(0))
        if not key_names:
            # Global aggregate: every shard's single partial row is
            # gathered everywhere; shard 0 reports the merged result.
            merged = all_gather_batch(partial_states, n)
            out = agg_exec._merge_finalize(merged)
            keep = lax.axis_index(DATA_AXIS) == 0
            out = ColumnarBatch(
                out.columns, out.names,
                jnp.where(keep, out.num_rows, 0).astype(jnp.int32))
        else:
            exchanged = shuffle_exchange(partial_states, key_names, n,
                                         slot_capacity, DATA_AXIS)
            out = agg_exec._merge_finalize(exchanged)
        return _expand_shard(out)

    return named_jit(
        jax.shard_map(shard_step, mesh=mesh,
                      in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
                      check_vma=False), "distributed_aggregate")
