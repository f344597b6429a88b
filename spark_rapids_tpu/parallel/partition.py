"""On-device partitioning of columnar batches.

Rebuild of the reference's GPU partitioning layer (GpuPartitioning.scala
``sliceInternalOnGpuAndClose:63``, GpuHashPartitioningBase.scala:64,
GpuRoundRobinPartitioning.scala): rows are assigned a destination
partition on device, then sliced into per-partition sub-batches. The
static-shape formulation packs every partition into a dense
``(num_parts, slot_capacity)`` layout — exactly the shape
``lax.all_to_all`` wants — with per-partition row counts carried
alongside. A partition that would overflow ``slot_capacity`` reports its
true count so the host can split-and-retry, mirroring the reference's
SplitAndRetryOOM contract.

Spark semantics preserved: hash partitioning is
``pmod(murmur3(keys, seed=42), num_parts)`` so a row lands on the same
partition id the CPU would send it to (GpuHashPartitioningBase.scala:64).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import (Column, ColumnVector, ColumnarBatch,
                               StringColumn, round_pow2, sort_carries,
                               sort_rows)
from ..expr.hashing import murmur3_row_hash


def hash_partition_ids(key_cols: Sequence[Column], num_parts: int,
                       seed: int = 42) -> jnp.ndarray:
    """int32[capacity] destination partition per row (Spark pmod rule)."""
    h = murmur3_row_hash(key_cols, seed=seed)  # int32
    m = h % jnp.int32(num_parts)
    return jnp.where(m < 0, m + num_parts, m)


def round_robin_partition_ids(capacity: int, num_parts: int,
                              start: int = 0) -> jnp.ndarray:
    return ((jnp.arange(capacity, dtype=jnp.int32) + start) % num_parts)


def _order_class(col: Column, n: int, asc: bool, nf: bool) -> jnp.ndarray:
    """int8[n] ordering class consistent with ops.kernels.sort_indices:
    nulls-first nulls < values < NaN (ascending) with NaN leading under
    descending, nulls-last nulls always last."""
    valid = col.validity
    if isinstance(col, ColumnVector) and \
            jnp.issubdtype(col.data.dtype, jnp.floating):
        nan = jnp.isnan(col.data)
    else:
        nan = jnp.zeros(n, jnp.bool_)
    value_cls = jnp.where(nan, jnp.int8(2 if asc else 1),
                          jnp.int8(1 if asc else 2))
    return jnp.where(valid, value_cls, jnp.int8(0 if nf else 3))


def range_partition_ids(key_cols: Sequence[Column],
                        bound_cols: Sequence[Column],
                        ascending: Sequence[bool],
                        nulls_first: Sequence[bool]) -> jnp.ndarray:
    """int32[capacity] destination partition by bound search.

    GpuRangePartitioner semantics: partition id = number of bounds the
    row sorts strictly after, so rows equal to a bound land with that
    bound's partition and the concatenation of partitions in id order is
    globally sorted. ``bound_cols`` hold exactly ``num_parts - 1`` rows
    (capacity == row count; null bounds are legitimate sampled keys).
    Comparison semantics match ops.kernels.sort_indices exactly —
    required for distributed sort correctness.
    """
    from ..ops.kernels import _rank_keys
    cap = key_cols[0].capacity
    B = bound_cols[0].capacity
    before = jnp.zeros((cap, B), jnp.bool_)
    eq = jnp.ones((cap, B), jnp.bool_)
    for rc, bc, asc, nf in zip(key_cols, bound_cols, ascending, nulls_first):
        rcls = _order_class(rc, cap, asc, nf)
        bcls = _order_class(bc, B, asc, nf)
        before = before | (eq & (rcls[:, None] < bcls[None, :]))
        eq = eq & (rcls[:, None] == bcls[None, :])
        rkeys = list(_rank_keys(rc))
        bkeys = list(_rank_keys(bc))
        # strings of different pad buckets produce different word counts;
        # zero-extend (zero == empty suffix, ordered before any byte)
        while len(rkeys) < len(bkeys):
            rkeys.append(jnp.zeros(cap, rkeys[0].dtype))
        while len(bkeys) < len(rkeys):
            bkeys.append(jnp.zeros(B, bkeys[0].dtype))
        for rk, bk in zip(rkeys, bkeys):
            lt = (rk[:, None] < bk[None, :]) if asc \
                else (rk[:, None] > bk[None, :])
            before = before | (eq & lt)
            eq = eq & (rk[:, None] == bk[None, :])
    after = ~(before | eq)
    return jnp.sum(after.astype(jnp.int32), axis=1)


class PartitionedBatch:
    """A batch split into ``num_parts`` dense slots.

    ``columns[i]`` holds per-column arrays with a leading partition dim:
      - primitive: data (P, S), validity (P, S)
      - string:    padded bytes (P, S, W), lengths (P, S), validity (P, S)
    ``counts`` is int32[P] live rows per partition. All shapes static.
    """

    __slots__ = ("columns", "names", "dtypes", "counts", "slot_capacity")

    def __init__(self, columns, names, dtypes, counts, slot_capacity: int):
        self.columns = columns
        self.names = list(names)
        self.dtypes = list(dtypes)
        self.counts = counts
        self.slot_capacity = slot_capacity

    @property
    def num_parts(self) -> int:
        return self.counts.shape[0]


def _pb_flatten(p: PartitionedBatch):
    return (tuple(p.columns), p.counts), (tuple(p.names), tuple(p.dtypes),
                                          p.slot_capacity)


def _pb_unflatten(aux, children):
    names, dtypes, slot_capacity = aux
    columns, counts = children
    return PartitionedBatch(list(columns), list(names), list(dtypes), counts,
                            slot_capacity)


jax.tree_util.register_pytree_node(PartitionedBatch, _pb_flatten, _pb_unflatten)


def _route(batch: ColumnarBatch, part_ids: jnp.ndarray, num_parts: int):
    """Destination of every slot (dead rows past the live buckets) and
    the live rows each destination receives."""
    pid = jnp.where(batch.live_mask(), part_ids, jnp.int32(num_parts))
    counts_all = jnp.zeros(num_parts + 1, jnp.int32).at[
        jnp.clip(pid, 0, num_parts)].add(1)
    return pid, counts_all[:num_parts]


def partition_counts(batch: ColumnarBatch, part_ids: jnp.ndarray,
                     num_parts: int) -> jnp.ndarray:
    """int32[num_parts]: ``partition_batch(...).counts`` without
    packing a single row."""
    return _route(batch, part_ids, num_parts)[1]


def _slot_keys_fit(capacity: int, num_parts: int) -> bool:
    """Whether ``cut_batch``'s key, a slot's destination then its
    position, fits 32 bits."""
    return (num_parts + 1) * capacity < 2 ** 31


def cut_carries(batch: ColumnarBatch, num_parts: int) -> bool:
    """Whether ``cut_batch`` moves any of this batch's columns through
    its sort (``sort_carries``, and a key that fits 32 bits): the host's
    count of the launch."""
    return sort_carries(batch.schema()) and \
        _slot_keys_fit(batch.capacity, num_parts)


def cut_batch(batch: ColumnarBatch, part_ids: jnp.ndarray, num_parts: int,
              capacities: Sequence[int]) -> List[Optional[ColumnarBatch]]:
    """Partition ``p``'s live rows, in their order, moved straight into
    a batch of ``capacities[p]`` slots (None where that is 0): what
    ``partition_batch``'s slot ``p`` holds once repacked to that capacity
    (``ops/kernels.py::repack_to``), with the same shapes, a string
    column's byte buffer included. Each partition takes its own
    capacity and not the batch's, so a cut whose partitions' rows are
    known moves about the batch once instead of ``num_parts`` times.

    One unstable sort of a key unique to each slot, its destination then
    its position, lays the partitions end to end, each in its rows'
    order. The fixed-width columns ride that sort as payload
    (``sort_rows``) and partition ``p`` is a contiguous slice of the
    sorted planes; strings are gathered by the sorted positions, and so
    is every column of a batch whose key would pass 32 bits (a stable
    argsort of the destinations). ListColumns are not cut here
    (``partition_batch``)."""
    cap = batch.capacity
    pid, counts = _route(batch, part_ids, num_parts)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    moved: List[Optional[ColumnVector]] = [None] * len(batch.columns)
    if _slot_keys_fit(cap, num_parts):
        # unique keys need no stable sort, and the unstable one compiles
        # five times faster than the stable sort or the counting sort's
        # cumsums
        key, moved = sort_rows(pid * cap + jnp.arange(cap, dtype=jnp.int32),
                               batch.columns)
        order = key % cap
    else:
        order = jnp.argsort(pid, stable=True).astype(jnp.int32)
    # padded by the widest slice, so that no slice's start is clamped
    width = max(capacities, default=0)
    moved = [None if m is None else
             ColumnVector(jnp.pad(m.data, (0, width)),
                          jnp.pad(m.validity, (0, width)), m.dtype)
             for m in moved]
    gathers = any(m is None for m in moved)
    slices: List[Optional[ColumnarBatch]] = []
    for p, c in enumerate(capacities):
        if not c:
            slices.append(None)
            continue
        at = jnp.arange(c, dtype=jnp.int32)
        rows = jnp.take(order, jnp.clip(offsets[p] + at, 0, cap - 1)) \
            if gathers else None
        n = jnp.minimum(counts[p], c)
        valid = at < n
        cols = []
        for col, m in zip(batch.columns, moved):
            if m is not None:
                cols.append(ColumnVector(
                    jax.lax.dynamic_slice_in_dim(m.data, offsets[p], c),
                    jax.lax.dynamic_slice_in_dim(m.validity, offsets[p], c),
                    m.dtype).masked(valid))
            elif isinstance(col, StringColumn):
                # the byte buffer partition_batch's slot would have after
                # the repack: min(capacity x width, pow2(c x width))
                w = col.pad_bucket
                cols.append(col.gather(rows, valid, out_char_capacity=min(
                    cap * w, round_pow2(max(c * w, 128)))))
            else:
                cols.append(col.gather(rows, valid))
        slices.append(ColumnarBatch(cols, batch.names, n))
    return slices


def partition_batch(batch: ColumnarBatch, part_ids: jnp.ndarray,
                    num_parts: int,
                    slot_capacity: Optional[int] = None) -> PartitionedBatch:
    """Pack rows into a dense (num_parts, slot_capacity) layout.

    Rows keep their relative order within a partition (stable sort by
    destination). Dead rows are routed past the live buckets and dropped.
    """
    cap = batch.capacity
    S = slot_capacity or cap
    live = batch.live_mask()
    pid, counts = _route(batch, part_ids, num_parts)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    if num_parts <= 32:
        # counting sort: per-partition rank via one cumsum per partition
        # — replaces the full argsort whose cost dominated partitioning
        within = jnp.zeros(cap, jnp.int32)
        for p in range(num_parts):
            is_p = pid == p
            within = jnp.where(
                is_p, jnp.cumsum(is_p.astype(jnp.int32)) - 1, within)
        slot = jnp.take(offsets, jnp.clip(pid, 0, num_parts - 1)) + within
        order = jnp.zeros(cap, jnp.int32).at[
            jnp.where(pid < num_parts, slot, cap)].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop")
    else:
        order = jnp.argsort(pid, stable=True).astype(jnp.int32)
    j = jnp.arange(S, dtype=jnp.int32)
    srcpos = offsets[:num_parts, None] + j[None, :]          # (P, S)
    row = jnp.take(order, jnp.clip(srcpos, 0, cap - 1))      # (P, S)
    valid = j[None, :] < jnp.minimum(counts, S)[:, None]     # (P, S)

    from ..columnar.nested import ListColumn
    cols_out = []
    for c in batch.columns:
        if isinstance(c, ListColumn):
            # lists shuffle as (lens, validity) row planes plus a child
            # plane packed row-major PER PARTITION: every element gets
            # its row's destination, then the same dense-pack as rows
            # runs on the element axis (no fixed-width truncation, so
            # collect-style states of any length survive)
            lens_all = jnp.where(c.validity & live, c.lengths(), 0)
            pl = jnp.where(valid, jnp.take(lens_all, row), 0)
            pv = valid & jnp.take(c.validity, row)
            ccap = c.child_capacity
            epos = jnp.arange(ccap, dtype=jnp.int32)
            erow = jnp.clip(jnp.searchsorted(c.offsets[1:], epos,
                                             side="right"),
                            0, cap - 1).astype(jnp.int32)
            e_live = epos < c.offsets[cap]
            e_pid = jnp.where(e_live & jnp.take(live, erow),
                              jnp.take(part_ids, erow),
                              jnp.int32(num_parts))
            # elements of one row stay contiguous and rows keep their
            # relative order inside a partition: sort by (pid, position)
            e_order = jnp.argsort(e_pid, stable=True).astype(jnp.int32)
            e_counts = jnp.zeros(num_parts + 1, jnp.int32).at[
                jnp.clip(e_pid, 0, num_parts)].add(1)[:num_parts]
            e_offsets = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(e_counts, dtype=jnp.int32)])
            j2 = jnp.arange(ccap, dtype=jnp.int32)
            esrc = e_offsets[:num_parts, None] + j2[None, :]
            etake = jnp.take(e_order, jnp.clip(esrc, 0, ccap - 1))
            e_valid = j2[None, :] < e_counts[:, None]       # (P, Sc)
            cdata = jnp.where(e_valid,
                              jnp.take(c.child.data, etake),
                              jnp.zeros((), c.child.data.dtype))
            cok = e_valid & jnp.take(c.child.validity, etake)
            cols_out.append((pl, pv, cdata, cok,
                             jnp.minimum(e_counts, ccap)))
            continue
        if isinstance(c, StringColumn):
            padded = c.padded()                              # (cap, W)
            lens = c.lengths()
            pb = jnp.take(padded, row, axis=0)               # (P, S, W)
            pl = jnp.where(valid, jnp.take(lens, row), 0)
            pv = valid & jnp.take(c.validity, row)
            pb = jnp.where(valid[:, :, None], pb, jnp.zeros((), jnp.uint8))
            cols_out.append((pb, pl, pv))
        else:
            from ..columnar.decimal128 import Decimal128Column
            if isinstance(c, Decimal128Column):
                hi = jnp.where(valid, jnp.take(c.hi, row),
                               jnp.zeros((), jnp.int64))
                lo = jnp.where(valid, jnp.take(c.lo, row),
                               jnp.zeros((), jnp.uint64))
                v = valid & jnp.take(c.validity, row)
                cols_out.append((hi, lo, v))
            else:
                data = jnp.take(c.data, row)
                data = jnp.where(valid, data, jnp.zeros((), data.dtype))
                v = valid & jnp.take(c.validity, row)
                cols_out.append((data, v))
    return PartitionedBatch(cols_out, batch.names,
                            [c.dtype for c in batch.columns],
                            jnp.minimum(counts, S), S)


def list_from_packed(lens: jnp.ndarray, validity: jnp.ndarray,
                     child_vals: jnp.ndarray, child_ok: jnp.ndarray,
                     n_elems, element_type):
    """Rebuild a ListColumn from the packed shuffle layout: row lens +
    validity, and child elements packed row-major with ``n_elems``
    live."""
    from ..columnar.nested import ListColumn
    from ..columnar.vector import ColumnVector
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)])
    pos = jnp.arange(child_vals.shape[0], dtype=jnp.int32)
    live = pos < n_elems
    child = ColumnVector(
        jnp.where(live & child_ok, child_vals,
                  jnp.zeros((), child_vals.dtype)),
        live & child_ok, element_type)
    return ListColumn(offsets, child, validity, element_type)


def string_from_padded(padded: jnp.ndarray, lens: jnp.ndarray,
                       validity: jnp.ndarray,
                       char_capacity: Optional[int] = None) -> StringColumn:
    """Rebuild a StringColumn from a fixed-width (N, W) padded view.

    The inverse of ``StringColumn.padded()`` — used on the receive side of
    the shuffle, where strings travel as fixed-width byte lanes.
    """
    n, w = padded.shape
    nbytes = char_capacity or n * w
    from ..columnar.vector import rows_from_offsets
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)])
    pos = jnp.arange(nbytes, dtype=jnp.int32)
    row_c = rows_from_offsets(offsets[:-1], lens, nbytes)
    within = pos - jnp.take(offsets, row_c)
    total = offsets[n]
    chars = jnp.where(
        pos < total,
        padded[row_c, jnp.clip(within, 0, w - 1)],
        jnp.zeros((), jnp.uint8))
    return StringColumn(offsets, chars, validity, pad_bucket=w)


def flatten_partitions(pb: PartitionedBatch,
                       received_counts: Optional[jnp.ndarray] = None
                       ) -> ColumnarBatch:
    """Flatten a (P, S) partitioned layout back into one dense batch.

    ``received_counts`` overrides ``pb.counts`` (after an all_to_all, the
    exchanged counts describe the blocks now held). Rows are compacted so
    the output is a standard live-prefix batch of capacity P*S.
    """
    from ..columnar.vector import compaction_indices, live_mask
    P, S = pb.num_parts, pb.slot_capacity
    counts = pb.counts if received_counts is None else received_counts
    cap = P * S
    j = jnp.arange(S, dtype=jnp.int32)
    slot_valid = (j[None, :] < counts[:, None]).reshape(cap)
    n = jnp.sum(jnp.minimum(counts, S)).astype(jnp.int32)
    order = compaction_indices(slot_valid)
    keep = live_mask(cap, n)  # compacted output: live rows are a prefix

    cols: List[Column] = []
    for spec, dtype in zip(pb.columns, pb.dtypes):
        if isinstance(dtype, dt.ArrayType):
            lens, valid, cdata, cok, e_counts = spec
            flat_l = jnp.take(lens.reshape(cap), order)
            flat_v = jnp.take(valid.reshape(cap), order)
            flat_l = jnp.where(keep, flat_l, 0)
            flat_v = flat_v & keep
            # child planes: compact each partition's live element run,
            # partition-major (matches the row flattening order)
            P_, Sc = cdata.shape
            je = jnp.arange(Sc, dtype=jnp.int32)
            e_slot_valid = (je[None, :] < e_counts[:, None]).reshape(
                P_ * Sc)
            n_elems = jnp.sum(e_counts).astype(jnp.int32)
            e_order = compaction_indices(e_slot_valid)
            flat_cd = jnp.take(cdata.reshape(P_ * Sc), e_order)
            flat_co = jnp.take(cok.reshape(P_ * Sc), e_order) & \
                live_mask(P_ * Sc, n_elems)
            cols.append(list_from_packed(flat_l, flat_v, flat_cd,
                                         flat_co, n_elems,
                                         dtype.element_type))
            continue
        if dtype == dt.STRING:
            padded, lens, valid = spec
            w = padded.shape[-1]
            flat_b = jnp.take(padded.reshape(cap, w), order, axis=0)
            flat_l = jnp.take(lens.reshape(cap), order)
            flat_v = jnp.take(valid.reshape(cap), order)
            flat_l = jnp.where(keep, flat_l, 0)
            flat_v = flat_v & keep
            cols.append(string_from_padded(flat_b, flat_l, flat_v))
        elif isinstance(dtype, dt.DecimalType) and dtype.is_wide:
            from ..columnar.decimal128 import Decimal128Column
            hi, lo, valid = spec
            h = jnp.take(hi.reshape(cap), order)
            l = jnp.take(lo.reshape(cap), order)
            v = jnp.take(valid.reshape(cap), order) & keep
            h = jnp.where(v, h, jnp.zeros((), jnp.int64))
            l = jnp.where(v, l, jnp.zeros((), jnp.uint64))
            cols.append(Decimal128Column(h, l, v, dtype))
        else:
            data, valid = spec
            d = jnp.take(data.reshape(cap), order)
            v = jnp.take(valid.reshape(cap), order) & keep
            d = jnp.where(v, d, jnp.zeros((), d.dtype))
            cols.append(ColumnVector(d, v, dtype))
    return ColumnarBatch(cols, pb.names, n)
