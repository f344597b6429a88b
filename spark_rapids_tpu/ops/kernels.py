"""Batch kernels: the jittable cores of the physical operators.

This module is the TPU replacement for the cuDF kernel surface the
reference calls through JNI (SURVEY §2.9: Table.gather / sort / groupBy /
hashJoinGatherMaps / partition). Everything here is a pure function over
ColumnarBatch pytrees with **static capacities**, so each operator
pipeline compiles to one XLA program per capacity bucket:

- cardinality changes (filter/join/aggregate) keep capacity and move
  ``num_rows``; dead rows carry validity=False,
- sort is a chain of stable ``argsort`` passes over int64 "rank keys"
  (IEEE total-order transform for floats, packed big-endian words for
  strings) — radix-style multi-pass, the XLA-friendly formulation,
- group-by is sort-based: sort by keys, flag segment boundaries,
  scatter-reduce into a static-capacity state table (the reference uses
  cuDF hash groupby; sorting composes better with static shapes),
- join is hash-partition-free sort-merge: sort the build side by a
  64-bit combined key hash, binary-search probes into it, expand match
  lists with a searchsorted-on-cumsum gather, then verify true key
  equality (hash collisions only waste slots, never corrupt results),
- a join whose build key is a dense run of unique integers is a lookup
  in a direct-address table, and its kept probe rows are compacted by
  one sort of their positions (``first_kept``): on the v5e a sort of
  2^20 int32 costs less than one gather of 2^17 elements out of them.

Join/expansion outputs that exceed the static output capacity report the
true row count; the host-side retry framework (memory/retry.py) splits
the probe batch and re-runs — the TPU analogue of the reference's
SplitAndRetryOOM contract (RmmRapidsRetryIterator.scala).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..jit_registry import named_jit
from ..columnar.vector import (Column, ColumnVector, ColumnarBatch,
                               StringColumn, compaction_indices, live_mask,
                               round_pow2, rows_from_offsets)

# ---------------------------------------------------------------------------
# Filter
# ---------------------------------------------------------------------------


def compact(batch: ColumnarBatch, keep: jnp.ndarray) -> ColumnarBatch:
    """Keep rows where ``keep`` (restricted to live rows), preserving order."""
    keep = keep & batch.live_mask()
    n = jnp.sum(keep).astype(jnp.int32)
    idx = compaction_indices(keep)
    return batch.gather(idx, n, unique=True)


def filter_batch(batch: ColumnarBatch, cond: ColumnVector) -> ColumnarBatch:
    """SQL WHERE: keep rows where the predicate is true-and-not-null."""
    return compact(batch, cond.data & cond.validity)


def bucket_compact(batch: ColumnarBatch, key_cols, num_parts: int,
                   p) -> ColumnarBatch:
    """Rows whose key-hash bucket equals ``p``, compacted.

    The hash-bucketing primitive shared by sub-partition joins and the
    aggregate re-partition merge fallback: both sides of a join (or all
    partials of a merge) bucket with the SAME chain (seed 7 — distinct
    from the shuffle partitioner's seed 42 so shuffle and sub-partition
    bucketing stay uncorrelated), so equal keys always co-locate.
    """
    from ..expr import hashing as H
    h = jnp.full((batch.capacity,), 7, jnp.uint32)
    for c in key_cols:
        h = H.murmur3_column(c, h)
    bucket = (h % jnp.uint32(num_parts)).astype(jnp.int32)
    return compact(batch, (bucket == p) & batch.live_mask())


# ---------------------------------------------------------------------------
# Sort
# ---------------------------------------------------------------------------


def _string_words(padded: jnp.ndarray) -> List[jnp.ndarray]:
    """A padded (capacity, W) byte view as packed big-endian uint64
    words, eight bytes a word: their order is the strings' order."""
    cap, w = padded.shape
    words = []
    for b0 in range(0, w, 8):
        word = jnp.zeros(cap, jnp.uint64)
        for k in range(min(8, w - b0)):
            word = word | (padded[:, b0 + k].astype(jnp.uint64)
                           << (8 * (7 - k)))
        words.append(word)
    return words


def _rank_keys(col: Column) -> List[jnp.ndarray]:
    """Lower a column to sort-key arrays whose ascending order equals SQL
    value order (most significant first). Floats sort natively (XLA's
    total-order comparator puts NaN last, matching Spark once NaN and
    -0.0 are normalized); strings become packed big-endian uint64 words.
    No 64-bit bitcasts — see utils/bits.py."""
    if isinstance(col, StringColumn):
        return _string_words(col.padded())
    d = col.data
    if jnp.issubdtype(d.dtype, jnp.floating):
        d = jnp.where(d == 0.0, jnp.zeros((), d.dtype), d)
        d = jnp.where(jnp.isnan(d), jnp.full((), jnp.nan, d.dtype), d)
        return [d]
    if d.dtype == jnp.bool_:
        return [d.astype(jnp.int8)]
    return [d]


def sort_indices(columns: Sequence[Column], ascending: Sequence[bool],
                 nulls_first: Sequence[bool], live) -> jnp.ndarray:
    """Stable multi-key sort permutation; dead rows always sort last.

    Chain of stable argsorts from least-significant to most-significant
    key (classic LSD radix structure).
    """
    cap = columns[0].capacity if columns else live.shape[0]
    perm = jnp.arange(cap, dtype=jnp.int32)
    for col, asc, nf in reversed(list(zip(columns, ascending, nulls_first))):
        keys = _rank_keys(col)
        for key in reversed(keys):
            k = jnp.take(key, perm)
            perm = jnp.take(perm, jnp.argsort(k, stable=True, descending=not asc))
        # null placement pass (most significant within this key):
        # ascending argsort puts 0 first, so the "goes first" class maps to 0
        null_key = jnp.take(col.validity, perm) if nf else ~jnp.take(col.validity, perm)
        perm = jnp.take(perm, jnp.argsort(null_key.astype(jnp.int8), stable=True))
    dead = ~jnp.take(live, perm)
    perm = jnp.take(perm, jnp.argsort(dead.astype(jnp.int8), stable=True))
    return perm


def sort_batch(batch: ColumnarBatch, key_cols: Sequence[Column],
               ascending: Sequence[bool], nulls_first: Sequence[bool]) -> ColumnarBatch:
    perm = sort_indices(key_cols, ascending, nulls_first, batch.live_mask())
    return batch.gather(perm, batch.num_rows, unique=True)


# ---------------------------------------------------------------------------
# Group-by aggregate (sort-based)
# ---------------------------------------------------------------------------


def _adjacent_equal(col: Column) -> jnp.ndarray:
    """eq[i] = row i equals row i-1 (null-safe); eq[0] = False."""
    if isinstance(col, StringColumn):
        padded = col.padded()
        data_eq = jnp.all(padded[1:] == padded[:-1], axis=1) & \
            (col.lengths()[1:] == col.lengths()[:-1])
    else:
        d = col.data
        if jnp.issubdtype(d.dtype, jnp.floating):
            # NaN == NaN for grouping (Spark normalizes NaNs in group keys)
            nan_eq = jnp.isnan(d[1:]) & jnp.isnan(d[:-1])
            data_eq = (d[1:] == d[:-1]) | nan_eq
        else:
            data_eq = d[1:] == d[:-1]
    v = col.validity
    null_safe = (v[1:] == v[:-1]) & (~v[1:] | data_eq)
    return jnp.concatenate([jnp.zeros(1, jnp.bool_), null_safe])


def group_ids(sorted_keys: Sequence[Column], live) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(gid, num_groups, boundary) for key-sorted rows."""
    cap = live.shape[0]
    if not sorted_keys:
        # global aggregate: one group holding all live rows
        gid = jnp.zeros(cap, jnp.int32)
        boundary = jnp.zeros(cap, jnp.bool_).at[0].set(True) & live
        num_groups = jnp.minimum(jnp.sum(live), 1).astype(jnp.int32)
        return gid, num_groups, boundary
    eq_prev = jnp.ones(cap, jnp.bool_)
    for col in sorted_keys:
        eq_prev = eq_prev & _adjacent_equal(col)
    boundary = live & ~eq_prev
    boundary = jnp.where(jnp.arange(cap) == 0, live, boundary)
    gid = (jnp.cumsum(boundary.astype(jnp.int32)) - 1).clip(0)
    num_groups = jnp.sum(boundary).astype(jnp.int32)
    return gid.astype(jnp.int32), num_groups, boundary


def _gather_rows(col: Column, idx: jnp.ndarray, valid) -> Column:
    """Permutation/compaction row gather (each source row used at most
    once among valid slots) — string/list columns keep tight buffers."""
    from ..columnar.nested import ListColumn
    if isinstance(col, (StringColumn, ListColumn)):
        return col.gather(idx, valid, unique=True)
    return col.gather(idx, valid)


def _keys_eq_pairs(col: Column, ia: jnp.ndarray, ib: jnp.ndarray
                   ) -> jnp.ndarray:
    """Null-safe key equality of row pairs (ia[k], ib[k]) without
    gathering the column: strings compare via their packed big-endian
    words (dense take, no byte repack), floats collapse NaNs so
    NaN == NaN for grouping (Spark normalizes NaN group keys)."""
    va = jnp.take(col.validity, ia)
    vb = jnp.take(col.validity, ib)
    if isinstance(col, StringColumn):
        data_eq = jnp.take(col.lengths(), ia) == jnp.take(col.lengths(), ib)
        for w in _rank_keys(col):
            data_eq = data_eq & (jnp.take(w, ia) == jnp.take(w, ib))
    else:
        da = jnp.take(col.data, ia)
        db = jnp.take(col.data, ib)
        if jnp.issubdtype(da.dtype, jnp.floating):
            data_eq = (da == db) | (jnp.isnan(da) & jnp.isnan(db))
        else:
            data_eq = da == db
    return (va == vb) & (~va | data_eq)


def _group_ids_from_eq(eq_prev: jnp.ndarray, live) -> Tuple:
    """(gid, num_groups, boundary) from a rows-equal-previous mask over
    key-sorted rows."""
    cap = live.shape[0]
    boundary = live & ~eq_prev
    boundary = jnp.where(jnp.arange(cap) == 0, live, boundary)
    gid = (jnp.cumsum(boundary.astype(jnp.int32)) - 1).clip(0)
    num_groups = jnp.sum(boundary).astype(jnp.int32)
    return gid.astype(jnp.int32), num_groups, boundary


def _key_batch(key_cols, key_rows, cap, num_groups) -> ColumnarBatch:
    klm = live_mask(cap, num_groups)
    key_out = [_gather_rows(c, key_rows, klm) for c in key_cols]
    return ColumnarBatch(
        key_out, [f"k{i}" for i in range(len(key_out))], num_groups)


def _prelude_exact(batch: ColumnarBatch, key_cols: Sequence[Column],
                   live=None):
    """Sort-based grouping (the always-correct fallback): rank-chain
    sort, adjacent-equality boundaries, one representative row per
    group. ``live`` is the rows that count (default: the batch's
    prefix; a fused chain passes its filter's mask, any shape)."""
    if live is None:
        live = batch.live_mask()
    cap = batch.capacity
    perm = sort_indices(key_cols, [True] * len(key_cols),
                        [True] * len(key_cols), live)
    live_s = jnp.take(live, perm)
    prev = jnp.concatenate([perm[:1], perm[:-1]])
    eq = jnp.ones(cap, jnp.bool_)
    for c in key_cols:
        eq = eq & _keys_eq_pairs(c, perm, prev)
    eq = eq & (jnp.arange(cap) != 0)
    gid, num_groups, boundary = _group_ids_from_eq(eq, live_s)
    # scratch slot for dead rows; num_groups == cap implies no dead rows
    gid_safe = jnp.where(live_s, gid,
                         jnp.minimum(num_groups, cap - 1).astype(jnp.int32))
    key_rows = jnp.take(perm, compaction_indices(boundary))
    return perm, live_s, gid_safe, num_groups, key_rows


# multiplicative mixers for the claim rounds (odd 64-bit constants from
# splitmix64/xxhash); one claim table per round
_CLAIM_MIXERS = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
                 0x165667B19E3779F9, 0x27D4EB2F165667C5)


def _prelude_fast(batch: ColumnarBatch, key_cols: Sequence[Column],
                  live=None):
    """Sort-free hash-claim grouping: what finds the groups of a batch
    that holds more of them than ``_prelude_direct``'s rounds resolve
    (``_prelude_hashed`` decides, from the traced count).

    Rows claim hash-table slots by scatter-min of a 64-bit key hash
    (one table per round; losers retry under a fresh mixer). Winners of
    one slot share a gid. Exactness is enforced by comparing every
    row's TRUE key against its slot representative — a 64-bit collision
    or an unclaimed row flips ``ok`` and the caller falls back to the
    sort path. Rows stay in original order (perm = iota), so this is
    only valid for scatter-style aggregates (see needs_sorted_groups).

    This replaces cuDF's iterative open-addressing hash groupby
    (GpuAggregateExec.scala:175's cudf groupBy) with a bounded-round,
    branch-free formulation XLA can fuse: every round is a scatter-min
    + gathers over static shapes.
    """
    from ..expr import hashing as H
    if live is None:
        live = batch.live_mask()
    cap = batch.capacity
    h1 = jnp.full((cap,), 0x3C6EF372, jnp.uint32)
    h2 = jnp.full((cap,), 0xA54FF53A, jnp.uint32)
    for c in key_cols:
        h1 = H.murmur3_column(c, h1)
        h2 = H.murmur3_column(c, h2)
        # murmur3_column leaves h unchanged on null rows; fold the
        # validity bit in so null patterns hash apart from values
        h1 = jnp.where(c.validity, h1, h1 ^ jnp.uint32(0x9E3779B9))
        h2 = jnp.where(c.validity, h2,
                       h2 * jnp.uint32(2654435761) + jnp.uint32(1))
    h = (h1.astype(jnp.uint64) << 32) | h2.astype(jnp.uint64)
    INF = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    h = jnp.minimum(h, INF - 1)  # INF is the empty-slot sentinel
    T = round_pow2(cap)
    log2T = T.bit_length() - 1
    arange = jnp.arange(cap, dtype=jnp.int32)

    def one_round(mix, state):
        unresolved, gid, key_rows, offset = state
        slot = ((h * jnp.uint64(mix)) >> jnp.uint64(64 - log2T)
                ).astype(jnp.int32)
        tbl = jnp.full(T, INF, jnp.uint64).at[slot].min(
            jnp.where(unresolved, h, INF))
        won = unresolved & (jnp.take(tbl, slot) == h)
        occ = tbl != INF
        slot_gid = offset + jnp.cumsum(occ.astype(jnp.int32)) - 1
        rep_tbl = jnp.full(T, cap, jnp.int32).at[slot].min(
            jnp.where(won, arange, cap))
        gid = jnp.where(won, jnp.take(slot_gid, slot), gid)
        key_rows = key_rows.at[jnp.where(occ, slot_gid, cap)].set(
            rep_tbl, mode="drop")
        offset = offset + jnp.sum(occ).astype(jnp.int32)
        return unresolved & ~won, gid, key_rows, offset

    state = one_round(_CLAIM_MIXERS[0],
                      (live, jnp.zeros(cap, jnp.int32),
                       jnp.zeros(cap, jnp.int32), jnp.int32(0)))

    def more_rounds(s):
        for mix in _CLAIM_MIXERS[1:]:
            s = one_round(mix, s)
        return s

    # contested slots are the exception (low-cardinality groupings
    # resolve fully in round 1): skip rounds 2..R when nothing is left
    state = jax.lax.cond(jnp.any(state[0]), more_rounds, lambda s: s,
                         state)
    unresolved, gid, key_rows, num_groups = state
    # exactness check: every live row's true key must equal its slot
    # representative's (collisions merge distinct keys; catch them here)
    rep = jnp.take(key_rows, jnp.clip(gid, 0, cap - 1))
    eq = jnp.ones(cap, jnp.bool_)
    for c in key_cols:
        eq = eq & _keys_eq_pairs(c, arange, rep)
    ok = (~jnp.any(unresolved)) & (~jnp.any(live & ~eq))
    gid_safe = jnp.where(live, gid,
                         jnp.minimum(num_groups, cap - 1).astype(jnp.int32))
    return ok, (arange, live, gid_safe, num_groups, key_rows)


#: rounds of ``_prelude_direct``: a batch with at most this many groups
#: is grouped by comparison alone
DIRECT_GROUP_ROUNDS = 8


#: longest fixed width ``_string_key_bytes`` reads without a gather
DENSE_KEY_WIDTHS = 8


def _string_key_bytes(col: StringColumn) -> jnp.ndarray:
    """``col.padded()`` without its gather where the column allows it:
    when every row in front of the zero-length tail has the same length
    L <= ``DENSE_KEY_WIDTHS`` (a CHAR(L) column without nulls, rows in
    their original order), row i's bytes are ``chars[i * L:(i + 1) *
    L]`` and the char buffer, reshaped, IS the view. Decided from the
    offsets the program sees (``lax.switch`` over L: a reshape wants a
    static width); anything else takes the gather."""
    cap, w = col.capacity, col.pad_bucket
    widths = min(w, DENSE_KEY_WIDTHS)
    total = col.offsets[cap]
    first = col.offsets[1] - col.offsets[0]
    dense = (first >= 1) & (first <= widths) & jnp.all(
        col.offsets == jnp.minimum(
            jnp.arange(cap + 1, dtype=jnp.int32) * first, total))

    def view(length: int):
        def run(_):
            chars = col.chars
            if chars.shape[0] < cap * length:
                chars = jnp.pad(chars, (0, cap * length - chars.shape[0]))
            rows = chars[:cap * length].reshape(cap, length)
            filled = jnp.arange(cap, dtype=jnp.int32) * length < total
            rows = jnp.where(filled[:, None], rows, jnp.zeros((), jnp.uint8))
            return jnp.pad(rows, ((0, 0), (0, w - length)))
        return run

    return jax.lax.switch(
        jnp.where(dense, first, 0),
        [lambda _: col.padded()] + [view(n) for n in range(1, widths + 1)],
        None)


def _prelude_direct(batch: ColumnarBatch, key_cols: Sequence[Column],
                    live=None, rounds: int = DIRECT_GROUP_ROUNDS):
    """Grouping by comparison, for a batch that holds a handful of
    groups: no scatter, no sort, no row-count gather.

    Each round takes the first live row no earlier round claimed and
    compares every row's TRUE key with it, elementwise (null-safe, NaN
    equal to NaN, strings by length and packed words: the comparison
    ``_keys_eq_pairs`` makes, against one row instead of row by row);
    the rows that match are that round's group. ``rounds`` rounds
    resolve ``rounds`` groups in ``rounds`` passes over the batch; a
    ``while_loop`` ends early when nothing is left. ``ok`` is false when
    rows remain after the last round: the caller then takes the
    hash-claim prelude. Exact by construction (no hash), rows stay in
    their original order, group ids follow first appearance.

    Cost a round: one ``argmax`` over ``capacity`` bools, one scalar
    read a key word, one compare a key word. A string key's words come
    from ``_string_key_bytes``: free for fixed-width keys, one
    ``capacity x pad_bucket`` gather otherwise (once, not a round).
    """
    if live is None:
        live = batch.live_mask()
    cap = batch.capacity
    keys = []  # (words, validity): a key column as comparable arrays
    for c in key_cols:
        if isinstance(c, StringColumn):
            words = _string_words(_string_key_bytes(c)) + [c.lengths()]
        else:
            words = [c.data]
        keys.append((words, c.validity))

    def equals_row(row):
        eq = jnp.ones(cap, jnp.bool_)
        for words, validity in keys:
            data_eq = jnp.ones(cap, jnp.bool_)
            for w in words:
                ref = w[row]
                same = w == ref
                if jnp.issubdtype(w.dtype, jnp.floating):
                    same = same | (jnp.isnan(w) & jnp.isnan(ref))
                data_eq = data_eq & same
            eq = eq & (validity == validity[row]) & (~validity | data_eq)
        return eq

    def unfinished(state):
        unresolved, _, _, r = state
        return (r < rounds) & jnp.any(unresolved)

    def one_round(state):
        unresolved, gid, key_rows, r = state
        first = jnp.argmax(unresolved).astype(jnp.int32)
        match = unresolved & equals_row(first)
        return (unresolved & ~match, jnp.where(match, r, gid),
                key_rows.at[r].set(first), r + 1)

    unresolved, gid, key_rows, num_groups = jax.lax.while_loop(
        unfinished, one_round,
        (live, jnp.zeros(cap, jnp.int32), jnp.zeros(rounds, jnp.int32),
         jnp.int32(0)))
    ok = ~jnp.any(unresolved)
    gid_safe = jnp.where(live, gid,
                         jnp.minimum(num_groups, cap - 1).astype(jnp.int32))
    key_rows = jnp.zeros(cap, jnp.int32).at[:rounds].set(key_rows)
    return ok, (jnp.arange(cap, dtype=jnp.int32), live, gid_safe,
                num_groups, key_rows)


def _prelude_hashed(batch: ColumnarBatch, key_cols: Sequence[Column],
                    live=None):
    """Sort-free grouping, how the groups are found following how many
    the batch holds: the comparison rounds of ``_prelude_direct`` first;
    when rows remain after them, the hash-claim tables of
    ``_prelude_fast`` (inside one ``lax.cond``: only one of the two
    runs). Returns ``(ok, direct, prelude)``: ``ok`` false sends the
    caller to the sort path, ``direct`` says the rounds sufficed."""
    direct, resolved = _prelude_direct(batch, key_cols, live)
    ok, prelude = jax.lax.cond(
        direct, lambda _: (jnp.bool_(True), resolved),
        lambda _: _prelude_fast(batch, key_cols, live), None)
    return ok, direct, prelude


def _use_hash_grouping(batch: ColumnarBatch, key_cols, agg_fns) -> bool:
    """Static (trace-time) gate for the hash-claim fast path: needs
    grouping keys, scatter-safe aggregates, hashable key types and a
    batch big enough for the claim table to pay for itself."""
    return bool(key_cols) and batch.capacity >= 1024 and \
        all(not getattr(fn, "needs_sorted_groups", False)
            for fn in agg_fns) and \
        all(isinstance(c, (StringColumn, ColumnVector)) for c in key_cols)


def _sorted_group_prelude(batch: ColumnarBatch, key_cols: Sequence[Column],
                          live=None):
    """Sort-path grouping machinery for update and merge passes (the
    sort-free preludes are dispatched by group_aggregate/group_merge
    directly so they can also skip the input gathers).

    Returns (perm, live_s, gid_safe, num_groups, key_rows). Dead rows
    are routed to a scratch gid just past the live groups so their
    (zeroed) values never pollute a real group. Order-sensitive
    aggregates recover each row's original position from ``perm``.
    """
    if live is None:
        live = batch.live_mask()
    cap = batch.capacity
    if not key_cols:
        # global aggregate: one group of the live rows, wherever they
        # stand — no sort
        gid, num_groups, _ = group_ids([], live)
        gid_safe = jnp.where(
            live, gid, jnp.minimum(num_groups,
                                   max(cap - 1, 0)).astype(jnp.int32))
        return (jnp.arange(cap, dtype=jnp.int32), live, gid_safe,
                num_groups, jnp.zeros(cap, jnp.int32))
    return _prelude_exact(batch, key_cols, live)


def _group_states(prelude, fast: bool, agg_inputs, agg_fns, cap,
                  row_offset):
    """The update pass over found groups: per-aggregate partial states."""
    perm, live_s, gid, _, _ = prelude
    states = []
    for inp, fn in zip(agg_inputs, agg_fns):
        if inp is None:
            col_s = None
        elif fast:
            # sort-free prelude: rows untouched, perm is the identity —
            # skip the (pure-overhead) identity gathers
            col_s = inp
        else:
            col_s = _gather_rows(inp, perm, live_s)
        states.append(fn.update(gid, col_s, cap, live_s,
                                row_offset=row_offset,
                                perm=None if fast else perm))
    return states


def group_aggregate(batch: ColumnarBatch, key_cols: Sequence[Column],
                    agg_inputs: Sequence[Optional[Column]], agg_fns: Sequence,
                    row_offset=0, live=None
                    ) -> Tuple[ColumnarBatch, List[dict]]:
    """Group-by update pass: raw rows -> per-group partial states.
    ``row_offset`` is the stream-global position of this batch's row 0,
    consumed by order-sensitive aggregates (first/last). ``live`` is the
    mask of the rows that count: the batch's prefix by default, the
    prefix ANDed with a fused filter's predicate where the chain in
    front hands its filter over instead of compacting (exec/fused.py)."""
    cap = batch.capacity

    def body(prelude, fast: bool):
        return (_key_batch(key_cols, prelude[4], cap, prelude[3]),
                _group_states(prelude, fast, agg_inputs, agg_fns, cap,
                              row_offset))

    if not _use_hash_grouping(batch, key_cols, agg_fns):
        return body(_sorted_group_prelude(batch, key_cols, live), False)
    ok, _, hashed = _prelude_hashed(batch, key_cols, live)
    return jax.lax.cond(
        ok, lambda _: body(hashed, True),
        lambda _: body(_prelude_exact(batch, key_cols, live), False), None)


def pallas_group_fns_ok(agg_inputs: Sequence[Optional[Column]],
                        agg_fns: Sequence) -> bool:
    """Static gate for the MXU one-hot grouped lane: sum-decomposable
    aggregates only (the one-hot matmul is a segmented SUM), float
    inputs for sum/avg (integer sums must stay exact int64 — the f32
    tile arithmetic may drop low bits, the deviation the reference
    ships behind variableFloatAgg for floats ONLY)."""
    from ..expr import aggregates as Agg
    lanes = 0
    for inp, fn in zip(agg_inputs, agg_fns):
        if isinstance(fn, (Agg.Sum, Agg.Average)):
            if type(fn) not in (Agg.Sum, Agg.Average):
                return False  # subclasses may widen state
            if inp is None or inp.dtype not in (dt.FLOAT32, dt.FLOAT64) \
                    or not isinstance(inp, ColumnVector):
                return False
            lanes += 2  # value + count
        elif isinstance(fn, Agg.CountStar) and type(fn) is Agg.CountStar:
            lanes += 1
        elif isinstance(fn, Agg.Count) and type(fn) is Agg.Count:
            if inp is None:
                return False
            lanes += 1
        else:
            return False
    # one accumulator lane column per value column in the kernel —
    # wider aggregations degrade to the XLA path, never crash
    return lanes <= 128


#: one PallasCapacityFallback event per process: the capacity gate is
#: static per compiled program, so the event would otherwise repeat for
#: every trace of every over-capacity shape
_CAP_FALLBACK_WARNED = [False]


def _key_batch_few(key_cols, key_rows, cap: int, num_groups, few: int
                   ) -> ColumnarBatch:
    """``_key_batch`` for at most ``few`` groups: the representatives
    are gathered into ``few`` slots and padded to ``cap`` (the shape
    both branches of the caller's ``lax.cond`` must share), so a string
    key costs a ``few``-row gather, not a ``cap``-row one."""
    if few >= cap:
        return _key_batch(key_cols, key_rows, cap, num_groups)
    rows, klm = key_rows[:few], live_mask(few, num_groups)
    pad = cap - few
    out = []
    for c in key_cols:
        if isinstance(c, StringColumn):
            # distinct source rows: their bytes fit both bounds
            nbytes = min(round_pow2(max(few * c.pad_bucket, 128)),
                         c.char_capacity)
            g = c.gather(rows, klm, out_char_capacity=nbytes)
            out.append(StringColumn(
                jnp.concatenate([g.offsets, jnp.full(pad, g.offsets[few])]),
                jnp.pad(g.chars, (0, c.char_capacity - nbytes)),
                jnp.pad(g.validity, (0, pad)), c.pad_bucket))
        else:
            g = c.gather(rows, klm)
            out.append(ColumnVector(jnp.pad(g.data, (0, pad)),
                                    jnp.pad(g.validity, (0, pad)), c.dtype))
    return ColumnarBatch(out, [f"k{i}" for i in range(len(out))], num_groups)


def group_aggregate_pallas(batch: ColumnarBatch, key_cols: Sequence[Column],
                           agg_inputs: Sequence[Optional[Column]],
                           agg_fns: Sequence, row_offset=0,
                           num_buckets: int = 1024,
                           max_capacity: int = 1 << 24, live=None,
                           ) -> Tuple[ColumnarBatch, List[dict], jnp.ndarray]:
    """Grouped update pass with the pallas one-hot MXU lane.

    Same contract as :func:`group_aggregate` (``live`` included: dead
    rows, whether past the prefix or refused by a fused filter, land on
    the scratch gid with zeroed values) plus traced ``flags``:
    ``int32[2]``, ``flags[0]`` 1 when the lane took the batch,
    ``flags[1]`` 1 when ``_prelude_direct``'s comparison rounds found
    its groups (0: the hash-claim tables did). When a sort-free prelude
    resolves exactly AND the batch has at most ``num_buckets`` groups,
    per-bucket partials come from ``ops/pallas_kernels.tile_group_reduce``
    (a (tile, B) one-hot contracted on the MXU — no scatters) and the
    representatives' keys from a ``num_buckets``-row gather; otherwise
    the stock scatter/sort path runs inside the same ``lax.cond``.
    Mirrors the reference's device hash groupby being THE aggregate path
    (GpuAggregateExec.scala:175) rather than a special case.

    Callers gate with :func:`pallas_group_fns_ok` — this function
    assumes every aggregate is sum-decomposable.
    """
    cap = batch.capacity
    if live is None:
        live = batch.live_mask()

    def stock(prelude, fast: bool):
        return (_key_batch(key_cols, prelude[4], cap, prelude[3]),
                _group_states(prelude, fast, agg_inputs, agg_fns, cap,
                              row_offset))

    # counts accumulate in float32 lanes on the MXU: a group can hold
    # at most `cap` rows, and float32 represents integers exactly only
    # below 2^24 — batches at or past the ceiling must take the stock
    # integer path or Count/CountStar drift. The ceiling is
    # conf-controlled (srt.exec.pallas.groupAgg.maxCapacity); raising
    # it past 2^24 trades Count exactness for MXU throughput.
    cap_ok = cap < int(max_capacity)
    if not (_use_hash_grouping(batch, key_cols, agg_fns)
            and cap >= num_buckets
            and cap_ok
            and pallas_group_fns_ok(agg_inputs, agg_fns)):
        if (not cap_ok and not _CAP_FALLBACK_WARNED[0]
                and _use_hash_grouping(batch, key_cols, agg_fns)
                and cap >= num_buckets
                and pallas_group_fns_ok(agg_inputs, agg_fns)):
            # only the capacity ceiling blocked the MXU lane: surface
            # it once so fusion's terminal-stage choice is observable
            _CAP_FALLBACK_WARNED[0] = True
            from ..obs import events as _events
            _events.emit("PallasCapacityFallback", scope="pallas",
                         capacity=int(cap),
                         max_capacity=int(max_capacity))
        kb, st = group_aggregate(batch, key_cols, agg_inputs, agg_fns,
                                 row_offset, live)
        return kb, st, jnp.zeros(2, jnp.int32)

    from ..expr import aggregates as Agg
    ok, direct, hashed = _prelude_hashed(batch, key_cols, live)
    _, _, gid, num_groups, key_rows = hashed
    small = ok & (num_groups <= num_buckets)

    def pallas_branch(_):
        from . import pallas_kernels as PKn
        # dead rows already land on the scratch gid (== num_groups,
        # itself < num_buckets when this branch is taken) so their
        # zeroed values accumulate into a never-live bucket
        gid_c = jnp.minimum(gid, num_buckets - 1)
        values = []
        for inp, fn in zip(agg_inputs, agg_fns):
            if isinstance(fn, (Agg.Sum, Agg.Average)):
                m = live & inp.validity
                values.append(jnp.where(m, inp.data, jnp.zeros((), inp.data.dtype)))
                values.append(m.astype(jnp.float32))
            elif isinstance(fn, Agg.CountStar):
                values.append(live.astype(jnp.float32))
            else:  # Count
                values.append((live & inp.validity).astype(jnp.float32))
        outs = PKn.tile_group_reduce(gid_c, values,
                                     num_buckets=num_buckets)
        pad = cap - num_buckets

        def to_cap(arr, dtype):
            a = arr.astype(dtype)
            return a if pad == 0 else jnp.pad(a, (0, pad))
        states = []
        i = 0
        for inp, fn in zip(agg_inputs, agg_fns):
            if isinstance(fn, (Agg.Sum, Agg.Average)):
                states.append({"sum": to_cap(outs[i], jnp.float64),
                               "count": to_cap(outs[i + 1], jnp.int64)})
                i += 2
            else:
                states.append({"count": to_cap(outs[i], jnp.int64)})
                i += 1
        return _key_batch_few(key_cols, key_rows, cap, num_groups,
                              num_buckets), states

    def fallback(_):
        return jax.lax.cond(
            ok, lambda __: stock(hashed, True),
            lambda __: stock(_prelude_exact(batch, key_cols, live), False),
            None)

    kb, st = jax.lax.cond(small, pallas_branch, fallback, None)
    return kb, st, jnp.stack([small, direct]).astype(jnp.int32)


def group_merge(batch: ColumnarBatch, key_cols: Sequence[Column],
                agg_states: Sequence[dict], agg_fns: Sequence
                ) -> Tuple[ColumnarBatch, List[dict], jnp.ndarray]:
    """Merge partial aggregation states (the reference's merge pass,
    GpuMergeAggregateIterator GpuAggregateExec.scala:711).

    ``agg_states[i]`` is a dict of state arrays (capacity-length) aligned
    with ``batch`` rows; returns merged (key_batch, states, num_groups).
    Dead rows merge into the scratch gid (see _sorted_group_prelude), so
    their zeroed states cannot corrupt the last real group.
    """
    cap = batch.capacity

    def body(prelude, fast: bool):
        perm, live_s, gid, num_groups, key_rows = prelude
        key_batch = _key_batch(key_cols, key_rows, cap, num_groups)

        def _sort_state(v):
            from ..columnar.nested import ListColumn
            if fast:
                return v  # identity perm: states already row-aligned
            if isinstance(v, (StringColumn, ListColumn)):
                return v.gather(perm, live_s, unique=True)
            return jnp.take(v, perm, axis=0)
        merged = []
        for states, fn in zip(agg_states, agg_fns):
            sorted_states = {k: _sort_state(v) for k, v in states.items()}
            merged.append(fn.merge(gid, sorted_states, cap))
        return key_batch, merged, num_groups

    if not _use_hash_grouping(batch, key_cols, agg_fns):
        return body(_sorted_group_prelude(batch, key_cols), False)
    ok, _, hashed = _prelude_hashed(batch, key_cols)
    return jax.lax.cond(
        ok, lambda _: body(hashed, True),
        lambda _: body(_prelude_exact(batch, key_cols), False), None)


# ---------------------------------------------------------------------------
# Join (sort-merge on 64-bit combined key hash + verification)
# ---------------------------------------------------------------------------


def _join_key_hash(cols: Sequence[Column], null_sentinel: int) -> jnp.ndarray:
    """64-bit combined hash of the key columns; rows with any null key get
    the given sentinel. Probe and build use *different* null sentinels so
    null keys never pair up (SQL join semantics); a real hash landing on a
    sentinel only creates spurious candidates that the equality
    verification pass rejects."""
    from ..expr import hashing as H
    cap = cols[0].capacity
    h1 = jnp.full((cap,), 42, jnp.uint32)
    h2 = jnp.full((cap,), 0xDEADBEEF, jnp.uint32)
    for c in cols:
        h1 = H.murmur3_column(c, h1)
        h2 = H.murmur3_column(c, h2)
    h = (h1.astype(jnp.uint64) << 32) | h2.astype(jnp.uint64)
    any_null = jnp.zeros(cap, jnp.bool_)
    for c in cols:
        any_null = any_null | ~c.validity
    h_i64 = h.astype(jnp.int64)  # wrapping convert, not bitcast (TPU-legal)
    return jnp.where(any_null, jnp.int64(null_sentinel), h_i64)


def _keys_equal(a_cols: Sequence[Column], a_idx, b_cols: Sequence[Column],
                b_idx, null_safe: bool = False) -> jnp.ndarray:
    """True key equality for candidate pairs (collision verification).

    Default is JOIN equality (null matches nothing). ``null_safe=True``
    gives grouping equality — null == null, NaN == NaN — for callers
    comparing partition/group keys (e.g. the running-window carried-
    state continuation check)."""
    ok = jnp.ones(a_idx.shape[0], jnp.bool_)
    for ca, cb in zip(a_cols, b_cols):
        va = jnp.take(ca.validity, a_idx)
        vb = jnp.take(cb.validity, b_idx)
        if isinstance(ca, StringColumn):
            pa = ca.padded()
            pb = cb.padded()
            w = max(ca.pad_bucket, cb.pad_bucket)
            if ca.pad_bucket < w:
                pa = jnp.pad(pa, ((0, 0), (0, w - ca.pad_bucket)))
            if cb.pad_bucket < w:
                pb = jnp.pad(pb, ((0, 0), (0, w - cb.pad_bucket)))
            eq = jnp.all(jnp.take(pa, a_idx, axis=0) == jnp.take(pb, b_idx, axis=0),
                         axis=1)
        else:
            da = jnp.take(ca.data, a_idx)
            db = jnp.take(cb.data, b_idx)
            if da.dtype != db.dtype:
                tgt = jnp.promote_types(da.dtype, db.dtype)
                da = da.astype(tgt)
                db = db.astype(tgt)
            eq = da == db
            if null_safe and jnp.issubdtype(da.dtype, jnp.floating):
                eq = eq | (jnp.isnan(da) & jnp.isnan(db))
        if null_safe:
            ok = ok & ((va & vb & eq) | (~va & ~vb))
        else:
            ok = ok & va & vb & eq
    return ok


def build_hash_index(build_keys: Sequence[Column], build_live):
    """The build side's half of ``join_gather_maps``: ``(bh_sorted,
    order)``, the 64-bit key hashes in ascending order and the build row
    each came from. It depends on the build batch alone, so a join that
    probes one build with many batches computes it once (exec/join.py)
    and hands it back through ``build_index``."""
    imax = jnp.iinfo(jnp.int64).max
    bh = _join_key_hash(build_keys, imax - 2)
    bh = jnp.where(build_live, bh, jnp.int64(imax))
    order = jnp.argsort(bh, stable=True).astype(jnp.int32)
    return jnp.take(bh, order), order


def join_gather_maps(probe_keys: Sequence[Column], build_keys: Sequence[Column],
                     probe_live, build_live, out_capacity: int,
                     build_index=None):
    """Compute (probe_idx, build_idx, pair_valid, total_pairs) gather maps
    for matching pairs — the cuDF ``hashJoinGatherMaps`` equivalent.

    total_pairs is the true match count; if it exceeds out_capacity the
    caller must split and retry. ``build_index`` is ``build_hash_index``
    of the same build side where the caller computed it ahead.
    """
    imax = jnp.iinfo(jnp.int64).max
    cap_b = build_keys[0].capacity
    bh_sorted, order = build_index if build_index is not None \
        else build_hash_index(build_keys, build_live)

    ph = _join_key_hash(probe_keys, imax - 3)
    ph = jnp.where(probe_live, ph, jnp.int64(imax - 1))
    lo = jnp.searchsorted(bh_sorted, ph, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(bh_sorted, ph, side="right").astype(jnp.int32)
    counts = jnp.where(probe_live, hi - lo, 0)

    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    total_cand = offsets[-1]
    pos = jnp.arange(out_capacity, dtype=jnp.int32)
    probe_row = rows_from_offsets(offsets[:-1], counts, out_capacity)
    within = pos - jnp.take(offsets, probe_row)
    build_sorted_pos = jnp.take(lo, probe_row) + within
    build_row = jnp.take(order, jnp.clip(build_sorted_pos, 0, cap_b - 1))
    cand_valid = pos < total_cand

    true_eq = _keys_equal(probe_keys, probe_row, build_keys, build_row)
    pair_valid = cand_valid & true_eq
    return probe_row, build_row, pair_valid, total_cand, counts


def inner_join(probe: ColumnarBatch, build: ColumnarBatch,
               probe_keys: Sequence[Column], build_keys: Sequence[Column],
               out_capacity: int, build_index=None
               ) -> Tuple[ColumnarBatch, jnp.ndarray]:
    """Inner join; returns (joined_batch, candidate_total) — the candidate
    total lets the host detect output-capacity overflow."""
    p_idx, b_idx, pair_valid, total_cand, _ = join_gather_maps(
        probe_keys, build_keys, probe.live_mask(), build.live_mask(),
        out_capacity, build_index)
    compact_idx = compaction_indices(pair_valid)
    n_out = jnp.sum(pair_valid).astype(jnp.int32)
    p_take = jnp.take(p_idx, compact_idx)
    b_take = jnp.take(b_idx, compact_idx)
    valid = live_mask(out_capacity, n_out)
    out_cols = [c.gather(p_take, valid) for c in probe.columns] + \
        [c.gather(b_take, valid) for c in build.columns]
    out_names = probe.names + build.names
    return ColumnarBatch(out_cols, out_names, n_out), total_cand


def left_join(probe: ColumnarBatch, build: ColumnarBatch,
              probe_keys: Sequence[Column], build_keys: Sequence[Column],
              out_capacity: int, build_index=None
              ) -> Tuple[ColumnarBatch, jnp.ndarray]:
    """Left outer join with probe as the preserved/stream side.

    The returned size scalar is max(candidate window, true output rows
    incl. unmatched probe rows) — if it exceeds out_capacity the caller
    must retry bigger (candidates past the window are lost AND output
    rows past capacity are dropped, so both bound the retry)."""
    cap_p = probe.capacity
    p_idx, b_idx, pair_valid, total_cand, _ = join_gather_maps(
        probe_keys, build_keys, probe.live_mask(), build.live_mask(),
        out_capacity, build_index)
    # per-probe-row true match count
    match_per_probe = jnp.zeros(cap_p, jnp.int32).at[p_idx].add(
        pair_valid.astype(jnp.int32))
    unmatched = probe.live_mask() & (match_per_probe == 0)
    n_pairs = jnp.sum(pair_valid).astype(jnp.int32)
    n_unmatched = jnp.sum(unmatched).astype(jnp.int32)
    n_out = n_pairs + n_unmatched

    pair_order = compaction_indices(pair_valid)
    un_order = compaction_indices(unmatched)
    pos = jnp.arange(out_capacity, dtype=jnp.int32)
    from_pairs = pos < n_pairs
    p_take = jnp.where(from_pairs,
                       jnp.take(p_idx, jnp.take(pair_order, jnp.clip(pos, 0, out_capacity - 1))),
                       jnp.take(un_order, jnp.clip(pos - n_pairs, 0, cap_p - 1)))
    b_take = jnp.take(b_idx, jnp.take(pair_order, jnp.clip(pos, 0, out_capacity - 1)))
    valid = live_mask(out_capacity, n_out)
    build_valid = valid & from_pairs
    out_cols = [c.gather(p_take, valid) for c in probe.columns] + \
        [c.gather(b_take, build_valid) for c in build.columns]
    required = jnp.maximum(total_cand, n_out)
    return ColumnarBatch(out_cols, probe.names + build.names, n_out), required


def semi_anti_join(probe: ColumnarBatch, build_keys: Sequence[Column],
                   probe_keys: Sequence[Column], build_live,
                   anti: bool, scratch_capacity: Optional[int] = None,
                   build_index=None
                   ) -> Tuple[ColumnarBatch, jnp.ndarray]:
    """Left semi / anti join — output rows come only from the probe side
    (no expansion), but the *candidate window* can still overflow when
    build keys are heavily duplicated. total_cand is returned so the host
    retries with a larger scratch_capacity when total_cand exceeds it."""
    cap_p = probe.capacity
    scratch = scratch_capacity or cap_p
    p_idx, b_idx, pair_valid, total_cand, counts = join_gather_maps(
        probe_keys, build_keys, probe.live_mask(), build_live, scratch,
        build_index)
    matched = jnp.zeros(cap_p, jnp.bool_).at[p_idx].max(pair_valid)
    keep = probe.live_mask() & (~matched if anti else matched)
    return compact(probe, keep), total_cand


# ---------------------------------------------------------------------------
# Lookup join (direct-address table over a unique integer build key)
# ---------------------------------------------------------------------------
#
# A dimension's surrogate key is a dense run of whole numbers, each once.
# For such a build side a join is a lookup: ``table[key - kmin]`` is the
# build row that holds ``key``, or -1. No hash, no sort, no binary search;
# the one 64-bit operation on the probe path is the subtraction, and the
# index is int32. Whether a build side qualifies is read from its data
# (``build_lookup_table``), never from a name; the
# exec (exec/join.py) keeps ``join_gather_maps`` for everything else.


def build_lookup_table(key: ColumnVector, live, size: int):
    """``(table, kmin, kmax, n_keys, n_distinct)`` in one pass over the
    build key: the least and largest live, non-null key (int64; 0 and -1
    where there is none), how many there are, and ``table[k - kmin]`` =
    the build row whose key is ``k`` (-1 where no row has it),
    int32[size], with the count of slots taken. The table is whole only
    where ``kmax - kmin < size`` — the caller checks that on the host,
    in exact integers — and the keys are unique exactly when
    ``n_distinct`` then equals ``n_keys``. NULL build keys take no slot:
    they match nothing."""
    ok = live & key.validity
    k = key.data.astype(jnp.int64)
    n = jnp.sum(ok).astype(jnp.int32)
    info = jnp.iinfo(jnp.int64)
    none = n == 0
    kmin = jnp.where(none, jnp.int64(0), jnp.min(jnp.where(ok, k, info.max)))
    kmax = jnp.where(none, jnp.int64(-1),
                     jnp.max(jnp.where(ok, k, info.min)))
    delta = k - kmin
    fits = ok & (delta >= 0) & (delta < size)
    slot = jnp.where(fits, delta, size).astype(jnp.int32)
    rows = jnp.arange(key.capacity, dtype=jnp.int32)
    table = jnp.full((size,), -1, jnp.int32).at[slot].set(rows, mode="drop")
    return table, kmin, kmax, n, jnp.sum(table >= 0).astype(jnp.int32)


def lookup_rows(probe_key: ColumnVector, probe_live, table, kmin, kind: str):
    """``(build_row, matched, keep)`` for every probe slot: the build row
    the table holds for the probe key, whether there is one, and whether
    a join of ``kind`` (``inner``, ``left``, ``semi``, ``anti``) keeps the
    probe row. A NULL, dead or out-of-range probe key matches nothing."""
    size = table.shape[0]
    delta = probe_key.data.astype(jnp.int64) - kmin
    in_range = (delta >= 0) & (delta < size)
    slot = jnp.where(in_range, delta, 0).astype(jnp.int32)
    build_row = jnp.take(table, slot)
    matched = probe_live & probe_key.validity & in_range & (build_row >= 0)
    keep = {"left": probe_live,
            "anti": probe_live & ~matched}.get(kind, matched)
    return build_row, matched, keep


def lookup_count(probe: ColumnarBatch, probe_key: ColumnVector, table,
                 kmin, kind: str):
    """Rows ``lookup_join`` of this pair will produce: what sizes a join's
    first output before any pair has run."""
    _, _, keep = lookup_rows(probe_key, probe.live_mask(), table, kmin,
                             kind)
    return jnp.sum(keep).astype(jnp.int32)


def first_kept(keep: jnp.ndarray, out_capacity: int) -> jnp.ndarray:
    """Positions of the first ``out_capacity`` kept rows, in order: entry
    j is the position of the j-th kept row (past the last one: the
    capacity's last row; callers mask). One sort of the positions with
    every dead row's replaced by ``cap``: kept positions are distinct and
    ascending already, so the sort needs no payload and no stability,
    and its cost does not follow ``out_capacity``. On the v5e 0.45 ms
    at 2^20 rows, where binary searches of the prefix sum (up to PR 33)
    cost 19.8 ms for 2^17 rows out and 0.35 for 2^10
    (tools/probe_first_kept.py, PR 34)."""
    cap = keep.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    pos = jax.lax.sort(jnp.where(keep, rows, cap), is_stable=False)
    if out_capacity > cap:
        pos = jnp.pad(pos, (0, out_capacity - cap), constant_values=cap)
    return jnp.minimum(pos[:out_capacity], cap - 1)


def _take_rows(col: Column, idx, valid, unique: bool) -> Column:
    from ..columnar.nested import ListColumn
    if isinstance(col, (StringColumn, ListColumn)):
        return col.gather(idx, valid, unique=unique)
    return col.gather(idx, valid)


def lookup_join(probe: ColumnarBatch, build: ColumnarBatch,
                probe_key: ColumnVector, table, kmin, out_capacity: int,
                kind: str) -> Tuple[ColumnarBatch, jnp.ndarray]:
    """Join ``probe`` to ``build`` through a lookup table of ``build``'s
    unique key. ``kind``: ``inner``, ``left`` (probe preserved, build
    columns NULL where nothing matched), ``semi`` or ``anti`` (probe
    columns only). Returns ``(batch, required)``: ``required`` is the
    exact row count of the full answer; the batch holds its first
    ``out_capacity`` rows, in probe order, and the caller relaunches
    larger when ``required`` exceeds that."""
    build_row, matched, keep = lookup_rows(probe_key, probe.live_mask(),
                                           table, kmin, kind)
    required = jnp.sum(keep).astype(jnp.int32)
    n_out = jnp.minimum(required, out_capacity)
    valid = live_mask(out_capacity, n_out)
    p_take = first_kept(keep, out_capacity)
    # a probe row is used at most once: the build key is unique
    out_cols = [_take_rows(c, p_take, valid, True) for c in probe.columns]
    names = list(probe.names)
    if kind in ("inner", "left"):
        b_take = jnp.take(build_row, p_take)
        b_valid = valid & jnp.take(matched, p_take)
        out_cols += [_take_rows(c, b_take, b_valid, False)
                     for c in build.columns]
        names += list(build.names)
    return ColumnarBatch(out_cols, names, n_out), required


# ---------------------------------------------------------------------------
# Concat / limit / slice
# ---------------------------------------------------------------------------


def concat_columns(cols: Sequence[Column], caps: Sequence[int], counts,
                   out_capacity: int) -> Column:
    """Concatenate the live prefixes of columns into one column."""
    if isinstance(cols[0], StringColumn):
        return _concat_strings(cols, caps, counts, out_capacity)
    from ..columnar.nested import ListColumn
    if isinstance(cols[0], ListColumn):
        return _concat_lists(cols, caps, counts, out_capacity)
    from ..columnar.decimal128 import Decimal128Column
    if isinstance(cols[0], Decimal128Column):
        hi = jnp.zeros(out_capacity, jnp.int64)
        lo = jnp.zeros(out_capacity, jnp.uint64)
        validity = jnp.zeros(out_capacity, jnp.bool_)
        offset = jnp.int32(0)
        for c, cap, n in zip(cols, caps, counts):
            idx = jnp.arange(out_capacity, dtype=jnp.int32) - offset
            in_range = (idx >= 0) & (idx < n)
            take = jnp.clip(idx, 0, cap - 1)
            hi = jnp.where(in_range, jnp.take(c.hi, take), hi)
            lo = jnp.where(in_range, jnp.take(c.lo, take), lo)
            validity = jnp.where(in_range, jnp.take(c.validity, take),
                                 validity)
            offset = offset + (n.astype(jnp.int32)
                               if hasattr(n, "astype") else n)
        return Decimal128Column(hi, lo, validity, cols[0].dtype)
    phys = cols[0].data.dtype
    data = jnp.zeros(out_capacity, phys)
    validity = jnp.zeros(out_capacity, jnp.bool_)
    offset = jnp.int32(0)
    for c, cap, n in zip(cols, caps, counts):
        idx = jnp.arange(out_capacity, dtype=jnp.int32) - offset
        in_range = (idx >= 0) & (idx < n)
        take = jnp.clip(idx, 0, cap - 1)
        data = jnp.where(in_range, jnp.take(c.data, take), data)
        validity = jnp.where(in_range, jnp.take(c.validity, take), validity)
        offset = offset + n.astype(jnp.int32) if hasattr(n, "astype") else offset + n
    return ColumnVector(data, validity, cols[0].dtype)


def _concat_lists(cols, caps, counts, out_capacity: int):
    """Concatenate COMPACT ListColumns (elements stored in row order
    with no gaps — the layout every builder in this codebase produces):
    children concatenate as columns, row offsets relabel by cumsum of
    gathered lengths. Dead/invalid rows must carry zero-length extents,
    the same invariant StringColumn concat relies on."""
    from ..columnar.nested import ListColumn
    lens = jnp.zeros(out_capacity, jnp.int32)
    validity = jnp.zeros(out_capacity, jnp.bool_)
    offset = jnp.int32(0)
    for c, cap, n in zip(cols, caps, counts):
        idx = jnp.arange(out_capacity, dtype=jnp.int32) - offset
        nn = n.astype(jnp.int32) if hasattr(n, "astype") else jnp.int32(n)
        in_range = (idx >= 0) & (idx < nn)
        take = jnp.clip(idx, 0, cap - 1)
        lens = jnp.where(in_range, jnp.take(c.lengths(), take), lens)
        validity = jnp.where(in_range, jnp.take(c.validity, take),
                             validity)
        offset = offset + nn
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)])
    child_cap = sum(c.child_capacity for c in cols)
    elem_counts = [c.offsets[c.capacity] for c in cols]
    child = concat_columns([c.child for c in cols],
                           [c.child_capacity for c in cols],
                           elem_counts, child_cap)
    return ListColumn(offsets, child, validity,
                      cols[0].dtype.element_type, cols[0].pad_bucket)


def _concat_strings(cols: Sequence[StringColumn], caps, counts,
                    out_capacity: int) -> StringColumn:
    lens = jnp.zeros(out_capacity, jnp.int32)
    validity = jnp.zeros(out_capacity, jnp.bool_)
    offset = jnp.int32(0)
    for c, cap, n in zip(cols, caps, counts):
        idx = jnp.arange(out_capacity, dtype=jnp.int32) - offset
        in_range = (idx >= 0) & (idx < n)
        take = jnp.clip(idx, 0, cap - 1)
        lens = jnp.where(in_range, jnp.take(c.lengths(), take), lens)
        validity = jnp.where(in_range, jnp.take(c.validity, take), validity)
        offset = offset + (n.astype(jnp.int32) if hasattr(n, "astype") else jnp.int32(n))
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)])
    char_cap = sum(c.char_capacity for c in cols)
    pos = jnp.arange(char_cap, dtype=jnp.int32)
    row_c = rows_from_offsets(offsets[:-1], lens, char_cap)
    within = pos - jnp.take(offsets, row_c)
    # map row -> source column and source row
    byte = jnp.zeros(char_cap, jnp.uint8)
    offset = jnp.int32(0)
    for c, cap, n in zip(cols, caps, counts):
        nn = n.astype(jnp.int32) if hasattr(n, "astype") else jnp.int32(n)
        src_row = row_c - offset
        mine = (src_row >= 0) & (src_row < nn)
        src_row_c = jnp.clip(src_row, 0, cap - 1)
        src = jnp.take(c.offsets[:-1], src_row_c) + within
        b = jnp.take(c.chars, jnp.clip(src, 0, c.char_capacity - 1))
        byte = jnp.where(mine, b, byte)
        offset = offset + nn
    total = offsets[out_capacity]
    chars = jnp.where(pos < total, byte, jnp.zeros((), jnp.uint8))
    pad = max(c.pad_bucket for c in cols)
    return StringColumn(offsets, chars, validity, pad_bucket=pad)


def _concat_batches_impl(batches: Sequence[ColumnarBatch],
                         out_capacity: int) -> ColumnarBatch:
    counts = [b.num_rows for b in batches]
    total = sum(int(c) if isinstance(c, int) else c for c in counts)
    caps = [b.capacity for b in batches]
    names = batches[0].names
    out_cols = []
    for ci in range(len(names)):
        cols = [b.columns[ci] for b in batches]
        out_cols.append(concat_columns(cols, caps, counts, out_capacity))
    return ColumnarBatch(out_cols, names, total)


# one jit wrapper per output capacity; jax's trace cache inside each
# wrapper keys on the input pytree structure (schemas, per-batch
# capacities), with num_rows as TRACED leaves so varying live counts
# never retrace. Without this every concat dispatched hundreds of tiny
# eager XLA ops per call — the dominant cost of warm group-by queries.
_CONCAT_JIT: dict = {}


def concat_batches(batches: Sequence[ColumnarBatch],
                   out_capacity: int) -> ColumnarBatch:
    """Concatenate batches (same schema) into one batch of out_capacity."""
    fn = _CONCAT_JIT.get(out_capacity)
    if fn is None:
        fn = named_jit(lambda bs, cap=out_capacity:
                       _concat_batches_impl(bs, cap), "concat_batches")
        _CONCAT_JIT[out_capacity] = fn
    return fn(list(batches))


_COMPACT_JIT: dict = {}


def compact_for_transfer(batch: ColumnarBatch,
                         slack: int = 4) -> ColumnarBatch:
    """Shrink a sparse batch to a small power-of-two capacity before it
    crosses a serialization/transfer boundary (shuffle write, broadcast,
    collect). Operators keep their input's static capacity, so a
    partial aggregate of a 512k-row batch emits a 512k-capacity batch
    with a handful of live groups — serializing THAT pulls the whole
    padded capacity off the device. Only compacts when it saves at
    least ``slack``×; costs one host sync of the (scalar) row count."""
    from ..columnar.vector import choose_capacity
    n = int(batch.num_rows)
    cap = choose_capacity(n)
    if cap * slack > batch.capacity:
        return batch
    return repack_to(batch, cap)


def repack_to(batch: ColumnarBatch, cap: int) -> ColumnarBatch:
    """Rows [0, num_rows) re-laid into a fresh batch of capacity
    ``cap`` — one process-wide jit per target capacity (the trace cache
    inside each wrapper keys on the input batch structure). Shared by
    every repack site: join/aggregate sub-partition shrink, transfer
    compaction."""
    fn = _COMPACT_JIT.get(cap)
    if fn is None:
        fn = named_jit(lambda b, c=cap: slice_batch(b, 0, b.num_rows, c),
                       "repack_to")
        _COMPACT_JIT[cap] = fn
    return fn(batch)


def slice_batch(batch: ColumnarBatch, start: int, length,
                out_capacity: int) -> ColumnarBatch:
    """Rows [start, start+length) into a fresh batch of out_capacity.

    The split primitive behind split-and-retry (the contiguousSplit
    analogue); start/length may be traced scalars.
    """
    idx = jnp.arange(out_capacity, dtype=jnp.int32) + start
    n = jnp.minimum(length, jnp.maximum(batch.num_rows - start, 0))
    return batch.gather(idx, n, unique=True)


def local_limit(batch: ColumnarBatch, n: int) -> ColumnarBatch:
    new_n = jnp.minimum(batch.num_rows, n)
    mask = live_mask(batch.capacity, new_n)
    cols = [c.with_validity(c.validity & mask) for c in batch.columns]
    return ColumnarBatch(cols, batch.names, new_n)


# ---------------------------------------------------------------------------
# Generate / explode
# ---------------------------------------------------------------------------

def explode_batch(batch: ColumnarBatch, list_col, element_name: str,
                  out_capacity: int, outer: bool = False,
                  pos_name: str = None):
    """One output row per list element (GpuExplode / GpuGenerateExec).

    ``outer=True``: null/empty lists still produce one row with a null
    element (explode_outer). ``pos_name`` adds the 0-based element
    position column (posexplode). Returns (out_batch, total_rows);
    total may exceed out_capacity — the caller retries with a larger
    capacity bucket (the same overflow contract as the join kernels).
    """
    cap = batch.capacity
    live = batch.live_mask()
    real = jnp.where(list_col.validity & live, list_col.lengths(), 0)
    eff = jnp.maximum(real, 1) if outer else real
    eff = jnp.where(live, eff, 0)
    out_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(eff, dtype=jnp.int32)])
    total = out_offsets[cap]
    pos = jnp.arange(out_capacity, dtype=jnp.int32)
    row_c = rows_from_offsets(out_offsets[:-1], eff, out_capacity)
    within = pos - jnp.take(out_offsets, row_c)
    n_out = jnp.minimum(total, out_capacity)
    gathered = batch.gather(row_c, n_out)
    out_live = live_mask(out_capacity, n_out)
    elem_ok = out_live & (within < jnp.take(real, row_c))
    src = jnp.take(list_col.offsets[:-1], row_c) + \
        jnp.clip(within, 0)
    element = list_col.child.gather(
        jnp.clip(src, 0, list_col.child_capacity - 1), elem_ok)
    cols = list(gathered.columns)
    names = list(gathered.names)
    if pos_name is not None:
        pdata = jnp.where(elem_ok, within, jnp.zeros((), jnp.int32))
        from ..columnar import dtypes as _dt
        cols.append(ColumnVector(pdata, elem_ok, _dt.INT32))
        names.append(pos_name)
    cols.append(element)
    names.append(element_name)
    return ColumnarBatch(cols, names, n_out), total
