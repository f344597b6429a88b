"""Device-resident columnar vectors and batches.

TPU-native analogue of the reference's columnar data layer
(sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java and
cuDF's column model): a column is one or more flat device buffers plus a
validity mask. The decisive architectural difference from cuDF is that XLA
wants **static shapes**, so every batch here carries a static ``capacity``
and a (possibly traced) ``num_rows`` scalar:

- rows ``[0, num_rows)`` are live; rows beyond are dead padding,
- all kernels compute over the full capacity and mask with
  ``live_mask(capacity, num_rows)`` where results would otherwise leak,
- operations that change cardinality (filter, join, aggregate) keep the
  same capacity and only move ``num_rows`` — no recompilation, and XLA
  sees one fixed program per capacity bucket.

Strings use the Arrow/cuDF layout: ``offsets:int32[capacity+1]`` into a
flat ``chars:uint8[char_capacity]`` buffer.

ColumnVector / StringColumn / ColumnarBatch are registered as JAX pytrees so
whole batches flow through ``jax.jit`` / ``shard_map`` untouched.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as dt


def live_mask(capacity: int, num_rows) -> jax.Array:
    """bool[capacity] mask of live rows."""
    return jnp.arange(capacity, dtype=jnp.int32) < num_rows


def rows_from_offsets(starts: jax.Array, lens: jax.Array,
                      out_size: int) -> jax.Array:
    """Owning row per flat element position.

    Row r owns positions [starts[r], starts[r]+lens[r]); spans are
    contiguous and ascending (the Arrow offsets invariant). Returns
    int32[out_size] with positions past the last span mapping to the
    last row (callers mask with a total-length check). Implemented as
    scatter-max + cummax — two linear passes, replacing the
    searchsorted formulation whose log-factor passes dominated every
    string repack at batch scale."""
    n = starts.shape[0]
    # only rows that own at least one byte mark their start; at a shared
    # start position the non-empty row is the max index by construction
    mark = jnp.full(out_size, -1, jnp.int32).at[
        jnp.where(lens > 0, starts, out_size)].max(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    row = jax.lax.cummax(mark)
    return jnp.clip(row, 0, n - 1)


def compaction_indices(keep: jax.Array) -> jax.Array:
    """Stable-compaction gather map: entry j (for j < sum(keep)) is the
    position of the j-th kept row; tail entries are 0 (callers mask dead
    output rows, so the duplicated row-0 gather is harmless). cumsum +
    scatter — replaces ``argsort(~keep)`` whose full sort cost dominated
    every filter/compact on batches at capacity scale."""
    cap = keep.shape[0]
    slot = jnp.cumsum(keep.astype(jnp.int32)) - 1
    return jnp.zeros(cap, jnp.int32).at[
        jnp.where(keep, slot, cap)].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")


class ColumnVector:
    """A flat primitive column: data buffer + validity mask.

    ``validity[i] == True`` means row i is non-null. Dead rows (beyond the
    owning batch's num_rows) must have ``validity == False``; data there is
    zeroed so reductions can use data*validity without masking twice.
    """

    __slots__ = ("data", "validity", "dtype")

    def __init__(self, data: jax.Array, validity: jax.Array, dtype: dt.DType):
        self.data = data
        self.validity = validity
        self.dtype = dtype

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def with_validity(self, validity: jax.Array) -> "ColumnVector":
        return ColumnVector(self.data, validity, self.dtype)

    def gather(self, indices: jax.Array, valid: Optional[jax.Array] = None) -> "ColumnVector":
        """Gather rows; out-of-range/invalid gather slots become null.

        Mirrors cuDF ``Table.gather`` + GatherMap semantics used throughout
        the reference's join/sort paths (JoinGatherer.scala).
        """
        safe = jnp.clip(indices, 0, self.capacity - 1)
        data = jnp.take(self.data, safe, axis=0)
        validity = jnp.take(self.validity, safe, axis=0)
        if valid is not None:
            validity = validity & valid
            data = jnp.where(valid, data, jnp.zeros_like(data))
        return ColumnVector(data, validity, self.dtype)

    def to_numpy(self, num_rows: Optional[int] = None):
        """Host copy of live values as a (values, mask) pair."""
        n = self.capacity if num_rows is None else int(num_rows)
        return np.asarray(self.data)[:n], np.asarray(self.validity)[:n]

    def __repr__(self):
        return f"ColumnVector({self.dtype}, capacity={self.capacity})"


class StringColumn:
    """Variable-length UTF-8 column: offsets into a flat byte buffer.

    Arrow/cuDF string layout. ``offsets`` has capacity+1 entries; row i's
    bytes are chars[offsets[i]:offsets[i+1]]. Dead/null rows have
    zero-length extents so kernels never touch garbage bytes.

    ``pad_bucket`` is a static power-of-two upper bound on the longest
    string in the column. Column-to-column comparison, sorting, and
    hashing lower strings to a (capacity, pad_bucket) fixed-width view;
    keeping the bound static+bucketed bounds XLA recompiles.
    """

    __slots__ = ("offsets", "chars", "validity", "dtype", "pad_bucket")

    def __init__(self, offsets: jax.Array, chars: jax.Array, validity: jax.Array,
                 pad_bucket: int = 64):
        self.offsets = offsets
        self.chars = chars
        self.validity = validity
        self.dtype = dt.STRING
        self.pad_bucket = pad_bucket

    @property
    def capacity(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def char_capacity(self) -> int:
        return self.chars.shape[0]

    def lengths(self) -> jax.Array:
        return self.offsets[1:] - self.offsets[:-1]

    def with_validity(self, validity: jax.Array) -> "StringColumn":
        return StringColumn(self.offsets, self.chars, validity, self.pad_bucket)

    def padded(self) -> jax.Array:
        """(capacity, pad_bucket) uint8 fixed-width view, zero padded.

        The workhorse lowering for string compare/sort/hash kernels —
        zero never appears inside UTF-8 text, so byte-wise lexicographic
        order on the padded view equals string order.
        """
        cap = self.capacity
        starts = self.offsets[:-1]
        lens = self.lengths()
        k = jnp.arange(self.pad_bucket, dtype=jnp.int32)
        idx = starts[:, None] + k[None, :]
        take = jnp.take(self.chars, jnp.clip(idx, 0, self.char_capacity - 1))
        return jnp.where(k[None, :] < lens[:, None], take, jnp.zeros((), jnp.uint8))

    def gather(self, indices: jax.Array, valid: Optional[jax.Array] = None,
               out_char_capacity: Optional[int] = None,
               unique: bool = False) -> "StringColumn":
        """Gather string rows, repacking bytes into a new flat buffer.

        The output has ``len(indices)`` rows. The default output byte
        buffer is ``len(indices) * pad_bucket`` rounded to a power of
        two — a hard upper bound (every row is at most pad_bucket
        bytes), so duplicating gathers (joins with repeated keys,
        cross-pair replication) can never overflow-truncate.
        ``unique=True`` (permutations/compactions: each source row used
        at most once) keeps the tight source-sized buffer instead —
        total gathered bytes can't exceed the source total — or the
        first bound where that is the smaller one: a handful of rows
        repacked out of a 2^20-row batch (a partial aggregate's groups)
        carry ``pad_bucket`` bytes a row, not the batch's bytes, through
        every gather below.
        """
        src_cap = self.capacity
        out_cap = indices.shape[0]
        if out_char_capacity is not None:
            nbytes_cap = out_char_capacity
        elif unique:
            nbytes_cap = min(self.char_capacity,
                             round_pow2(max(out_cap * self.pad_bucket, 128)))
        else:
            nbytes_cap = round_pow2(max(out_cap * self.pad_bucket, 128))
        safe = jnp.clip(indices, 0, src_cap - 1)
        starts = jnp.take(self.offsets[:-1], safe)
        lens = jnp.take(self.lengths(), safe)
        validity = jnp.take(self.validity, safe)
        if valid is not None:
            validity = validity & valid
            lens = jnp.where(valid, lens, 0)
        # Truncate rows that would start past the output buffer: they
        # become empty rather than corrupting neighbours.
        ends = jnp.cumsum(lens, dtype=jnp.int32)
        lens = jnp.where(ends <= nbytes_cap, lens, 0)
        new_offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)])
        # Repack: for each output byte position find its row (linear
        # scatter+cummax scan), then index into the source chars buffer.
        pos = jnp.arange(nbytes_cap, dtype=jnp.int32)
        row_c = rows_from_offsets(new_offsets[:-1], lens, nbytes_cap)
        within = pos - jnp.take(new_offsets, row_c)
        src = jnp.take(starts, row_c) + within
        total = new_offsets[out_cap]
        new_chars = jnp.where(
            pos < total,
            jnp.take(self.chars, jnp.clip(src, 0, self.char_capacity - 1)),
            jnp.zeros((), jnp.uint8))
        return StringColumn(new_offsets, new_chars, validity, self.pad_bucket)

    def to_numpy(self, num_rows: Optional[int] = None):
        n = self.capacity if num_rows is None else int(num_rows)
        offs = np.asarray(self.offsets)
        chars = np.asarray(self.chars).tobytes()
        vals = np.array(
            [chars[offs[i]:offs[i + 1]].decode("utf-8", errors="replace") for i in range(n)],
            dtype=object)
        return vals, np.asarray(self.validity)[:n]

    def __repr__(self):
        return f"StringColumn(capacity={self.capacity}, char_capacity={self.char_capacity})"


Column = Union[ColumnVector, StringColumn]


class ColumnarBatch:
    """A batch of named columns with static capacity and dynamic num_rows.

    The unit that flows through the operator pipeline — the analogue of
    Spark's ColumnarBatch of GpuColumnVectors (RDD[ColumnarBatch] in the
    reference, SURVEY §1 L2). ``num_rows`` may be a Python int (host side)
    or a traced int32 scalar (inside jit).
    """

    __slots__ = ("columns", "names", "num_rows")

    def __init__(self, columns: Sequence[Column], names: Sequence[str], num_rows):
        assert len(columns) == len(names)
        self.columns = list(columns)
        self.names = list(names)
        self.num_rows = num_rows

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name: str) -> Column:
        return self.columns[self.names.index(name)]

    def live_mask(self) -> jax.Array:
        return live_mask(self.capacity, self.num_rows)

    def with_columns(self, columns: Sequence[Column], names: Sequence[str]) -> "ColumnarBatch":
        return ColumnarBatch(columns, names, self.num_rows)

    def select(self, names: Sequence[str]) -> "ColumnarBatch":
        return ColumnarBatch([self.column(n) for n in names], list(names), self.num_rows)

    def gather(self, indices: jax.Array, new_num_rows,
               unique: bool = False) -> "ColumnarBatch":
        """Gather rows by index; indices beyond new_num_rows produce dead
        rows. ``unique=True`` = permutation/compaction (no source row
        duplicated): string columns keep their tight byte buffers."""
        cap = indices.shape[0]
        valid = live_mask(cap, new_num_rows)

        def g(c):
            from .nested import ListColumn
            if isinstance(c, (StringColumn, ListColumn)):
                return c.gather(indices, valid, unique=unique)
            return c.gather(indices, valid)
        cols = [g(c) for c in self.columns]
        return ColumnarBatch(cols, self.names, new_num_rows)

    def schema(self):
        return [(n, c.dtype) for n, c in zip(self.names, self.columns)]

    def __repr__(self):
        cols = ", ".join(f"{n}:{c.dtype}" for n, c in zip(self.names, self.columns))
        return f"ColumnarBatch[{cols}](capacity={self.capacity}, num_rows={self.num_rows})"


# ---------------------------------------------------------------------------
# pytree registrations: batches flow through jit/shard_map as containers.
# ---------------------------------------------------------------------------

def _cv_flatten(v: ColumnVector):
    return (v.data, v.validity), v.dtype


def _cv_unflatten(dtype, children):
    data, validity = children
    return ColumnVector(data, validity, dtype)


jax.tree_util.register_pytree_node(ColumnVector, _cv_flatten, _cv_unflatten)


def _sc_flatten(v: StringColumn):
    return (v.offsets, v.chars, v.validity), v.pad_bucket


def _sc_unflatten(pad_bucket, children):
    return StringColumn(*children, pad_bucket=pad_bucket)


jax.tree_util.register_pytree_node(StringColumn, _sc_flatten, _sc_unflatten)


def _cb_flatten(b: ColumnarBatch):
    return (tuple(b.columns), b.num_rows), tuple(b.names)


def _cb_unflatten(names, children):
    columns, num_rows = children
    return ColumnarBatch(list(columns), list(names), num_rows)


jax.tree_util.register_pytree_node(ColumnarBatch, _cb_flatten, _cb_unflatten)


# ---------------------------------------------------------------------------
# Host <-> device construction
# ---------------------------------------------------------------------------

def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def round_pow2(n: int, minimum: int = 8) -> int:
    """Round up to a power of two (>= minimum). THE bucketing helper:
    capacities and string pad buckets all come from here so the XLA
    recompile behavior stays consistent across construction paths."""
    cap = max(minimum, 1)
    while cap < n:
        cap *= 2
    return cap


def choose_capacity(n: int, minimum: int = 8) -> int:
    """Bucket row counts to powers of two so XLA compiles once per bucket.

    This is the static-shape answer to cuDF's fully dynamic batch sizes
    (SURVEY §7 hard-part #1): a handful of capacity buckets means a handful
    of compiled programs, amortized across the whole query.
    """
    return round_pow2(n, minimum)


def _encode_strings(values, valid: np.ndarray, n: int):
    """utf-8 encode a host string column -> (lengths[int32], bytes).
    Invalid/None slots encode as zero-length. The hot path hands the
    whole column to pyarrow (C-speed layout) instead of per-row Python
    encode; anything pyarrow rejects (mixed/str-coercible objects)
    falls back to the per-row loop."""
    import pyarrow as pa
    vals = values.tolist() if isinstance(values, np.ndarray) else list(values)
    if not valid.all():
        vals = [v if (m and v is not None) else None
                for v, m in zip(vals, valid)]
    try:
        arr = pa.array(vals, type=pa.string(), from_pandas=True)
    except (pa.lib.ArrowInvalid, pa.lib.ArrowTypeError):
        encoded = [b"" if not valid[i] or vals[i] is None
                   else str(vals[i]).encode("utf-8") for i in range(n)]
        lens = np.fromiter((len(e) for e in encoded), dtype=np.int32,
                           count=n)
        return lens, np.frombuffer(b"".join(encoded), dtype=np.uint8)
    off_buf, data_buf = arr.buffers()[1], arr.buffers()[2]
    off = np.frombuffer(off_buf, dtype=np.int32)[
        arr.offset:arr.offset + n + 1]
    lens = np.diff(off)
    data = (np.frombuffer(data_buf, dtype=np.uint8)[off[0]:off[n]]
            if data_buf is not None and n else np.empty(0, np.uint8))
    # null slots in an arrow array built from python lists carry
    # zero-length extents already, matching the engine invariant
    return lens.astype(np.int32), data


def _string_column(lens: np.ndarray, data: np.ndarray, valid: np.ndarray,
                   capacity: int) -> StringColumn:
    """Host lengths + bytes (null rows zero-length) -> device column."""
    n = len(lens)
    offsets = np.zeros(capacity + 1, dtype=np.int32)
    offsets[1:n + 1] = np.cumsum(lens)
    offsets[n + 1:] = offsets[n]
    total = int(offsets[n])
    char_cap = max(_round_up(total, 128), 128)
    chars = np.zeros(char_cap, dtype=np.uint8)
    if total:
        chars[:total] = data[:total]
    validity = np.zeros(capacity, dtype=bool)
    validity[:n] = valid
    max_len = int(lens.max()) if n else 0
    return StringColumn(jnp.asarray(offsets), jnp.asarray(chars), jnp.asarray(validity),
                        pad_bucket=round_pow2(max_len))


def string_column_from_utf8(offsets: np.ndarray, data: np.ndarray,
                           valid: np.ndarray, capacity: int) -> StringColumn:
    """A device string column from Arrow-layout host buffers
    (``offsets`` int32[n + 1] into ``data``, as a decoder wrote them):
    no Python object a row. Null rows must be zero-length; bytes a
    null row carries are dropped here."""
    n = len(valid)
    assert capacity >= n and len(offsets) == n + 1
    lens = np.diff(offsets).astype(np.int32)
    data = data[offsets[0]:offsets[n]] if n else data[:0]
    if n and not valid.all() and lens[~valid].any():
        keep = np.repeat(valid, lens)
        data, lens = data[keep], np.where(valid, lens, 0).astype(np.int32)
    return _string_column(lens, data, valid, capacity)


def column_from_numpy(values: np.ndarray, capacity: int,
                      dtype: Optional[dt.DType] = None,
                      mask: Optional[np.ndarray] = None) -> Column:
    """Build a device column from host values (+ optional null mask)."""
    n = len(values)
    assert capacity >= n
    if dtype is None:
        dtype = dt.from_numpy_dtype(values.dtype)
    valid = np.ones(n, dtype=bool) if mask is None else np.asarray(mask, dtype=bool)

    if dtype == dt.STRING:
        lens, data = _encode_strings(values, valid, n)
        return _string_column(lens, data, valid, capacity)

    if isinstance(dtype, dt.DecimalType) and dtype.is_wide:
        from .decimal128 import from_unscaled_ints
        unscaled = [None if not valid[i] or values[i] is None
                    else _to_physical(values[i], dtype) for i in range(n)]
        return from_unscaled_ints(unscaled, capacity, dtype, mask=valid)

    phys = np.dtype(dtype.physical)
    data = np.zeros(capacity, dtype=phys)
    vals = np.asarray(values)
    if vals.dtype == object:
        vals = np.array([0 if (v is None) else _to_physical(v, dtype) for v in vals],
                        dtype=phys)
    data[:n] = np.where(valid, vals.astype(phys, copy=False), np.zeros(1, dtype=phys))
    validity = np.zeros(capacity, dtype=bool)
    validity[:n] = valid
    return ColumnVector(jnp.asarray(data), jnp.asarray(validity), dtype)


def _to_physical(v, dtype: dt.DType):
    """Convert one Python value to the physical lane representation."""
    import datetime
    import decimal
    if isinstance(dtype, dt.TimestampType):
        if isinstance(v, datetime.datetime):
            if v.tzinfo is None:
                v = v.replace(tzinfo=datetime.timezone.utc)
            return int(v.timestamp() * 1_000_000)
        return int(v)
    if isinstance(dtype, dt.DateType):
        if isinstance(v, datetime.date) and not isinstance(v, datetime.datetime):
            return (v - datetime.date(1970, 1, 1)).days
        return int(v)
    if isinstance(dtype, dt.DecimalType):
        if isinstance(v, decimal.Decimal):
            return int(v.scaleb(dtype.scale).to_integral_value())
        if isinstance(v, float):
            return int(round(v * 10 ** dtype.scale))
        return int(v) * 10 ** dtype.scale
    return v


def batch_from_pydict(data: dict, capacity: Optional[int] = None,
                      schema: Optional[List] = None) -> ColumnarBatch:
    """Build a ColumnarBatch from {name: list/ndarray}; None entries are null."""
    names = list(data.keys())
    n = len(next(iter(data.values()))) if data else 0
    cap = capacity or choose_capacity(n)
    cols = []
    for i, name in enumerate(names):
        values = data[name]
        dtype = None
        if schema is not None:
            dtype = dict(schema).get(name)
        arr = np.asarray(values, dtype=object)
        mask = np.array([v is not None for v in arr], dtype=bool)
        if dtype is None:
            sample = next((v for v in arr if v is not None), None)
            if isinstance(sample, str):
                dtype = dt.STRING
            elif isinstance(sample, bool):
                dtype = dt.BOOL
            elif isinstance(sample, (int, np.integer)):
                dtype = dt.INT64
            elif isinstance(sample, (float, np.floating)):
                dtype = dt.FLOAT64
            else:
                dtype = dt.INT64
        cols.append(column_from_numpy(arr, cap, dtype=dtype, mask=mask))
    return ColumnarBatch(cols, names, n)


def from_physical(v, dtype: dt.DType):
    """Convert one physical lane value back to its Python representation."""
    import datetime
    import decimal
    if hasattr(v, "item"):
        v = v.item()
    if isinstance(dtype, dt.DateType):
        return datetime.date(1970, 1, 1) + datetime.timedelta(days=int(v))
    if isinstance(dtype, dt.TimestampType):
        return datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc) + \
            datetime.timedelta(microseconds=int(v))
    if isinstance(dtype, dt.DecimalType):
        return decimal.Decimal(int(v)).scaleb(-dtype.scale)
    return v


def batch_to_pydict(batch: ColumnarBatch) -> dict:
    """Host copy of live rows; nulls become None."""
    n = int(batch.num_rows)
    out = {}
    for name, col in zip(batch.names, batch.columns):
        vals, mask = col.to_numpy(n)
        out[name] = [from_physical(vals[i], col.dtype) if mask[i] else None
                     for i in range(n)]
    return out
