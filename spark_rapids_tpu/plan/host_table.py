"""Host-side columnar table: the CPU fallback's data representation.

A HostTable is the row-variable CPU mirror of a device ColumnarBatch:
each column is (values: np.ndarray, mask: np.ndarray bool) in the SAME
physical lane encoding the device side uses (dates = int32 days,
timestamps = int64 micros, decimals = scaled int64, strings = object
array of str). Keeping physical encodings identical makes
device<->host transitions exact bit-level copies and lets the
differential test harness compare CPU and TPU results directly.

Reference counterpart: the row<->columnar transition layer
(GpuRowToColumnarExec.scala / GpuColumnarToRowExec.scala, SURVEY §1 L2) —
except our CPU side is columnar too, so transitions are buffer copies,
not row pivots.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnarBatch, ColumnVector, StringColumn,
                               choose_capacity, column_from_numpy,
                               from_physical, string_column_from_utf8)

Schema = List  # [(name, DType), ...]


class HostColumn:
    """``values`` in the device's physical lane encoding, ``mask`` true
    where a row is not null. A STRING column may instead carry ``utf8``:
    ``(offsets int32[n + 1], bytes uint8[])``, the Arrow / device layout
    as a decoder wrote it (null rows zero-length). Such a column goes
    to the device as it is (``table_to_batch``) and concatenates and
    slices as buffers; its object array of ``str`` is made only when
    something reads ``values`` (the CPU operators).

    A fixed-width column may carry ``padded``: ``(values, validity)`` of
    a batch capacity, of which ``values`` and ``mask`` are the first
    ``n`` rows, zero under nulls and past row ``n`` — the device's
    layout, written there by a scan's decoders (io/scan.py
    ``_PlacedBatch``). ``table_to_batch`` at that capacity transfers the
    two buffers as they are; nobody writes to them again."""

    __slots__ = ("_values", "mask", "dtype", "utf8", "padded")

    def __init__(self, values: Optional[np.ndarray], mask: np.ndarray,
                 dtype: dt.DType, utf8=None, padded=None):
        assert values is not None or utf8 is not None
        assert values is None or len(values) == len(mask)
        self._values = values
        self.mask = np.asarray(mask, dtype=bool)
        self.dtype = dtype
        self.utf8 = utf8
        self.padded = padded

    @property
    def values(self) -> np.ndarray:
        if self._values is None:
            import pyarrow as pa
            offsets, data = self.utf8
            n = len(self.mask)
            arr = pa.Array.from_buffers(
                pa.binary(), n, [None, pa.py_buffer(offsets),
                                 pa.py_buffer(data)])
            vals = np.empty(n, object)
            vals[:] = [b.decode("utf-8", "replace")
                       for b in arr.to_pylist()]
            self._values = vals
        return self._values

    @values.setter
    def values(self, values: np.ndarray) -> None:
        self._values, self.utf8, self.padded = values, None, None

    def __len__(self):
        return len(self.mask)

    def slice(self, start: int, end: int) -> "HostColumn":
        if self.utf8 is None:
            return HostColumn(self._values[start:end], self.mask[start:end],
                              self.dtype)
        offsets, data = self.utf8
        return HostColumn(None, self.mask[start:end], self.dtype,
                          utf8=(offsets[start:end + 1], data))

    def take(self, idx: np.ndarray, valid: Optional[np.ndarray] = None) -> "HostColumn":
        safe = np.clip(idx, 0, max(len(self.values) - 1, 0))
        if len(self.values) == 0:
            values = np.zeros(len(idx), dtype=self.values.dtype)
            mask = np.zeros(len(idx), dtype=bool)
        else:
            values = self.values[safe]
            mask = self.mask[safe]
        if valid is not None:
            mask = mask & valid
        return HostColumn(values, mask, self.dtype)

    def __repr__(self):
        return f"HostColumn({self.dtype}, n={len(self)})"


class HostTable:
    """Ordered named host columns; all the CPU operators' currency."""

    def __init__(self, columns: Sequence[HostColumn], names: Sequence[str]):
        assert len(columns) == len(names)
        self.columns = list(columns)
        self.names = list(names)
        #: set by a decoder that wrote this table's fixed-width columns
        #: into the rows a scan gave it (io/native_parquet.py): the
        #: first row's place among the file's
        self.placed: Optional[int] = None

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    def column(self, name: str) -> HostColumn:
        return self.columns[self.names.index(name)]

    def schema(self) -> Schema:
        return [(n, c.dtype) for n, c in zip(self.names, self.columns)]

    def take(self, idx: np.ndarray, valid: Optional[np.ndarray] = None) -> "HostTable":
        return HostTable([c.take(idx, valid) for c in self.columns], self.names)

    def select_rows(self, mask: np.ndarray) -> "HostTable":
        idx = np.nonzero(mask)[0]
        return self.take(idx)

    def with_columns(self, columns: Sequence[HostColumn],
                     names: Sequence[str]) -> "HostTable":
        return HostTable(list(columns), list(names))

    def __repr__(self):
        cols = ", ".join(f"{n}:{c.dtype}" for n, c in zip(self.names, self.columns))
        return f"HostTable[{cols}](n={self.num_rows})"


def _wide_decimal(t) -> bool:
    return isinstance(t, dt.DecimalType) and t.is_wide


def empty_like(schema: Schema) -> HostTable:
    cols = []
    for _, t in schema:
        if t == dt.STRING or t.is_nested or _wide_decimal(t):
            cols.append(HostColumn(np.empty(0, object), np.empty(0, bool), t))
        else:
            cols.append(HostColumn(np.empty(0, np.dtype(t.physical)),
                                   np.empty(0, bool), t))
    return HostTable(cols, [n for n, _ in schema])


def concat_tables(tables: Sequence[HostTable]) -> HostTable:
    first = tables[0]
    cols = []
    for i in range(len(first.columns)):
        parts = [t.columns[i] for t in tables]
        mask = np.concatenate([c.mask for c in parts])
        if all(c.utf8 is not None for c in parts):
            cols.append(HostColumn(None, mask, first.columns[i].dtype,
                                   utf8=_concat_utf8([c.utf8 for c in parts])))
            continue
        values = np.concatenate([c.values for c in parts])
        cols.append(HostColumn(values, mask, first.columns[i].dtype))
    return HostTable(cols, first.names)


def _concat_utf8(parts):
    """(offsets, bytes) of string columns laid end to end; a part's
    offsets may start anywhere in its bytes (a slice)."""
    offsets = np.empty(sum(len(o) - 1 for o, _ in parts) + 1, np.int32)
    offsets[0] = 0
    at, base, chunks = 0, 0, []
    for o, data in parts:
        n = len(o) - 1
        offsets[at + 1:at + n + 1] = o[1:] - (o[0] - base)
        chunks.append(data[o[0]:o[n]])
        at, base = at + n, base + int(o[n] - o[0])
    return offsets, np.concatenate(chunks) if chunks else np.empty(0, np.uint8)


def from_pydict(data: dict, schema: Schema) -> HostTable:
    """Build from {name: [python values]} using device physical encodings."""
    from ..columnar.vector import _to_physical
    n = len(next(iter(data.values()))) if data else 0
    cols = []
    for name, t in schema:
        raw = data[name]
        mask = np.array([v is not None for v in raw], dtype=bool)
        if t.is_nested:
            # nested host columns hold LOGICAL python values
            # (lists/dicts), not physical lanes
            values = np.empty(len(raw), dtype=object)
            for i, v in enumerate(raw):
                values[i] = v
        elif t == dt.STRING:
            values = np.array([v if v is not None else "" for v in raw],
                              dtype=object)
        elif _wide_decimal(t):
            # decimal128 host lanes are python ints (exact, unbounded) —
            # the oracle's arbitrary-precision mirror of the two-limb
            # device encoding (columnar/decimal128.py)
            values = np.array(
                [_to_physical(v, t) if v is not None else 0 for v in raw],
                dtype=object)
        else:
            phys = np.dtype(t.physical)
            values = np.array(
                [_to_physical(v, t) if v is not None else 0 for v in raw],
                dtype=phys)
        cols.append(HostColumn(values, mask, t))
    return HostTable(cols, [n for n, _ in schema])


def to_pydict(table: HostTable) -> dict:
    out = {}
    for name, col in zip(table.names, table.columns):
        if col.dtype == dt.STRING or col.dtype.is_nested:
            out[name] = [col.values[i] if col.mask[i] else None
                         for i in range(len(col))]
        else:
            out[name] = [from_physical(col.values[i], col.dtype)
                         if col.mask[i] else None for i in range(len(col))]
    return out


# ---------------------------------------------------------------------------
# Host <-> device transitions (GpuRowToColumnar / GpuColumnarToRow equiv)
# ---------------------------------------------------------------------------

def table_to_batch(table: HostTable,
                   capacity: Optional[int] = None) -> ColumnarBatch:
    n = table.num_rows
    cap = capacity or choose_capacity(n)
    cols = []
    for c in table.columns:
        if c.dtype.is_nested:
            from ..columnar.nested import nested_column_from_pylist
            values = [c.values[i] if c.mask[i] else None
                      for i in range(len(c))]
            cols.append(nested_column_from_pylist(
                values + [None] * (cap - n), cap, c.dtype))
        elif c.dtype == dt.STRING and c.utf8 is not None:
            cols.append(string_column_from_utf8(*c.utf8, c.mask, cap))
        elif c.dtype == dt.STRING:
            cols.append(column_from_numpy(
                np.asarray(c.values, dtype=object), cap,
                dtype=dt.STRING, mask=c.mask))
        elif _wide_decimal(c.dtype):
            # host lanes are already unscaled ints: build limbs directly
            from ..columnar.decimal128 import from_unscaled_ints
            cols.append(from_unscaled_ints(list(c.values), cap, c.dtype,
                                           mask=c.mask))
        elif c.padded is not None and len(c.padded[0]) == cap:
            values, validity = c.padded
            cols.append(ColumnVector(jnp.asarray(values),
                                     jnp.asarray(validity), c.dtype))
        else:
            cols.append(column_from_numpy(c.values, cap, dtype=c.dtype,
                                          mask=c.mask))
    return ColumnarBatch(cols, table.names, n)


def batch_to_table(batch: ColumnarBatch) -> HostTable:
    from ..columnar.nested import ListColumn, StructColumn
    n = int(batch.num_rows)
    cols = []
    for c in batch.columns:
        vals, mask = c.to_numpy(n)
        if isinstance(c, (StringColumn, ListColumn, StructColumn)):
            cols.append(HostColumn(np.asarray(vals, dtype=object),
                                   np.asarray(mask), c.dtype))
        else:
            cols.append(HostColumn(np.asarray(vals), np.asarray(mask),
                                   c.dtype))
    return HostTable(cols, batch.names)
