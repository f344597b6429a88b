"""Nested device columns: lists and structs.

TPU-native rebuild of cuDF's LIST/STRUCT column model as consumed by the
reference (GpuColumnVector.java type mapping :360, collectionOperations
/ complexTypeCreator / complexTypeExtractors.scala). Layouts follow
Arrow/cuDF:

- ``ListColumn``: ``offsets:int32[capacity+1]`` into a child Column
  holding the flattened elements; row i's elements are
  ``child[offsets[i]:offsets[i+1]]``. Null/dead rows have zero-length
  extents. ``pad_bucket`` is a static power-of-two bound on the longest
  list, the same static-shape device lowering trick StringColumn uses:
  element-wise kernels (contains/min/max/sort/get) view the list as a
  dense ``(capacity, pad_bucket)`` lane block.
- ``StructColumn``: parallel child columns sharing the parent validity;
  a null struct row nulls every child lane (the zero-under-null
  invariant from vector.py holds recursively).

Both register as JAX pytrees so nested batches flow through jit /
shard_map unchanged.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..jit_registry import read_back, read_int
from . import dtypes as dt


class ListColumn:
    __slots__ = ("offsets", "child", "validity", "dtype", "pad_bucket")

    def __init__(self, offsets: jax.Array, child, validity: jax.Array,
                 element_type: dt.DType, pad_bucket: int = 16,
                 map_type: Optional[dt.MapType] = None):
        self.offsets = offsets
        self.child = child
        self.validity = validity
        # maps ARE list<struct<key,value>> physically; map_type keeps
        # the logical map-ness through transformations so host
        # round-trips rebuild dicts (GpuColumnVector's LIST-backed MAP)
        self.dtype = map_type or dt.ArrayType(element_type)
        self.pad_bucket = pad_bucket

    @property
    def _map_type(self) -> Optional[dt.MapType]:
        return self.dtype if isinstance(self.dtype, dt.MapType) else None

    @property
    def capacity(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def child_capacity(self) -> int:
        return self.child.capacity

    def lengths(self) -> jax.Array:
        return self.offsets[1:] - self.offsets[:-1]

    def with_validity(self, validity: jax.Array) -> "ListColumn":
        return ListColumn(self.offsets, self.child, validity,
                          self.dtype.element_type, self.pad_bucket,
                          map_type=self._map_type)

    def element_lanes(self):
        """Dense (capacity, pad_bucket) view of a primitive child:
        (values, lane_ok, elem_ok) where lane_ok marks in-bounds lanes
        and elem_ok additionally requires a non-null element. The list
        analogue of StringColumn.padded()."""
        from .vector import ColumnVector
        assert isinstance(self.child, ColumnVector), \
            "element_lanes requires a primitive element type"
        cap = self.capacity
        starts = self.offsets[:-1]
        lens = self.lengths()
        k = jnp.arange(self.pad_bucket, dtype=jnp.int32)
        idx = jnp.clip(starts[:, None] + k[None, :], 0,
                       self.child_capacity - 1)
        vals = jnp.take(self.child.data, idx)
        lane_ok = k[None, :] < lens[:, None]
        elem_ok = lane_ok & jnp.take(self.child.validity, idx)
        vals = jnp.where(elem_ok, vals, jnp.zeros((), vals.dtype))
        return vals, lane_ok, elem_ok

    def gather(self, indices: jax.Array, valid: Optional[jax.Array] = None,
               unique: bool = False) -> "ListColumn":
        """Gather list rows, repacking the child (same scatter-free
        searchsorted pattern as StringColumn.gather)."""
        from .vector import round_pow2
        src_cap = self.capacity
        out_cap = indices.shape[0]
        if unique:
            child_cap = self.child_capacity
        else:
            child_cap = round_pow2(max(out_cap * self.pad_bucket, 8))
        safe = jnp.clip(indices, 0, src_cap - 1)
        starts = jnp.take(self.offsets[:-1], safe)
        lens = jnp.take(self.lengths(), safe)
        validity = jnp.take(self.validity, safe)
        if valid is not None:
            validity = validity & valid
            lens = jnp.where(valid, lens, 0)
        ends = jnp.cumsum(lens, dtype=jnp.int32)
        lens = jnp.where(ends <= child_cap, lens, 0)
        new_offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(lens, dtype=jnp.int32)])
        from .vector import rows_from_offsets
        pos = jnp.arange(child_cap, dtype=jnp.int32)
        row_c = rows_from_offsets(new_offsets[:-1], lens, child_cap)
        within = pos - jnp.take(new_offsets, row_c)
        src_idx = jnp.take(starts, row_c) + within
        total = new_offsets[out_cap]
        elem_valid = pos < total
        new_child = self.child.gather(
            jnp.clip(src_idx, 0, self.child_capacity - 1), elem_valid)
        return ListColumn(new_offsets, new_child, validity,
                          self.dtype.element_type, self.pad_bucket,
                          map_type=self._map_type)

    def to_numpy(self, num_rows: Optional[int] = None):
        """Host copy: object array of python lists (logical values);
        map-typed columns rebuild dicts from their entry structs."""
        from .vector import from_physical
        n = self.capacity if num_rows is None else read_int(num_rows)
        offs, = read_back(self.offsets)
        child_vals, child_mask = self.child.to_numpy()
        et = self.dtype.element_type
        as_map = self._map_type is not None
        out = np.empty(n, dtype=object)
        for i in range(n):
            lo, hi = int(offs[i]), int(offs[i + 1])
            items = [
                (_child_value(child_vals, child_mask, j, et))
                for j in range(lo, hi)]
            if as_map:
                out[i] = {e["key"]: e["value"] for e in items
                          if e is not None}
            else:
                out[i] = items
        return out, read_back(self.validity)[0][:n]

    def __repr__(self):
        return (f"ListColumn({self.dtype}, capacity={self.capacity}, "
                f"child_capacity={self.child_capacity})")


def _child_value(vals, mask, j, et):
    from .vector import from_physical
    if not mask[j]:
        return None
    v = vals[j]
    if isinstance(et, (dt.ArrayType, dt.StructType)):
        return v  # already logical (recursion happened in child.to_numpy)
    if et == dt.STRING:
        return v
    return from_physical(v, et)


class StructColumn:
    __slots__ = ("children", "validity", "dtype")

    def __init__(self, children: Sequence, validity: jax.Array,
                 struct_type: dt.StructType):
        self.children = list(children)
        self.validity = validity
        self.dtype = struct_type

    @property
    def capacity(self) -> int:
        return self.validity.shape[0]

    def field(self, name: str):
        return self.children[self.dtype.field_names().index(name)]

    def with_validity(self, validity: jax.Array) -> "StructColumn":
        return StructColumn(self.children, validity, self.dtype)

    def gather(self, indices: jax.Array, valid: Optional[jax.Array] = None,
               unique: bool = False) -> "StructColumn":
        safe = jnp.clip(indices, 0, self.capacity - 1)
        validity = jnp.take(self.validity, safe)
        if valid is not None:
            validity = validity & valid
        kids = []
        for c in self.children:
            if hasattr(c, "chars") or isinstance(c, ListColumn):
                kids.append(c.gather(indices, validity, unique=unique))
            else:
                kids.append(c.gather(indices, validity))
        return StructColumn(kids, validity, self.dtype)

    def to_numpy(self, num_rows: Optional[int] = None):
        n = self.capacity if num_rows is None else read_int(num_rows)
        field_data = []
        for c, (fname, ftype) in zip(self.children, self.dtype.fields):
            vals, mask = c.to_numpy(n)
            field_data.append((fname, ftype, vals, mask))
        out = np.empty(n, dtype=object)
        for i in range(n):
            out[i] = {fname: _child_value(vals, mask, i, ftype)
                      for fname, ftype, vals, mask in field_data}
        return out, read_back(self.validity)[0][:n]

    def __repr__(self):
        return f"StructColumn({self.dtype}, capacity={self.capacity})"


# ---------------------------------------------------------------------------
# pytree registration
# ---------------------------------------------------------------------------

def _lc_flatten(v: ListColumn):
    return ((v.offsets, v.child, v.validity),
            (v.dtype, v.pad_bucket))


def _lc_unflatten(aux, children):
    dtype, pad = aux
    offsets, child, validity = children
    mt = dtype if isinstance(dtype, dt.MapType) else None
    return ListColumn(offsets, child, validity, dtype.element_type,
                      pad, map_type=mt)


jax.tree_util.register_pytree_node(ListColumn, _lc_flatten, _lc_unflatten)


def _st_flatten(v: StructColumn):
    return (tuple(v.children), v.validity), v.dtype


def _st_unflatten(dtype, children):
    kids, validity = children
    return StructColumn(list(kids), validity, dtype)


jax.tree_util.register_pytree_node(StructColumn, _st_flatten, _st_unflatten)


# ---------------------------------------------------------------------------
# host -> device construction
# ---------------------------------------------------------------------------

def nested_column_from_pylist(values, capacity: int, dtype: dt.DType,
                              mask: Optional[np.ndarray] = None):
    """Build a device column for any (possibly nested) dtype from python
    values (None = null). Lists are python lists; structs are dicts (or
    tuples in field order)."""
    from .vector import column_from_numpy, round_pow2
    n = len(values)
    valid = np.array([v is not None for v in values], dtype=bool) \
        if mask is None else np.asarray(mask, dtype=bool)
    if isinstance(dtype, dt.MapType):
        # map = list<struct<key,value>>: values are dicts (or pair
        # sequences, the form pyarrow's to_pylist yields for pa.map_)
        def entries(v):
            pairs = v.items() if isinstance(v, dict) else v
            return [{"key": k, "value": val} for k, val in pairs]
        as_lists = [None if v is None else entries(v) for v in values]
        inner = dt.StructType((("key", dtype.key_type),
                               ("value", dtype.value_type)))
        lc = nested_column_from_pylist(as_lists, capacity,
                                       dt.ArrayType(inner), valid)
        return ListColumn(lc.offsets, lc.child, lc.validity, inner,
                          lc.pad_bucket, map_type=dtype)
    if isinstance(dtype, dt.ArrayType):
        lens = np.array([0 if v is None else len(v) for v in values],
                        dtype=np.int32)
        offsets = np.zeros(capacity + 1, dtype=np.int32)
        offsets[1:n + 1] = np.cumsum(lens)
        offsets[n + 1:] = offsets[n] if n else 0
        flat = []
        for v in values:
            if v is not None:
                flat.extend(v)
        child_cap = round_pow2(max(len(flat), 8))
        child = nested_column_from_pylist(flat + [None] * (child_cap -
                                                           len(flat)),
                                          child_cap, dtype.element_type)
        pad = round_pow2(max(int(lens.max()) if n else 1, 1))
        validity = np.zeros(capacity, dtype=bool)
        validity[:n] = valid
        return ListColumn(jnp.asarray(offsets), child,
                          jnp.asarray(validity), dtype.element_type,
                          pad_bucket=pad)
    if isinstance(dtype, dt.StructType):
        kids = []
        for fi, (fname, ftype) in enumerate(dtype.fields):
            fvals = []
            for v in values:
                if v is None:
                    fvals.append(None)
                elif isinstance(v, dict):
                    fvals.append(v.get(fname))
                else:
                    fvals.append(v[fi])
            kids.append(nested_column_from_pylist(fvals, capacity, ftype))
        validity = np.zeros(capacity, dtype=bool)
        validity[:n] = valid
        return StructColumn(kids, jnp.asarray(validity), dtype)
    # leaf
    arr = np.asarray(list(values), dtype=object)
    return column_from_numpy(arr, capacity, dtype=dtype, mask=valid)
