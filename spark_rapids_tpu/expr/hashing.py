"""Hash functions — bit-exact Spark Murmur3 (and xxhash64) on TPU.

Reference surface: sql-plugin/.../rapids/HashFunctions.scala + JNI Hash
kernels (murmur3 / xxhash64, SURVEY §2.5). Bit-exactness with Spark's
Murmur3_x86_32 matters because hash partitioning decides shuffle layout:
matching Spark means a CPU Spark job and this engine partition rows
identically. All arithmetic is wrapping uint32/uint64, which XLA gives us
natively on the VPU.

Null columns leave the running hash untouched (Spark semantics); the
default seed is 42 (HashPartitioning / Murmur3Hash expression).
"""

from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.vector import Column, ColumnVector, ColumnarBatch, StringColumn
from ..utils import bits
from .core import Expression, Schema, make_result

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _rotl32(x, r: int):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl32(k1, 15)
    return k1 * _C2


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _fmix(h1, length):
    h1 = h1 ^ jnp.uint32(length)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def _hash_int32(v_u32, seed_u32):
    return _fmix(_mix_h1(seed_u32, _mix_k1(v_u32)), 4)


def _hash_int64(v_u64, seed_u32):
    lo = (v_u64 & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (v_u64 >> 32).astype(jnp.uint32)
    h1 = _mix_h1(seed_u32, _mix_k1(lo))
    h1 = _mix_h1(h1, _mix_k1(hi))
    return _fmix(h1, 8)


def _normalize_float(data):
    """Spark: -0.0 hashes as 0.0, NaN as the canonical NaN bits."""
    data = jnp.where(data == 0.0, jnp.zeros((), data.dtype), data)
    canonical = jnp.asarray(float("nan"), data.dtype)
    return jnp.where(jnp.isnan(data), canonical, data)


def murmur3_column(col: Column, seed) -> jnp.ndarray:
    """uint32 per-row hash of one column; null rows return seed unchanged."""
    if isinstance(col, StringColumn):
        h = _murmur3_string(col, seed)
    else:
        d = col.data
        t = col.dtype
        if isinstance(t, dt.BooleanType):
            v = d.astype(jnp.uint32)  # Spark hashes booleans as int 1/0
            h = _hash_int32(v, seed)
        elif t in (dt.INT8, dt.INT16, dt.INT32) or isinstance(t, dt.DateType):
            v = d.astype(jnp.int64).astype(jnp.uint32)  # sign-extend then wrap
            h = _hash_int32(v, seed)
        elif t == dt.INT64 or isinstance(t, (dt.TimestampType, dt.DecimalType)):
            v = bits.i64_to_u64(d.astype(jnp.int64))
            h = _hash_int64(v, seed)
        elif t == dt.FLOAT32:
            v = bits.f32_bits_u32(_normalize_float(d))
            h = _hash_int32(v, seed)
        elif t == dt.FLOAT64:
            v = bits.f64_bits(_normalize_float(d))
            h = _hash_int64(v, seed)
        else:
            raise TypeError(f"murmur3 unsupported for {t}")
    return jnp.where(col.validity, h, seed)


def _murmur3_string(col: StringColumn, seed) -> jnp.ndarray:
    """Bit-exact Spark string murmur3, O(1) trace size.

    The mixing recurrence is sequential over 4-byte blocks, so it rides
    a single ``lax.scan`` over the word axis (one traced op regardless
    of the pad width W). A per-``b`` Python loop here previously issued
    W/4 distinctly-sliced ops — every eager call minted ~W fresh pjit
    cache entries and dominated wide-string exchange partitioning
    (q22-class NDS plans spent 30s+ hashing 8k rows)."""
    from jax import lax
    padded = col.padded()  # (cap, W) uint8, zero-padded
    cap, w = padded.shape
    lens = col.lengths()
    h1 = jnp.broadcast_to(seed, (cap,)).astype(jnp.uint32)
    nblocks = w // 4
    if nblocks:
        # all little-endian words at once: (cap, nblocks)
        p32 = padded[:, :nblocks * 4].astype(jnp.uint32) \
            .reshape(cap, nblocks, 4)
        words = (p32[:, :, 0] | (p32[:, :, 1] << 8)
                 | (p32[:, :, 2] << 16) | (p32[:, :, 3] << 24))
        use = lens[:, None] >= \
            (4 * jnp.arange(1, nblocks + 1, dtype=jnp.int32))

        def mix_block(h, word_use):
            word, u = word_use
            return jnp.where(u, _mix_h1(h, _mix_k1(word)), h), None

        h1, _ = lax.scan(mix_block, h1, (words.T, use.T))
    # tail: the <=3 trailing bytes, sign-extended, in byte order
    tail_start = (lens // 4) * 4
    for j in range(min(3, w)):
        idx = jnp.clip(tail_start + j, 0, w - 1)
        byte = jnp.take_along_axis(padded, idx[:, None], axis=1)[:, 0]
        byte = byte.astype(jnp.int8).astype(jnp.int32).astype(jnp.uint32)
        in_tail = (tail_start + j) < lens
        h1 = jnp.where(in_tail, _mix_h1(h1, _mix_k1(byte)), h1)
    return _fmix_dynamic(h1, lens)


def _fmix_dynamic(h1, lens):
    h1 = h1 ^ lens.astype(jnp.uint32)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def murmur3_row_hash(cols: Sequence[Column], seed: int = 42) -> jnp.ndarray:
    """Chained multi-column row hash (each column seeds the next), int32."""
    if not cols:
        raise ValueError("need at least one column")
    cap = cols[0].capacity
    h = jnp.full((cap,), seed, jnp.uint32)
    for c in cols:
        h = murmur3_column(c, h)
    return h.view(jnp.int32)  # 32-bit bitcast is TPU-native


class Murmur3Hash(Expression):
    """hash(...) expression — returns int32."""

    def __init__(self, *children: Expression, seed: int = 42):
        super().__init__(*children)
        self.seed = seed

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.INT32

    def nullable(self, schema: Schema) -> bool:
        return False

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        cols = [c.eval(batch) for c in self.children]
        h = murmur3_row_hash(cols, self.seed)
        return make_result(h.astype(jnp.int32), batch.live_mask(), dt.INT32)


# ---------------------------------------------------------------------------
# xxhash64 (Spark's XxHash64 expression; JNI Hash.xxhash64 in the reference)
# ---------------------------------------------------------------------------

_P1 = np.uint64(0x9E3779B185EBCA87)
_P2 = np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = np.uint64(0x165667B19E3779F9)
_P4 = np.uint64(0x85EBCA77C2B2AE63)
_P5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r: int):
    return (x << r) | (x >> (64 - r))


def _xx_fmix(h):
    h = h ^ (h >> 33)
    h = h * _P2
    h = h ^ (h >> 29)
    h = h * _P3
    return h ^ (h >> 32)


def _xx_hash_long(v_u64, seed_u64):
    h = seed_u64 + _P5 + jnp.uint64(8)
    k = _rotl64(v_u64 * _P2, 31) * _P1
    h = h ^ k
    h = _rotl64(h, 27) * _P1 + _P4
    return _xx_fmix(h)


def _xx_hash_int(v_u32, seed_u64):
    """Spark XxHash64.hashInt: the 4-byte tail path of xxhash64."""
    h = seed_u64 + _P5 + jnp.uint64(4)
    h = h ^ (v_u32.astype(jnp.uint64) * _P1)
    h = _rotl64(h, 23) * _P2 + _P3
    return _xx_fmix(h)


def xxhash64_column(col: Column, seed) -> jnp.ndarray:
    if isinstance(col, StringColumn):
        raise TypeError("xxhash64 on strings lands with the regex/unicode work")
    d = col.data
    t = col.dtype
    if isinstance(t, dt.BooleanType):
        # Spark hashes booleans through hashInt(0/1)
        h = _xx_hash_int(d.astype(jnp.uint32), seed)
    elif t in (dt.INT8, dt.INT16, dt.INT32) or isinstance(t, dt.DateType):
        h = _xx_hash_int(d.astype(jnp.int64).astype(jnp.uint32), seed)
    elif t == dt.INT64 or isinstance(t, (dt.TimestampType, dt.DecimalType)):
        h = _xx_hash_long(bits.i64_to_u64(d.astype(jnp.int64)), seed)
    elif t == dt.FLOAT32:
        h = _xx_hash_int(bits.f32_bits_u32(_normalize_float(d)), seed)
    elif t == dt.FLOAT64:
        h = _xx_hash_long(bits.f64_bits(_normalize_float(d)), seed)
    else:
        raise TypeError(f"xxhash64 unsupported for {t}")
    return jnp.where(col.validity, h, seed)


class XxHash64(Expression):
    def __init__(self, *children: Expression, seed: int = 42):
        super().__init__(*children)
        self.seed = seed

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.INT64

    def nullable(self, schema: Schema) -> bool:
        return False

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        cols = [c.eval(batch) for c in self.children]
        h = jnp.full((batch.capacity,), self.seed, jnp.uint64)
        for c in cols:
            h = xxhash64_column(c, h)
        return make_result(bits.u64_to_i64(h), batch.live_mask(), dt.INT64)


class BloomFilterMightContain(Expression):
    """might_contain(bloom_filter, expr) over a host-built filter
    (GpuBloomFilterMightContain.scala). ``bits`` is the bool[num_bits]
    lane filter from ops/bloom.py build_bloom; null inputs yield null
    (Spark's contract), non-null inputs yield the probe result."""

    def __init__(self, child: Expression, bits):
        super().__init__(child)
        import numpy as _np
        self.bits = _np.asarray(bits, dtype=bool)

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        from ..ops import bloom as B
        c = self.children[0].eval(batch)
        hit = B.might_contain(jnp.asarray(self.bits), [c])
        return make_result(hit, c.validity & batch.live_mask(), dt.BOOL)

    def __repr__(self):
        return f"might_contain(<{self.bits.shape[0]} bits>, " \
               f"{self.children[0]!r})"
