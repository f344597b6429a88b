"""Predicates and comparisons.

Reference surface: sql-plugin/.../org/apache/spark/sql/rapids/predicates.scala
and nullExpressions.scala. Comparisons follow Spark semantics: NaN compares
greater than everything and equal to itself (normalized NaN ordering, see
SURVEY §7 hard-part #6); AND/OR use Kleene three-valued logic; string
comparisons lower to byte-lexicographic compare on the fixed-width padded
view (columnar/vector.py StringColumn.padded).
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import Column, ColumnVector, ColumnarBatch, StringColumn
from .core import Expression, Schema, make_result, merged_validity


def _padded_pair(a: StringColumn, b: StringColumn):
    wa, wb = a.pad_bucket, b.pad_bucket
    pa, pb = a.padded(), b.padded()
    w = max(wa, wb)
    if wa < w:
        pa = jnp.pad(pa, ((0, 0), (0, w - wa)))
    if wb < w:
        pb = jnp.pad(pb, ((0, 0), (0, w - wb)))
    return pa, pb


def string_eq(a: StringColumn, b: StringColumn):
    pa, pb = _padded_pair(a, b)
    return jnp.all(pa == pb, axis=1) & (a.lengths() == b.lengths())


def string_lt(a: StringColumn, b: StringColumn):
    """Byte-lexicographic a < b (UTF-8 byte order == Spark string order)."""
    pa, pb = _padded_pair(a, b)
    diff = pa != pb
    any_diff = jnp.any(diff, axis=1)
    first = jnp.argmax(diff, axis=1)
    rows = jnp.arange(pa.shape[0])
    a_byte = pa[rows, first].astype(jnp.int32)
    b_byte = pb[rows, first].astype(jnp.int32)
    # padded() zero-fills past each string's length, and 0 sorts before any
    # UTF-8 byte, so prefix ordering falls out of the byte compare.
    return jnp.where(any_diff, a_byte < b_byte, False)


def _wide_cmp_lanes(left, right):
    """(lt, eq) lane pairs for comparisons involving a decimal128
    column: both sides lifted to limbs at the common scale. Lanes whose
    scale-up overflows 128 bits compare via the float64 approximation
    instead (only reachable at extreme scale gaps)."""
    from ..columnar import decimal128 as d128
    ls = left.dtype.scale if isinstance(left.dtype, dt.DecimalType) else 0
    rs = right.dtype.scale if isinstance(right.dtype, dt.DecimalType) else 0
    s = max(ls, rs)

    def lift(col, scale):
        if isinstance(col.dtype, dt.DecimalType):
            hi, lo = d128.limbs_of(col)
        else:
            hi, lo = d128.d128_from_i64(col.data.astype(jnp.int64))
        approx = d128.d128_to_f64(hi, lo) / (10.0 ** scale)
        hi, lo, ovf = d128.d128_mul_pow10(hi, lo, s - scale)
        return hi, lo, ovf, approx

    ah, al, o1, fa = lift(left, ls)
    bh, bl, o2, fb = lift(right, rs)
    any_ovf = o1 | o2
    lt_exact = d128.d128_lt(ah, al, bh, bl)
    eq_exact = d128.d128_eq(ah, al, bh, bl)
    lt = jnp.where(any_ovf, fa < fb, lt_exact)
    eq = jnp.where(any_ovf, fa == fb, eq_exact)
    return lt, eq


def _is_wide_col(col) -> bool:
    from ..columnar.decimal128 import Decimal128Column
    return isinstance(col, Decimal128Column)


class BinaryComparison(Expression):
    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        left = self.children[0].eval(batch)
        right = self.children[1].eval(batch)
        validity = merged_validity(left, right)
        if isinstance(left, StringColumn) or isinstance(right, StringColumn):
            data = self._compare_strings(left, right)
        elif _is_wide_col(left) or _is_wide_col(right):
            other = right if _is_wide_col(left) else left
            if not isinstance(other.dtype, dt.DecimalType) and \
                    other.dtype.is_floating:
                from ..columnar import decimal128 as d128

                def as_f64(c):
                    if _is_wide_col(c):
                        return d128.d128_to_f64(c.hi, c.lo) / \
                            (10.0 ** c.dtype.scale)
                    return c.data.astype(jnp.float64)
                data = self._compare(as_f64(left), as_f64(right))
            else:
                lt, eq = _wide_cmp_lanes(left, right)
                data = self._compare128(lt, eq)
        else:
            a, b = self._aligned(left, right)
            data = self._compare(a, b)
        return make_result(data, validity, dt.BOOL)

    def _compare128(self, lt, eq):
        raise NotImplementedError

    @staticmethod
    def _aligned(left, right):
        """Physical lanes made directly comparable (decimal scales aligned)."""
        a, b = left.data, right.data
        lt, rt = left.dtype, right.dtype
        l_dec = isinstance(lt, dt.DecimalType)
        r_dec = isinstance(rt, dt.DecimalType)
        if l_dec or r_dec:
            if (not l_dec and lt.is_floating) or (not r_dec and rt.is_floating):
                # decimal vs float: compare as doubles
                a = a.astype(jnp.float64) / (10.0 ** lt.scale if l_dec else 1.0)
                b = b.astype(jnp.float64) / (10.0 ** rt.scale if r_dec else 1.0)
                return a, b
            ls = lt.scale if l_dec else 0
            rs = rt.scale if r_dec else 0
            s = max(ls, rs)
            a = a.astype(jnp.int64) * (10 ** (s - ls))
            b = b.astype(jnp.int64) * (10 ** (s - rs))
            return a, b
        if a.dtype != b.dtype:
            out_t = dt.promote(lt, rt)
            a = a.astype(out_t.physical)
            b = b.astype(out_t.physical)
        return a, b

    def _compare(self, a, b):
        raise NotImplementedError

    def _compare_strings(self, a, b):
        raise TypeError(f"{type(self).__name__} unsupported on strings")


def _nan_safe_lt(a, b):
    """a < b with NaN greatest (Spark ordering)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        # pure mask algebra (not where(nan, False, ...)): a select over
        # bool constants reaches Mosaic as an i8->i1 truncation it refuses
        return ~jnp.isnan(a) & (jnp.isnan(b) | (a < b))
    return a < b


def _nan_safe_eq(a, b):
    if jnp.issubdtype(a.dtype, jnp.floating):
        both_nan = jnp.isnan(a) & jnp.isnan(b)
        return both_nan | (a == b)
    return a == b


class EqualTo(BinaryComparison):
    def _compare(self, a, b):
        return _nan_safe_eq(a, b)

    def _compare128(self, lt, eq):
        return eq

    def _compare_strings(self, a, b):
        return string_eq(a, b)


class LessThan(BinaryComparison):
    def _compare(self, a, b):
        return _nan_safe_lt(a, b)

    def _compare128(self, lt, eq):
        return lt

    def _compare_strings(self, a, b):
        return string_lt(a, b)


class GreaterThan(BinaryComparison):
    def _compare(self, a, b):
        return _nan_safe_lt(b, a)

    def _compare128(self, lt, eq):
        import jax.numpy as jnp
        return ~lt & ~eq

    def _compare_strings(self, a, b):
        return string_lt(b, a)


class LessThanOrEqual(BinaryComparison):
    def _compare(self, a, b):
        return ~_nan_safe_lt(b, a)

    def _compare128(self, lt, eq):
        return lt | eq

    def _compare_strings(self, a, b):
        return ~string_lt(b, a)


class GreaterThanOrEqual(BinaryComparison):
    def _compare(self, a, b):
        return ~_nan_safe_lt(a, b)

    def _compare128(self, lt, eq):
        return ~lt

    def _compare_strings(self, a, b):
        return ~string_lt(a, b)


class EqualNullSafe(Expression):
    """<=>: nulls compare equal; never returns null."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def nullable(self, schema: Schema) -> bool:
        return False

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        left = self.children[0].eval(batch)
        right = self.children[1].eval(batch)
        both_null = ~left.validity & ~right.validity
        both_valid = left.validity & right.validity
        if isinstance(left, StringColumn):
            eq = string_eq(left, right)
        elif _is_wide_col(left) or _is_wide_col(right):
            other = right if _is_wide_col(left) else left
            if not isinstance(other.dtype, dt.DecimalType) and \
                    other.dtype.is_floating:
                from ..columnar import decimal128 as d128

                def as_f64(c):
                    if _is_wide_col(c):
                        return d128.d128_to_f64(c.hi, c.lo) / \
                            (10.0 ** c.dtype.scale)
                    return c.data.astype(jnp.float64)
                eq = _nan_safe_eq(as_f64(left), as_f64(right))
            else:
                _, eq = _wide_cmp_lanes(left, right)
        else:
            eq = _nan_safe_eq(left.data, right.data)
        data = both_null | (both_valid & eq)
        return make_result(data, batch.live_mask(), dt.BOOL)


class And(Expression):
    """Kleene AND: false & null = false; true & null = null."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        l = self.children[0].eval(batch)
        r = self.children[1].eval(batch)
        lv, rv = l.validity, r.validity
        ld = l.data & lv  # null -> treated distinctly below
        rd = r.data & rv
        known_false = (lv & ~l.data) | (rv & ~r.data)
        data = l.data & r.data
        validity = (lv & rv) | known_false
        return make_result(data & ~known_false, validity, dt.BOOL)


class Or(Expression):
    """Kleene OR: true | null = true; false | null = null."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        l = self.children[0].eval(batch)
        r = self.children[1].eval(batch)
        lv, rv = l.validity, r.validity
        known_true = (lv & l.data) | (rv & r.data)
        validity = (lv & rv) | known_true
        return make_result(known_true | (l.data | r.data), validity, dt.BOOL)


class Not(Expression):
    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        c = self.children[0].eval(batch)
        return make_result(~c.data, c.validity, dt.BOOL)


class IsNull(Expression):
    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def nullable(self, schema: Schema) -> bool:
        return False

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        c = self.children[0].eval(batch)
        live = batch.live_mask()
        return make_result(~c.validity & live, live, dt.BOOL)


class IsNotNull(Expression):
    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def nullable(self, schema: Schema) -> bool:
        return False

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        c = self.children[0].eval(batch)
        return make_result(c.validity, batch.live_mask(), dt.BOOL)


class IsNaN(Expression):
    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        c = self.children[0].eval(batch)
        return make_result(jnp.isnan(c.data), c.validity, dt.BOOL)


class InSet(Expression):
    """expr IN (literal set) — GpuInSet equivalent."""

    def __init__(self, child: Expression, values: List):
        super().__init__(child)
        self.values = values

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.BOOL

    def eval(self, batch: ColumnarBatch) -> ColumnVector:
        from .core import Literal
        c = self.children[0].eval(batch)
        if isinstance(c, StringColumn):
            hit = jnp.zeros(batch.capacity, jnp.bool_)
            for v in self.values:
                lit_col = Literal(v).eval(batch)
                hit = hit | string_eq(c, lit_col)
            return make_result(hit, c.validity, dt.BOOL)
        vals = jnp.asarray(
            [v for v in self.values if v is not None], c.data.dtype)
        hit = jnp.any(c.data[:, None] == vals[None, :], axis=1) if vals.size else \
            jnp.zeros(batch.capacity, jnp.bool_)
        return make_result(hit, c.validity, dt.BOOL)
