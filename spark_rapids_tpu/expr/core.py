"""Expression IR core.

TPU-native analogue of the reference's expression layer: where the
reference wraps Catalyst Expressions in BaseExprMeta and lowers each to a
cuDF ColumnVector call (RapidsMeta.scala:1030, per-expression GpuExpression
impls across sql-plugin), here an Expression tree lowers directly to
jax.numpy ops over ColumnVector/StringColumn buffers. An entire operator's
expression set evaluates inside one jax.jit trace, so XLA fuses the whole
expression DAG into a handful of TPU kernels — the "one JNI call per
expression" hot loop of the reference (SURVEY §3.3) simply does not exist
here.

Null semantics are SQL three-valued logic carried in the validity mask:
- most scalar functions: result null iff any input null,
- AND/OR use Kleene logic (predicates.py),
- data lanes under a null are zeroed so downstream kernels never see
  garbage (the invariant established in columnar/vector.py).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import Column, ColumnVector, ColumnarBatch, StringColumn

Schema = Sequence  # [(name, DType), ...]


class Expression:
    """Base expression node. Immutable; children in ``children``."""

    def __init__(self, *children: "Expression"):
        self.children: List[Expression] = list(children)

    # --- planning-time ---
    def data_type(self, schema: Schema) -> dt.DType:
        raise NotImplementedError

    def nullable(self, schema: Schema) -> bool:
        return True

    def references(self) -> set:
        refs = set()
        for c in self.children:
            refs |= c.references()
        return refs

    # --- execution-time (inside jit) ---
    def eval(self, batch: ColumnarBatch) -> Column:
        raise NotImplementedError

    # --- sugar for building trees (mirrors Spark's Column DSL) ---
    def __add__(self, other):
        from .arithmetic import Add
        return Add(self, _lit(other))

    def __radd__(self, other):
        from .arithmetic import Add
        return Add(_lit(other), self)

    def __sub__(self, other):
        from .arithmetic import Subtract
        return Subtract(self, _lit(other))

    def __rsub__(self, other):
        from .arithmetic import Subtract
        return Subtract(_lit(other), self)

    def __mul__(self, other):
        from .arithmetic import Multiply
        return Multiply(self, _lit(other))

    def __rmul__(self, other):
        from .arithmetic import Multiply
        return Multiply(_lit(other), self)

    def __truediv__(self, other):
        from .arithmetic import Divide
        return Divide(self, _lit(other))

    def __mod__(self, other):
        from .arithmetic import Remainder
        return Remainder(self, _lit(other))

    def __neg__(self):
        from .arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __eq__(self, other):  # type: ignore[override]
        from .predicates import EqualTo
        return EqualTo(self, _lit(other))

    def __ne__(self, other):  # type: ignore[override]
        from .predicates import Not, EqualTo
        return Not(EqualTo(self, _lit(other)))

    def __lt__(self, other):
        from .predicates import LessThan
        return LessThan(self, _lit(other))

    def __le__(self, other):
        from .predicates import LessThanOrEqual
        return LessThanOrEqual(self, _lit(other))

    def __gt__(self, other):
        from .predicates import GreaterThan
        return GreaterThan(self, _lit(other))

    def __ge__(self, other):
        from .predicates import GreaterThanOrEqual
        return GreaterThanOrEqual(self, _lit(other))

    def __and__(self, other):
        from .predicates import And
        return And(self, _lit(other))

    def __or__(self, other):
        from .predicates import Or
        return Or(self, _lit(other))

    def __invert__(self):
        from .predicates import Not
        return Not(self)

    def __hash__(self):
        return id(self)

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def cast(self, to: dt.DType) -> "Expression":
        from .cast import Cast
        return Cast(self, to)

    def is_null(self):
        from .predicates import IsNull
        return IsNull(self)

    def is_not_null(self):
        from .predicates import IsNotNull
        return IsNotNull(self)

    def isin(self, *values):
        from .predicates import InSet
        return InSet(self, list(values))

    def between(self, lo, hi):
        return (self >= lo) & (self <= hi)

    def __repr__(self):
        args = ", ".join(repr(c) for c in self.children)
        return f"{type(self).__name__}({args})"


def _lit(v):
    if isinstance(v, Expression):
        return v
    return Literal(v)


class ColumnRef(Expression):
    """Reference to a named input column (Catalyst AttributeReference)."""

    def __init__(self, name: str):
        super().__init__()
        self.name = name

    def data_type(self, schema: Schema) -> dt.DType:
        for n, t in schema:
            if n == self.name:
                return t
        raise KeyError(f"column {self.name!r} not in schema {[n for n, _ in schema]}")

    def references(self) -> set:
        return {self.name}

    def eval(self, batch: ColumnarBatch) -> Column:
        return batch.column(self.name)

    def __repr__(self):
        return f"col({self.name!r})"


def col(name: str) -> ColumnRef:
    return ColumnRef(name)


def _infer_literal_dtype(value) -> dt.DType:
    if value is None:
        return dt.NULL
    if isinstance(value, bool):
        return dt.BOOL
    if isinstance(value, int):
        if -(2**31) <= value < 2**31:
            return dt.INT32
        if -(2**63) <= value < 2**63:
            return dt.INT64
        # Spark types integral literals beyond long as DecimalType
        # (Literal.apply on BigInt/BigDecimal); beyond 38 digits Spark
        # fails analysis (DECIMAL_PRECISION_EXCEEDED) — mirror that
        # rather than silently clamping to an unrepresentable type
        digits = len(str(abs(value)))
        if digits > 38:
            raise TypeError(
                f"integral literal needs precision {digits} > 38")
        return dt.DecimalType(digits, 0)
    if isinstance(value, float):
        return dt.FLOAT64
    if isinstance(value, str):
        return dt.STRING
    import datetime
    if isinstance(value, datetime.datetime):
        return dt.TIMESTAMP
    if isinstance(value, datetime.date):
        return dt.DATE
    import decimal
    if isinstance(value, decimal.Decimal):
        exp = -value.as_tuple().exponent
        digits = len(value.as_tuple().digits)
        return dt.DecimalType(max(digits, exp + 1), max(exp, 0))
    raise TypeError(f"cannot make literal from {type(value)}")


class Literal(Expression):
    """A scalar constant, broadcast to the batch capacity at eval.

    XLA constant-folds and fuses the broadcast, so unlike cuDF Scalars
    there is no per-literal device allocation.
    """

    def __init__(self, value, dtype: Optional[dt.DType] = None):
        super().__init__()
        self.value = value
        self.dtype = dtype or _infer_literal_dtype(value)

    def data_type(self, schema: Schema) -> dt.DType:
        return self.dtype

    def nullable(self, schema: Schema) -> bool:
        return self.value is None

    def eval(self, batch: ColumnarBatch) -> Column:
        cap = batch.capacity
        live = batch.live_mask()
        if self.value is None:
            if self.dtype == dt.STRING:
                return StringColumn(jnp.zeros(cap + 1, jnp.int32),
                                    jnp.zeros(8, jnp.uint8),
                                    jnp.zeros(cap, jnp.bool_),
                                    pad_bucket=8)
            phys = self.dtype.physical or jnp.int32
            return ColumnVector(jnp.zeros(cap, phys), jnp.zeros(cap, jnp.bool_),
                                self.dtype if self.dtype != dt.NULL else dt.INT32)
        if self.dtype == dt.STRING:
            from ..columnar.vector import round_pow2
            raw = str(self.value).encode("utf-8")
            n = len(raw)
            pad = round_pow2(n)
            offsets = jnp.arange(cap + 1, dtype=jnp.int32) * n
            chars = jnp.tile(jnp.frombuffer(raw, dtype=jnp.uint8) if n else
                             jnp.zeros(1, jnp.uint8), max(cap, 1))
            return StringColumn(offsets, chars, live, pad_bucket=pad)
        phys = self.dtype.physical
        value = self.value
        if isinstance(self.dtype, dt.DecimalType):
            import decimal
            value = int(decimal.Decimal(value).scaleb(self.dtype.scale).to_integral_value())
            if self.dtype.is_wide:
                from ..columnar.decimal128 import Decimal128Column
                hi = jnp.full(cap, value >> 64, jnp.int64)
                lo = jnp.full(cap, value & ((1 << 64) - 1), jnp.uint64)
                z64, zu = jnp.zeros((), jnp.int64), jnp.zeros((), jnp.uint64)
                return Decimal128Column(jnp.where(live, hi, z64),
                                        jnp.where(live, lo, zu),
                                        live, self.dtype)
        data = jnp.full(cap, self.physical_value(), phys)
        return ColumnVector(jnp.where(live, data, jnp.zeros((), phys)), live, self.dtype)

    def physical_value(self):
        """The scalar as its physical lane holds it (temporal values as
        epoch micros / days)."""
        import datetime
        value = self.value
        if isinstance(value, datetime.datetime):
            return int(value.replace(tzinfo=datetime.timezone.utc).timestamp() * 1_000_000)
        if isinstance(value, datetime.date):
            return (value - datetime.date(1970, 1, 1)).days
        return value

    def __repr__(self):
        return f"lit({self.value!r})"


def lit(value, dtype: Optional[dt.DType] = None) -> Literal:
    return Literal(value, dtype)


class Alias(Expression):
    """Named output expression (Catalyst Alias)."""

    def __init__(self, child: Expression, name: str):
        super().__init__(child)
        self.name = name

    def data_type(self, schema: Schema) -> dt.DType:
        return self.children[0].data_type(schema)

    def nullable(self, schema: Schema) -> bool:
        return self.children[0].nullable(schema)

    def eval(self, batch: ColumnarBatch) -> Column:
        return self.children[0].eval(batch)

    def __repr__(self):
        return f"{self.children[0]!r}.alias({self.name!r})"


def output_name(expr: Expression, index: int) -> str:
    """Output column name for a projection list entry."""
    if isinstance(expr, Alias):
        return expr.name
    if isinstance(expr, ColumnRef):
        return expr.name
    return f"_c{index}"


# ---------------------------------------------------------------------------
# Helpers shared by concrete expression modules
# ---------------------------------------------------------------------------

def numeric_result(*cols: ColumnVector) -> dt.DType:
    out = cols[0].dtype
    for c in cols[1:]:
        out = dt.promote(out, c.dtype)
    return out


def merged_validity(*cols: Column):
    v = cols[0].validity
    for c in cols[1:]:
        v = v & c.validity
    return v


def make_result(data, validity, dtype: dt.DType) -> ColumnVector:
    """Standard result construction: zero data lanes under nulls."""
    if data.dtype == jnp.bool_:
        # mask algebra, not a select: Mosaic refuses a select between
        # bool vectors (it reaches it as an i8->i1 truncation)
        data = data & validity
    else:
        data = jnp.where(validity, data, jnp.zeros((), data.dtype))
    return ColumnVector(data, validity, dtype)
