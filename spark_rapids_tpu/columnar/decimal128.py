"""Two-limb int128 decimal column and arithmetic.

The device representation for DECIMAL(p>18): an unscaled 128-bit signed
integer split into ``hi`` (int64, sign-carrying) and ``lo`` (uint64)
limbs — the layout cuDF's DECIMAL128 columns use natively and the
reference leans on throughout (decimalExpressions.scala, GpuCast.scala
decimal paths, SURVEY §7 hard-part 6). TPU constraint: XLA's x64
rewriting has no 64-bit bitcast and no 128-bit integers, so every
operation here is built from wrapping 64-bit adds/multiplies and 32-bit
limb decompositions (utils/bits.py conventions).

Key ops: add/sub with carry, full 128x128 multiply (truncated, with
overflow detection), scale by 10^k, divide by 10^k with HALF_UP
rounding (chunked 32-bit schoolbook division so no intermediate exceeds
64 bits), comparisons, and precision-overflow checks against 10^p
bounds.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..jit_registry import read_back, read_int
from . import dtypes as dt

_U32 = np.uint64(0xFFFFFFFF)


def _u(x):
    return x.astype(jnp.uint64)


def _s(x):
    return x.astype(jnp.int64)


class Decimal128Column:
    """DECIMAL(p>18) column: hi:int64 + lo:uint64 unscaled limbs."""

    __slots__ = ("hi", "lo", "validity", "dtype")

    def __init__(self, hi: jax.Array, lo: jax.Array, validity: jax.Array,
                 dtype: dt.DecimalType):
        self.hi = hi
        self.lo = lo
        self.validity = validity
        self.dtype = dtype

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]

    def with_validity(self, validity: jax.Array) -> "Decimal128Column":
        return Decimal128Column(self.hi, self.lo, validity, self.dtype)

    def gather(self, indices: jax.Array,
               valid: Optional[jax.Array] = None) -> "Decimal128Column":
        safe = jnp.clip(indices, 0, self.capacity - 1)
        hi = jnp.take(self.hi, safe)
        lo = jnp.take(self.lo, safe)
        validity = jnp.take(self.validity, safe)
        if valid is not None:
            validity = validity & valid
            hi = jnp.where(validity, hi, jnp.zeros((), hi.dtype))
            lo = jnp.where(validity, lo, jnp.zeros((), lo.dtype))
        return Decimal128Column(hi, lo, validity, self.dtype)

    def to_numpy(self, num_rows: Optional[int] = None):
        n = self.capacity if num_rows is None else read_int(num_rows)
        hi = read_back(self.hi)[0][:n].astype(object)
        lo = read_back(self.lo)[0][:n].astype(object)
        vals = np.empty(n, dtype=object)
        for i in range(n):
            vals[i] = int(hi[i]) * (1 << 64) + int(lo[i])
        return vals, read_back(self.validity)[0][:n]

    def __repr__(self):
        return f"Decimal128Column({self.dtype}, capacity={self.capacity})"


def _d128_flatten(v: Decimal128Column):
    return (v.hi, v.lo, v.validity), v.dtype


def _d128_unflatten(dtype, children):
    return Decimal128Column(*children, dtype=dtype)


jax.tree_util.register_pytree_node(Decimal128Column, _d128_flatten,
                                   _d128_unflatten)


# ---------------------------------------------------------------------------
# limb arithmetic ((hi:int64, lo:uint64) pairs; wrapping semantics)
# ---------------------------------------------------------------------------

def d128_from_i64(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sign-extend an int64 into two limbs."""
    return jnp.where(x < 0, jnp.int64(-1), jnp.int64(0)), _u(x)


def d128_add(ah, al, bh, bl):
    lo = al + bl  # wrapping uint64
    carry = (lo < al).astype(jnp.int64)
    hi = ah + bh + carry
    return hi, lo


def d128_neg(h, l):
    nl = (~l) + jnp.uint64(1)
    nh = (~h) + jnp.where(nl == 0, jnp.int64(1), jnp.int64(0))
    return nh, nl


def d128_sub(ah, al, bh, bl):
    nh, nl = d128_neg(bh, bl)
    return d128_add(ah, al, nh, nl)


def d128_abs(h, l):
    neg = h < 0
    nh, nl = d128_neg(h, l)
    return jnp.where(neg, nh, h), jnp.where(neg, nl, l)


def d128_lt(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al < bl))


def d128_eq(ah, al, bh, bl):
    return (ah == bh) & (al == bl)


def _mul_u64(a, b):
    """Full 64x64 -> 128 unsigned multiply via 32-bit limbs."""
    a0, a1 = a & _U32, a >> jnp.uint64(32)
    b0, b1 = b & _U32, b >> jnp.uint64(32)
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> jnp.uint64(32)) + (p01 & _U32) + (p10 & _U32)
    lo = (p00 & _U32) | (mid << jnp.uint64(32))
    hi = p11 + (p01 >> jnp.uint64(32)) + (p10 >> jnp.uint64(32)) + \
        (mid >> jnp.uint64(32))
    return hi, lo


def d128_mul(ah, al, bh, bl):
    """Signed 128x128 multiply, truncated to 128 bits, with an overflow
    flag (true when the mathematical product does not fit in 128 bits).
    Operates on magnitudes, reapplies sign — overflow detection is then
    a check on the high magnitude limbs."""
    sa, sb = ah < 0, bh < 0
    ah1, al1 = d128_abs(ah, al)
    bh1, bl1 = d128_abs(bh, bl)
    uah, ubh = _u(ah1), _u(bh1)
    # |a| * |b| = (ah*2^64 + al)(bh*2^64 + bl)
    p_hi, p_lo = _mul_u64(al1, bl1)          # al*bl -> (hi, lo)
    cross1 = uah * bl1                        # wraps; overflow checked below
    cross2 = ubh * al1
    hi = p_hi + cross1 + cross2
    # overflow if: both highs nonzero, or cross terms overflow 64 bits,
    # or result hi exceeds the signed-positive range
    c1h, _ = _mul_u64(uah, bl1)
    c2h, _ = _mul_u64(ubh, al1)
    overflow = (uah != 0) & (ubh != 0)
    overflow |= (c1h != 0) | (c2h != 0)
    overflow |= (hi < p_hi)  # wrapped on accumulate (approximate)
    neg = sa ^ sb
    nh, nl = d128_neg(_s(hi), p_lo)
    rh = jnp.where(neg, nh, _s(hi))
    rl = jnp.where(neg, nl, p_lo)
    overflow |= (_s(hi) < 0)  # magnitude spilled into the sign bit
    return rh, rl, overflow


_POW10_U64 = [10 ** k for k in range(20)]


def d128_mul_pow10(h, l, k: int):
    """(h, l) * 10^k, k static >= 0; overflow flag like d128_mul."""
    overflow = jnp.zeros(h.shape, jnp.bool_)
    while k > 0:
        step = min(k, 18)
        m = jnp.uint64(_POW10_U64[step])
        sa = h < 0
        h1, l1 = d128_abs(h, l)
        phi, plo = _mul_u64(l1, m)
        cross = _u(h1) * m
        chk, _ = _mul_u64(_u(h1), m)
        hi = phi + cross
        overflow |= (chk != 0) | (hi < phi) | (_s(hi) < 0)
        nh, nl = d128_neg(_s(hi), plo)
        h = jnp.where(sa, nh, _s(hi))
        l = jnp.where(sa, nl, plo)
        k -= step
    return h, l, overflow


def _divmod_small(h, l, d: int):
    """Unsigned (h:uint64, l:uint64) // d for d < 2^31, via 32-bit
    schoolbook division (no intermediate exceeds 64 bits)."""
    dd = jnp.uint64(d)
    limbs = [h >> jnp.uint64(32), h & _U32, l >> jnp.uint64(32), l & _U32]
    rem = jnp.zeros(h.shape, jnp.uint64)
    qs = []
    for limb in limbs:
        cur = (rem << jnp.uint64(32)) | limb
        q = cur // dd
        rem = cur - q * dd
        qs.append(q & _U32)
    qh = (qs[0] << jnp.uint64(32)) | qs[1]
    ql = (qs[2] << jnp.uint64(32)) | qs[3]
    return qh, ql, rem


def d128_div_pow10_half_up(h, l, k: int):
    """(h, l) / 10^k with HALF_UP rounding, k static >= 0."""
    if k == 0:
        return h, l
    neg = h < 0
    mh, ml = d128_abs(h, l)
    uh, ul = _u(mh), _u(ml)
    # add 10^k / 2 for HALF_UP before truncating division
    half = 10 ** k // 2
    add_h = jnp.uint64(half >> 64)
    add_l = jnp.uint64(half & ((1 << 64) - 1))
    nl = ul + add_l
    carry = (nl < ul).astype(jnp.uint64)
    nh = uh + add_h + carry
    uh, ul = nh, nl
    kk = k
    while kk > 0:
        step = min(kk, 9)
        uh, ul, _ = _divmod_small(uh, ul, 10 ** step)
        kk -= step
    rh, rl = _s(uh), ul
    nh2, nl2 = d128_neg(rh, rl)
    return jnp.where(neg, nh2, rh), jnp.where(neg, nl2, rl)


def d128_div_pow10_trunc(h, l, k: int):
    """(h, l) / 10^k truncating toward zero, k static >= 0."""
    if k == 0:
        return h, l
    neg = h < 0
    mh, ml = d128_abs(h, l)
    uh, ul = _u(mh), _u(ml)
    kk = k
    while kk > 0:
        step = min(kk, 9)
        uh, ul, _ = _divmod_small(uh, ul, 10 ** step)
        kk -= step
    rh, rl = _s(uh), ul
    nh2, nl2 = d128_neg(rh, rl)
    return jnp.where(neg, nh2, rh), jnp.where(neg, nl2, rl)


def _u128_ge(ah, al, bh, bl):
    """Unsigned (ah,al) >= (bh,bl); all uint64."""
    return (ah > bh) | ((ah == bh) & (al >= bl))


def _u128_sub(ah, al, bh, bl):
    lo = al - bl
    borrow = (al < bl).astype(jnp.uint64)
    return ah - bh - borrow, lo


def d128_divmod_u(nh, nl, dh, dl):
    """Unsigned 128/128 long division: returns (qh, ql, rh, rl), all
    uint64. Division by zero yields garbage — callers must mask.

    Shift-subtract restoring division, 128 fixed iterations under
    ``lax.fori_loop`` — data-independent control flow, so XLA compiles
    one small loop body instead of a 128-step unrolled graph."""
    zero = jnp.zeros_like(nh)

    def body(i, st):
        qh, ql, rh, rl = st
        k = jnp.uint64(127) - jnp.uint64(i)
        # bit k of the dividend
        bit = jnp.where(
            k >= 64,
            (nh >> jnp.where(k >= 64, k - jnp.uint64(64), jnp.uint64(0)))
            & jnp.uint64(1),
            (nl >> jnp.where(k >= 64, jnp.uint64(0), k)) & jnp.uint64(1))
        # remainder <<= 1 | bit
        rh = (rh << jnp.uint64(1)) | (rl >> jnp.uint64(63))
        rl = (rl << jnp.uint64(1)) | bit
        ge = _u128_ge(rh, rl, dh, dl)
        sh, sl = _u128_sub(rh, rl, dh, dl)
        rh = jnp.where(ge, sh, rh)
        rl = jnp.where(ge, sl, rl)
        qbit = ge.astype(jnp.uint64)
        qh = qh | jnp.where(
            k >= 64,
            qbit << jnp.where(k >= 64, k - jnp.uint64(64), jnp.uint64(0)),
            jnp.uint64(0))
        ql = ql | jnp.where(
            k >= 64, jnp.uint64(0),
            qbit << jnp.where(k >= 64, jnp.uint64(0), k))
        return qh, ql, rh, rl

    qh, ql, rh, rl = jax.lax.fori_loop(
        0, 128, body, (zero, zero, zero, zero))
    return qh, ql, rh, rl


def d128_div_trunc(ah, al, bh, bl):
    """Signed truncating 128/128 divide; returns (q_hi, q_lo, r_hi,
    r_lo) with the remainder taking the dividend's sign (Java %)."""
    qneg = (ah < 0) ^ (bh < 0)
    rneg = ah < 0
    mah, mal = d128_abs(ah, al)
    mbh, mbl = d128_abs(bh, bl)
    qh, ql, rh, rl = d128_divmod_u(_u(mah), _u(mal), _u(mbh), _u(mbl))
    sqh, sql = _s(qh), ql
    srh, srl = _s(rh), rl
    nqh, nql = d128_neg(sqh, sql)
    nrh, nrl = d128_neg(srh, srl)
    return (jnp.where(qneg, nqh, sqh), jnp.where(qneg, nql, sql),
            jnp.where(rneg, nrh, srh), jnp.where(rneg, nrl, srl))


# ---------------------------------------------------------------------------
# 256-bit intermediates (Spark-exact wide multiply / divide)
#
# decimal(38)*decimal(38) products and scaled-up division numerators
# exceed 128 bits before the result scale is applied — the reference
# leans on cuDF's __int128/256-bit fixed-point paths for the same reason
# (decimalExpressions.scala, GpuDecimalMultiply/GpuDecimalDivide). Here a
# 256-bit magnitude is four uint64 limbs, little-endian.
# ---------------------------------------------------------------------------

def _mul_u128_to_256(ah, al, bh, bl):
    """Unsigned 128x128 -> 256-bit product as 4 uint64 limbs (LE)."""
    p0h, p0l = _mul_u64(al, bl)          # al*bl -> limbs 0,1
    p1h, p1l = _mul_u64(al, bh)          # -> limbs 1,2
    p2h, p2l = _mul_u64(ah, bl)          # -> limbs 1,2
    p3h, p3l = _mul_u64(ah, bh)          # -> limbs 2,3
    w0 = p0l
    w1 = p0h + p1l
    c1 = (w1 < p0h).astype(jnp.uint64)
    w1b = w1 + p2l
    c1 = c1 + (w1b < w1).astype(jnp.uint64)
    w2 = p1h + p2h
    c2 = (w2 < p1h).astype(jnp.uint64)
    w2b = w2 + p3l
    c2 = c2 + (w2b < w2).astype(jnp.uint64)
    w2c = w2b + c1
    c2 = c2 + (w2c < w2b).astype(jnp.uint64)
    w3 = p3h + c2
    return w0, w1b, w2c, w3


def _d256_divmod_small(limbs, d: int):
    """(4xuint64 LE) // d for d < 2^31 via 32-bit schoolbook division.
    Returns (quotient limbs, remainder)."""
    dd = jnp.uint64(d)
    w0, w1, w2, w3 = limbs
    chunks = []
    for w in (w3, w2, w1, w0):
        chunks.extend([w >> jnp.uint64(32), w & _U32])
    rem = jnp.zeros(w0.shape, jnp.uint64)
    qs = []
    for c in chunks:
        cur = (rem << jnp.uint64(32)) | c
        q = cur // dd
        rem = cur - q * dd
        qs.append(q & _U32)
    out = []
    for i in (3, 2, 1, 0):
        out.append((qs[2 * i] << jnp.uint64(32)) | qs[2 * i + 1])
    return tuple(out), rem


def _d256_add_small(limbs, const: int):
    """Add a python-int constant (< 2^256) to a 256-bit magnitude."""
    out = []
    carry = jnp.zeros(limbs[0].shape, jnp.uint64)
    for i, w in enumerate(limbs):
        a = jnp.uint64((const >> (64 * i)) & ((1 << 64) - 1))
        r = w + a
        c_new = (r < w).astype(jnp.uint64)
        r2 = r + carry
        c_new = c_new + (r2 < carry).astype(jnp.uint64)
        out.append(r2)
        carry = c_new
    return tuple(out)


def d256_div_pow10_half_up(limbs, k: int):
    """256-bit magnitude / 10^k with HALF_UP rounding."""
    if k == 0:
        return limbs
    limbs = _d256_add_small(limbs, 10 ** k // 2)
    kk = k
    while kk > 0:
        step = min(kk, 9)
        limbs, _ = _d256_divmod_small(limbs, 10 ** step)
        kk -= step
    return limbs


def _d256_mul_small(limbs, m: int):
    """256-bit magnitude * m (m < 2^31). Returns (limbs, overflow)."""
    mm = jnp.uint64(m)
    w0, w1, w2, w3 = limbs
    chunks = []
    for w in (w0, w1, w2, w3):
        chunks.extend([w & _U32, w >> jnp.uint64(32)])
    carry = jnp.zeros(w0.shape, jnp.uint64)
    outc = []
    for c in chunks:
        cur = c * mm + carry
        outc.append(cur & _U32)
        carry = cur >> jnp.uint64(32)
    out = tuple((outc[2 * i + 1] << jnp.uint64(32)) | outc[2 * i]
                for i in range(4))
    return out, carry != 0


def d256_mul_pow10(limbs, k: int):
    """256-bit magnitude * 10^k with overflow detection."""
    overflow = jnp.zeros(limbs[0].shape, jnp.bool_)
    while k > 0:
        step = min(k, 9)
        limbs, o = _d256_mul_small(limbs, 10 ** step)
        overflow |= o
        k -= step
    return limbs, overflow


def d256_fits_128(limbs):
    """Magnitude fits a signed 128-bit value (< 2^127)."""
    w0, w1, w2, w3 = limbs
    return (w2 == 0) & (w3 == 0) & ((w1 >> jnp.uint64(63)) == 0)


def d256_divmod_u128(n_limbs, dh, dl):
    """Unsigned 256-bit / 128-bit long division. Returns (overflow,
    qh, ql, rh, rl): ``overflow`` is set when the quotient exceeds 128
    bits. Division by zero yields garbage — callers must mask."""
    w0, w1, w2, w3 = n_limbs
    zero = jnp.zeros_like(w0)

    def bit_of(k):
        """bit k (0..255) of the 256-bit dividend; k traced uint64."""
        limb_idx = k >> jnp.uint64(6)
        sh = k & jnp.uint64(63)
        v0 = (w0 >> sh) & jnp.uint64(1)
        v1 = (w1 >> sh) & jnp.uint64(1)
        v2 = (w2 >> sh) & jnp.uint64(1)
        v3 = (w3 >> sh) & jnp.uint64(1)
        return jnp.where(limb_idx == 0, v0,
                         jnp.where(limb_idx == 1, v1,
                                   jnp.where(limb_idx == 2, v2, v3)))

    def body(i, st):
        qh, ql, rh, rl, ovf = st
        k = jnp.uint64(255) - jnp.uint64(i)
        bit = bit_of(k)
        rh = (rh << jnp.uint64(1)) | (rl >> jnp.uint64(63))
        rl = (rl << jnp.uint64(1)) | bit
        ge = _u128_ge(rh, rl, dh, dl)
        sh_, sl_ = _u128_sub(rh, rl, dh, dl)
        rh = jnp.where(ge, sh_, rh)
        rl = jnp.where(ge, sl_, rl)
        # shift a new bit into the quotient; anything pushed past bit
        # 127 is overflow
        ovf = ovf | ((qh >> jnp.uint64(63)) & jnp.uint64(1)).astype(jnp.bool_)
        qh = (qh << jnp.uint64(1)) | (ql >> jnp.uint64(63))
        ql = (ql << jnp.uint64(1)) | ge.astype(jnp.uint64)
        return qh, ql, rh, rl, ovf

    qh, ql, rh, rl, ovf = jax.lax.fori_loop(
        0, 256, body, (zero, zero, zero, zero,
                       jnp.zeros(w0.shape, jnp.bool_)))
    return ovf, qh, ql, rh, rl


def d128_mul_exact(ah, al, bh, bl, drop_scale: int):
    """Spark-exact wide multiply: |a|*|b| in 256 bits, divide by
    10^drop_scale with HALF_UP, reapply sign. Returns (hi, lo,
    overflow) where overflow = the rounded product exceeds 128 bits."""
    neg = (ah < 0) ^ (bh < 0)
    mah, mal = d128_abs(ah, al)
    mbh, mbl = d128_abs(bh, bl)
    limbs = _mul_u128_to_256(_u(mah), _u(mal), _u(mbh), _u(mbl))
    limbs = d256_div_pow10_half_up(limbs, drop_scale)
    ok = d256_fits_128(limbs)
    w0, w1 = limbs[0], limbs[1]
    sh, sl = _s(w1), w0
    nh, nl = d128_neg(sh, sl)
    return jnp.where(neg, nh, sh), jnp.where(neg, nl, sl), ~ok


def d128_div_exact(ah, al, bh, bl, up_scale: int):
    """Spark-exact wide divide: (|a| * 10^up_scale) / |b| with HALF_UP
    rounding via 256-bit numerator. Returns (hi, lo, overflow);
    division by zero must be masked by the caller."""
    neg = (ah < 0) ^ (bh < 0)
    mah, mal = d128_abs(ah, al)
    mbh, mbl = d128_abs(bh, bl)
    k0 = min(up_scale, 38)
    ph, pl = _pow10_limbs(k0)
    n_limbs = _mul_u128_to_256(_u(mah), _u(mal),
                               jnp.full(ah.shape, np.uint64(ph)),
                               jnp.full(ah.shape, np.uint64(pl)))
    num_ovf = jnp.zeros(ah.shape, jnp.bool_)
    if up_scale > k0:
        n_limbs, num_ovf = d256_mul_pow10(n_limbs, up_scale - k0)
    ubh, ubl = _u(mbh), _u(mbl)
    ovf, qh, ql, rh, rl = d256_divmod_u128(n_limbs, ubh, ubl)
    ovf = ovf | num_ovf
    # HALF_UP on the remainder
    r2h = (rh << jnp.uint64(1)) | (rl >> jnp.uint64(63))
    r2l = rl << jnp.uint64(1)
    bump = _u128_ge(r2h, r2l, ubh, ubl).astype(jnp.uint64)
    ql2 = ql + bump
    qh2 = qh + (ql2 < ql).astype(jnp.uint64)
    ovf = ovf | ((qh2 >> jnp.uint64(63)) != 0)
    sh, sl = _s(qh2), ql2
    nh, nl = d128_neg(sh, sl)
    return jnp.where(neg, nh, sh), jnp.where(neg, nl, sl), ovf


def d128_to_f64(h, l):
    """Approximate float64 value of the signed 128-bit integer.

    Convert SIGN-MAGNITUDE, not h*2^64+l directly: for small negative
    values (h = -1, l = 2^64 - v) the direct form cancels two ~2^64
    floats whose difference is far below their ulp (2048 at 2^64), so
    e.g. -350 rounded to exactly 0.0 (round-4 bug: every small negative
    decimal cast to double collapsed to zero)."""
    neg = h < 0
    nh, nl = d128_neg(h, l)
    mh = jnp.where(neg, nh, h)
    ml = jnp.where(neg, nl, l)
    m = mh.astype(jnp.float64) * (2.0 ** 64) + ml.astype(jnp.float64)
    return jnp.where(neg, -m, m)


def f64_to_d128(x):
    """Round a float64 to the nearest signed 128-bit integer limbs.
    Precision is inherently float64's 53 bits; out-of-range values wrap
    (callers bound-check via the float before converting)."""
    neg = x < 0
    m = jnp.abs(x)
    hi_f = jnp.floor(m / (2.0 ** 64))
    lo_f = m - hi_f * (2.0 ** 64)
    # round lo; a carry can push it to exactly 2^64
    lo_f = jnp.floor(lo_f + 0.5)
    carry = lo_f >= 2.0 ** 64
    hi_f = hi_f + carry
    lo_f = jnp.where(carry, 0.0, lo_f)
    h = jnp.clip(hi_f, 0.0, 2.0 ** 63).astype(jnp.uint64)
    l = lo_f.astype(jnp.uint64)
    sh, sl = _s(h), l
    nh, nl = d128_neg(sh, sl)
    return jnp.where(neg, nh, sh), jnp.where(neg, nl, sl)


def _pow10_limbs(p: int) -> Tuple[int, int]:
    v = 10 ** p
    return v >> 64, v & ((1 << 64) - 1)


def d128_fits_precision(h, l, precision: int):
    """|x| < 10^precision (Spark changePrecision overflow check)."""
    if precision >= 39:
        return jnp.ones(h.shape, jnp.bool_)
    bh, bl = _pow10_limbs(precision)
    mh, ml = d128_abs(h, l)
    return d128_lt(mh, ml, jnp.int64(bh), jnp.uint64(bl))


def d128_rescale(h, l, from_scale: int, to_scale: int):
    """Change scale; returns (h, l, overflow_from_upscale)."""
    if to_scale == from_scale:
        return h, l, jnp.zeros(h.shape, jnp.bool_)
    if to_scale > from_scale:
        return d128_mul_pow10(h, l, to_scale - from_scale)
    h2, l2 = d128_div_pow10_half_up(h, l, from_scale - to_scale)
    return h2, l2, jnp.zeros(h.shape, jnp.bool_)


# ---------------------------------------------------------------------------
# host <-> device
# ---------------------------------------------------------------------------

def limbs_of(col) -> Tuple[jax.Array, jax.Array]:
    """(hi:int64, lo:uint64) limbs of any decimal column — sign-extends
    long-backed (int64) decimals, passes wide columns through."""
    if isinstance(col, Decimal128Column):
        return col.hi, col.lo
    return d128_from_i64(col.data.astype(jnp.int64))


def build_decimal_column(hi, lo, validity, dtype: dt.DecimalType):
    """Materialize limbs as the physical column for ``dtype``: a
    Decimal128Column when wide, otherwise an int64 ColumnVector (the
    value is known to fit by the caller's precision check). Lanes under
    nulls are zeroed (the engine-wide invariant)."""
    from .vector import ColumnVector
    z64 = jnp.zeros((), jnp.int64)
    if dtype.is_wide:
        zu = jnp.zeros((), jnp.uint64)
        return Decimal128Column(jnp.where(validity, hi, z64),
                                jnp.where(validity, lo, zu),
                                validity, dtype)
    data = lo.astype(jnp.int64)  # wrapping; exact when |v| < 2^63
    return ColumnVector(jnp.where(validity, data, z64), validity, dtype)


def seg_sum128(hi, lo, gid, num_groups):
    """Segmented 128-bit sum. Decomposes each two's-complement value
    into four 32-bit limbs, segment-sums each into uint64 accumulators
    (exact for < 2^32 rows), then carry-propagates back to (hi, lo).
    The result is the true sum mod 2^128 — wrap detection is the
    caller's job (see expr/aggregates.py decimal sum)."""
    uh, ul = _u(hi), lo
    limbs = [ul & _U32, ul >> jnp.uint64(32), uh & _U32,
             uh >> jnp.uint64(32)]
    sums = []
    for w in limbs:
        acc = jnp.zeros(num_groups, jnp.uint64)
        sums.append(acc.at[gid].add(w))
    acc = sums[0]
    w0 = acc & _U32
    acc = (acc >> jnp.uint64(32)) + sums[1]
    w1 = acc & _U32
    acc = (acc >> jnp.uint64(32)) + sums[2]
    w2 = acc & _U32
    acc = (acc >> jnp.uint64(32)) + sums[3]
    w3 = acc & _U32
    out_lo = w0 | (w1 << jnp.uint64(32))
    out_hi = _s(w2 | (w3 << jnp.uint64(32)))
    return out_hi, out_lo


def sort_key_bias(h):
    """Order-preserving uint64 image of the hi limb: flip the sign bit
    so (biased_hi, lo) lexicographic unsigned order == signed 128-bit
    numeric order. Used by segmented min/max and sort-key expansion."""
    return _u(h) ^ jnp.uint64(1 << 63)


def seg_minmax128(hi, lo, valid, gid, num_groups, largest: bool):
    """Segmented 128-bit min/max via two lexicographic passes: first
    reduce the biased hi limb, then reduce lo among rows whose hi limb
    equals the group winner."""
    bh = sort_key_bias(hi)
    hi_fill = jnp.uint64(0) if largest else jnp.uint64(0xFFFFFFFFFFFFFFFF)
    lo_fill = hi_fill
    bh_m = jnp.where(valid, bh, hi_fill)
    acc = jnp.full(num_groups, hi_fill, jnp.uint64)
    best_hi = (acc.at[gid].max(bh_m) if largest else acc.at[gid].min(bh_m))
    on_best = valid & (bh_m == best_hi[gid])
    lo_m = jnp.where(on_best, lo, lo_fill)
    acc2 = jnp.full(num_groups, lo_fill, jnp.uint64)
    best_lo = (acc2.at[gid].max(lo_m) if largest else acc2.at[gid].min(lo_m))
    out_hi = _s(best_hi ^ jnp.uint64(1 << 63))
    return out_hi, best_lo


def from_unscaled_ints(values, capacity: int, dtype: dt.DecimalType,
                       mask: Optional[np.ndarray] = None
                       ) -> Decimal128Column:
    """Build from python unscaled ints (arbitrary precision)."""
    n = len(values)
    valid = np.array([v is not None for v in values], dtype=bool) \
        if mask is None else np.asarray(mask, dtype=bool)
    hi = np.zeros(capacity, np.int64)
    lo = np.zeros(capacity, np.uint64)
    for i in range(n):
        if not valid[i] or values[i] is None:
            continue
        v = int(values[i])
        hi[i] = np.int64(v >> 64)  # python >> is arithmetic: sign-correct
        lo[i] = np.uint64(v & ((1 << 64) - 1))
    validity = np.zeros(capacity, bool)
    validity[:n] = valid
    return Decimal128Column(jnp.asarray(hi), jnp.asarray(lo),
                            jnp.asarray(validity), dtype)
