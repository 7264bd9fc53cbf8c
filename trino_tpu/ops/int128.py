"""Int128 limb arithmetic for long decimals (p > 18).

Reference: ``core/trino-spi/.../spi/type/Int128Math.java`` (+ Int128.java) —
the reference's long-decimal substrate. Device representation here: a pair
of int64 arrays ``(hi, lo)``; ``lo`` carries the low 64 bits as a raw bit
pattern (interpreted unsigned), ``hi`` the high 64 bits including sign.

Engaged by the expression lowering (ops/expr_lower.py) for decimal
arithmetic whose intermediates or results exceed int64. Long-decimal
(p > 18) values AT REST are adaptive two-limb: columns carry an optional
``hi`` int64 limb (data/page.py Column.hi) exactly when the data needs it,
so the full ±(10^38 - 1) range round-trips, joins, groups, and sums;
results past the p=38 cap raise the deferred DECIMAL_OVERFLOW error
(matching the reference's Int128Math overflow throws).

All ops are elementwise on uint64 words (TPU-native 32-bit pairs under the
hood; no Python bigints inside jit).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from trino_tpu.ops import expr_key

I128 = Tuple[jnp.ndarray, jnp.ndarray]  # (hi int64, lo int64 bit pattern)

# numpy scalar to stay concrete if first imported under a trace
_MASK32 = np.uint64(0xFFFFFFFF)


def _u(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.uint64)


def _s(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.int64)


def from_int64(x: jnp.ndarray) -> I128:
    x = x.astype(jnp.int64)
    return x >> 63, x


def is_negative(a: I128) -> jnp.ndarray:
    return a[0] < 0


def neg(a: I128) -> I128:
    hi, lo = a
    nlo = _u(~lo) + jnp.uint64(1)
    # ~lo + 1 == 0 only when lo == 0 (then the +1 carries into hi)
    nhi = _u(~hi) + (nlo == 0).astype(jnp.uint64)
    return _s(nhi), _s(nlo)


def add(a: I128, b: I128) -> I128:
    hi1, lo1 = a
    hi2, lo2 = b
    lo = _u(lo1) + _u(lo2)
    carry = (lo < _u(lo1)).astype(jnp.uint64)
    hi = _u(hi1) + _u(hi2) + carry
    return _s(hi), _s(lo)


def sub(a: I128, b: I128) -> I128:
    return add(a, neg(b))


def abs128(a: I128) -> Tuple[I128, jnp.ndarray]:
    """(|a|, was_negative)."""
    n = is_negative(a)
    na = neg(a)
    return (jnp.where(n, na[0], a[0]), jnp.where(n, na[1], a[1])), n


def _mul_u64(x: jnp.ndarray, y: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full 128-bit product of two uint64 arrays -> (hi u64, lo u64)."""
    x0, x1 = x & _MASK32, x >> 32
    y0, y1 = y & _MASK32, y >> 32
    ll = x0 * y0
    m1 = x1 * y0
    m2 = x0 * y1
    hh = x1 * y1
    t = (ll >> 32) + (m1 & _MASK32) + (m2 & _MASK32)
    lo = (ll & _MASK32) | (t << 32)
    hi = hh + (m1 >> 32) + (m2 >> 32) + (t >> 32)
    return hi, lo


def mul_int64(x: jnp.ndarray, y: jnp.ndarray) -> I128:
    """Exact signed product of two int64 arrays."""
    sx = x < 0
    sy = y < 0
    ax = _u(jnp.where(sx, -x, x))
    ay = _u(jnp.where(sy, -y, y))
    hi, lo = _mul_u64(ax, ay)
    res = (_s(hi), _s(lo))
    nres = neg(res)
    flip = sx ^ sy
    return jnp.where(flip, nres[0], res[0]), jnp.where(flip, nres[1], res[1])


def mul_small(a: I128, m: int) -> I128:
    """a * m for a small non-negative Python int m (< 2^63); caller must
    bound the magnitude (see mul_small_checked for the flagged variant)."""
    out, _ = mul_small_checked(a, m)
    return out


def mul_small_checked(a: I128, m: int) -> Tuple[I128, jnp.ndarray]:
    """(a * m, overflowed): flags rows whose |a|*m exceeds 2^127 - 1
    (reference: Int128Math overflow checks on rescale)."""
    (ahi, alo), n = abs128(a)
    mm = jnp.uint64(m)
    phi, plo = _mul_u64(_u(alo), mm)
    hh_hi, hh_lo = _mul_u64(_u(ahi), mm)  # high-limb product, 128-bit
    hi2 = phi + hh_lo
    overflow = (hh_hi != 0) | (hi2 < phi) | (_s(hi2) < 0)  # >= 2^127
    res = (_s(hi2), _s(plo))
    nres = neg(res)
    return (jnp.where(n, nres[0], res[0]), jnp.where(n, nres[1], res[1])), overflow


def mul_checked(a: I128, b: I128) -> Tuple[I128, jnp.ndarray]:
    """(a * b, overflowed) for two int128 operands — the low 128 bits of the
    signed product, flagging rows whose |a|*|b| exceeds 2^127 - 1
    (reference: Int128Math.multiply)."""
    (ahi, alo), na = abs128(a)
    (bhi, blo), nb = abs128(b)
    p_hi, p_lo = _mul_u64(_u(alo), _u(blo))  # |a|.lo * |b|.lo, 128-bit
    c1_hi, c1_lo = _mul_u64(_u(alo), _u(bhi))  # contributes << 64
    c2_hi, c2_lo = _mul_u64(_u(ahi), _u(blo))  # contributes << 64
    hh = (_u(ahi) != 0) & (_u(bhi) != 0)  # |a|.hi * |b|.hi -> always >= 2^128
    hi1 = p_hi + c1_lo
    hi2 = hi1 + c2_lo
    overflow = (
        hh
        | (c1_hi != 0)
        | (c2_hi != 0)
        | (hi1 < p_hi)
        | (hi2 < hi1)
        | (_s(hi2) < 0)  # >= 2^127
    )
    res = (_s(hi2), _s(p_lo))
    nres = neg(res)
    flip = na ^ nb
    return (
        jnp.where(flip, nres[0], res[0]),
        jnp.where(flip, nres[1], res[1]),
    ), overflow


def _divmod_core(hi: jnp.ndarray, lo: jnp.ndarray, dd: jnp.ndarray):
    """Unsigned (hi,lo) u64 pair divided by u64 ``dd`` (< 2^63): shift-
    subtract over the low word after dividing the high word (64 unrolled
    vector steps)."""
    expr_key.eager_only("int128 long division")
    q_hi = hi // dd
    r = hi % dd  # < d <= 2^63: doubling stays below 2^64
    q_lo = jnp.zeros_like(lo)
    for i in range(63, -1, -1):
        bit = (lo >> jnp.uint64(i)) & jnp.uint64(1)
        r = (r << jnp.uint64(1)) | bit
        ge = r >= dd
        r = jnp.where(ge, r - dd, r)
        q_lo = q_lo | (ge.astype(jnp.uint64) << jnp.uint64(i))
    return (_s(q_hi), _s(q_lo)), r


def divmod_u128(a: I128, b: I128) -> Tuple[I128, I128]:
    """Unsigned 128/128 division of NON-NEGATIVE operands (b > 0): classic
    shift-subtract long division, 128 unrolled vector steps (reference:
    Int128Math.divide's unsigned core). Returns (quotient, remainder)."""
    expr_key.eager_only("int128 long division")
    n_hi, n_lo = _u(a[0]), _u(a[1])
    d_hi, d_lo = _u(b[0]), _u(b[1])
    r_hi = jnp.zeros_like(n_hi)
    r_lo = jnp.zeros_like(n_lo)
    q_hi = jnp.zeros_like(n_hi)
    q_lo = jnp.zeros_like(n_lo)
    one = jnp.uint64(1)
    for i in range(127, -1, -1):
        bit = (
            (n_hi >> jnp.uint64(i - 64)) & one
            if i >= 64
            else (n_lo >> jnp.uint64(i)) & one
        )
        # r = (r << 1) | bit
        r_hi = (r_hi << one) | (r_lo >> jnp.uint64(63))
        r_lo = (r_lo << one) | bit
        ge = (r_hi > d_hi) | ((r_hi == d_hi) & (r_lo >= d_lo))
        # r -= d where ge
        borrow = (r_lo < d_lo).astype(jnp.uint64)
        r_lo = jnp.where(ge, r_lo - d_lo, r_lo)
        r_hi = jnp.where(ge, r_hi - d_hi - borrow, r_hi)
        if i >= 64:
            q_hi = q_hi | jnp.where(ge, one << jnp.uint64(i - 64), jnp.uint64(0))
        else:
            q_lo = q_lo | jnp.where(ge, one << jnp.uint64(i), jnp.uint64(0))
    return (_s(q_hi), _s(q_lo)), (_s(r_hi), _s(r_lo))


def divmod_u64(a: I128, d: int) -> Tuple[I128, jnp.ndarray]:
    """Unsigned division of a NON-NEGATIVE int128 by a Python int d < 2^63.
    Returns (quotient int128, remainder u64)."""
    return _divmod_core(_u(a[0]), _u(a[1]), jnp.uint64(d))


def divmod_u64_arr(a: I128, d: jnp.ndarray) -> Tuple[I128, jnp.ndarray]:
    """Unsigned division of a NON-NEGATIVE int128 by a positive u64 array."""
    return _divmod_core(_u(a[0]), _u(a[1]), d.astype(jnp.uint64))


def div_round_small(a: I128, d: int) -> I128:
    """a / d with HALF-UP rounding away from zero (Trino decimal rescale
    semantics, Int128Math.rescale), d a positive Python int < 2^63."""
    (ahi, alo), n = abs128(a)
    q, r = divmod_u64((ahi, alo), d)
    round_up = r >= jnp.uint64((d + 1) // 2)
    q = add(q, (jnp.zeros_like(q[0]), round_up.astype(jnp.int64)))
    nq = neg(q)
    return jnp.where(n, nq[0], q[0]), jnp.where(n, nq[1], q[1])


def compare(a: I128, b: I128) -> jnp.ndarray:
    """-1 / 0 / 1 signed comparison."""
    hi1, lo1 = a
    hi2, lo2 = b
    lt = (hi1 < hi2) | ((hi1 == hi2) & (_u(lo1) < _u(lo2)))
    gt = (hi1 > hi2) | ((hi1 == hi2) & (_u(lo1) > _u(lo2)))
    return jnp.where(lt, -1, jnp.where(gt, 1, 0)).astype(jnp.int8)


def fits_int64(a: I128) -> jnp.ndarray:
    """True where the value is exactly representable as int64."""
    hi, lo = a
    return hi == (lo >> 63)


def to_int64(a: I128) -> jnp.ndarray:
    """Low 64 bits as signed (caller checks fits_int64)."""
    return a[1]


def rescale(a: I128, from_scale: int, to_scale: int) -> I128:
    """Multiply/divide by powers of ten (half-up on scale-down)."""
    out, _ = rescale_checked(a, from_scale, to_scale)
    return out


def rescale_checked(a: I128, from_scale: int, to_scale: int) -> Tuple[I128, jnp.ndarray]:
    """rescale + a per-row overflow flag for the scale-up direction
    (scale-up by 10^40+ happily wraps 128 bits otherwise)."""
    if to_scale == from_scale:
        return a, jnp.zeros(a[0].shape, bool)
    if to_scale > from_scale:
        out = a
        overflow = jnp.zeros(a[0].shape, bool)
        k = to_scale - from_scale
        while k > 0:  # 10^18 fits the small-multiplier bound
            step = min(k, 18)
            out, ovf = mul_small_checked(out, 10 ** step)
            overflow = overflow | ovf
            k -= step
        return out, overflow
    out = a
    k = from_scale - to_scale
    while k > 18:
        out, _ = divmod_u64_signed_trunc(out, 10 ** 18)
        k -= 18
    return div_round_small(out, 10 ** k), jnp.zeros(a[0].shape, bool)


def divmod_u64_signed_trunc(a: I128, d: int) -> Tuple[I128, jnp.ndarray]:
    """Truncating signed division by positive d (no rounding)."""
    (ahi, alo), n = abs128(a)
    q, r = divmod_u64((ahi, alo), d)
    nq = neg(q)
    return (jnp.where(n, nq[0], q[0]), jnp.where(n, nq[1], q[1])), r
