"""A comparison of a whole number with ``k * (s / c)`` decided as IEEE-754
double arithmetic decides it, in 64-bit integers.

On the TPU a double is a pair of floats: a quotient and a product come
out within a few units of the 49th bit, not rounded to the 53rd.  Where
the exact value sits on an integer that flips a comparison: ``l_quantity
< 0.2 * avg(l_quantity)`` over a part whose lines average 25.0 exactly
compares 5 with ``fl(0.2 * 25.0)``, which IEEE rounds to 5.0 (false) and
the pair may leave a hair above it (true).  So where the operands are
whole numbers that the replica proves exact (``x`` and a SUM ``s`` of a
whole-number column, a COUNT ``c``), the decision is made here on their
integer values: ``y = fl(s / c)`` by long division in 16-bit steps,
``t = fl(k * y)`` by a 106-bit product of 53-bit significands, each
rounded to nearest, ties to even, then ``x`` against ``floor(t)`` /
``ceil(t)``.  Every quotient is taken in floating point and corrected in
integers, so no step trusts the floating point to more than a few units
(and no 64-bit integer division is asked of the chip).

Bounds (the caller proves them): ``|x| < 2^31``, ``|s| < 2^40``,
``0 <= c < 2^31``, ``k`` a finite double with ``2^-60 <= |k| <= 2^60``.
``c = 0`` is a division by zero: NULL, as the SQL quotient is.
"""
from __future__ import annotations

import math

#: the comparisons, each as the one it is with its operands swapped
FLIPPED = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}
S_BITS, K_EXP = 40, 60


def split_double(k: float) -> tuple:
    """(sign, K, E) with ``|k| = K * 2^E`` and ``2^52 <= K < 2^53``."""
    m, e = math.frexp(abs(k))
    return (-1 if k < 0 else 1), int(m * (1 << 53)), e - 53


def scalable(k: float) -> bool:
    """Whether ``k`` is a constant this module takes."""
    return math.isfinite(k) and k != 0 \
        and -K_EXP <= math.frexp(abs(k))[1] - 1 <= K_EXP


def _bitlen(jn, v):
    """Bits of each ``v >= 0`` (0 for 0)."""
    n = jn.zeros_like(v)
    for sh in (32, 16, 8, 4, 2, 1):
        big = v >= (1 << sh)
        v = jn.where(big, v >> sh, v)
        n = jn.where(big, n + sh, n)
    return n + (v > 0)


def _divmod(jn, a, c):
    """(a // c, a % c) for ``0 <= a < 2^48``, ``1 <= c < 2^31``: the
    quotient of the doubles, corrected twice in integers."""
    f64, i64 = jn.float64, jn.int64
    q = jn.floor(a.astype(f64) / c.astype(f64)).astype(i64)
    r = a - q * c
    q2 = jn.floor(r.astype(f64) / c.astype(f64)).astype(i64)
    q, r = q + q2, r - q2 * c
    low = r < 0
    q, r = jn.where(low, q - 1, q), jn.where(low, r + c, r)
    high = r >= c
    return jn.where(high, q + 1, q), jn.where(high, r - c, r)


def _round53(jn, n, drop, sticky):
    """``n >> drop`` rounded to nearest, ties to even (``sticky``: bits
    below ``n`` are not all zero); (significand, extra exponent)."""
    one = jn.ones_like(n)
    y = n >> drop
    rem = n & ((one << drop) - 1)
    half = one << (drop - 1)
    up = (rem > half) | ((rem == half) & (sticky | ((y & 1) == 1)))
    y = y + up.astype(n.dtype)
    carry = y == (1 << 53)
    return jn.where(carry, y >> 1, y), carry.astype(n.dtype)


def _quotient(jn, s, c):
    """``fl(s / c)`` as (Y, E): ``Y * 2^E``, ``2^52 <= Y < 2^53``, for
    ``1 <= s < 2^40`` and ``1 <= c < 2^31``."""
    # N = floor(s * 2^sh / c) holds 55 or 56 bits
    sh = 55 - (_bitlen(jn, s) - _bitlen(jn, c))
    q0, r = _divmod(jn, s, c)
    frac = jn.zeros_like(s)
    for i in range(6):  # sh <= 85: six steps of at most 16 bits
        b = jn.clip(sh - 16 * i, 0, 16)
        qq, r = _divmod(jn, r << b, c)
        frac = (frac << b) + qq
    n = (q0 << sh) + frac
    drop = _bitlen(jn, n) - 53
    y, carry = _round53(jn, n, drop, r != 0)
    return y, drop + carry - sh


def _product(jn, k_sig: int, y):
    """``fl(K * Y)`` as (T, E): ``T * 2^E``, ``2^52 <= T < 2^53``."""
    m27 = (1 << 27) - 1
    k1, k0 = k_sig >> 27, k_sig & m27
    y1, y0 = y >> 27, y & m27
    mid = k1 * y0 + k0 * y1
    low = (mid & ((1 << 25) - 1)) * (1 << 27) + k0 * y0
    # floor(K * Y / 2^52): 53 or 54 bits, and what lies below it
    n = (k1 * y1) * 4 + (mid >> 25) + (low >> 52)
    rest = low & ((1 << 52) - 1)
    wide = n >= (1 << 53)
    # one more bit to drop where it has 54: fold it in front of ``rest``
    n2 = jn.where(wide, n, n * 2 + (rest >> 51))
    sticky = jn.where(wide, rest != 0, (rest & ((1 << 51) - 1)) != 0)
    t, carry = _round53(jn, n2, jn.ones_like(n2), sticky)
    return t, carry + jn.where(wide, 53, 52)


def compare(jn, op: str, x, s, c, sign, k_sig, k_exp):
    """(``x op fl(k * fl(s / c))`` as IEEE doubles decide it, NULL) for
    int64 lanes ``x``, ``s``, ``c`` within the module's bounds; ``k`` as
    :func:`split_double` gives it (Python ints, or int64 scalars traced
    as the program's parameters)."""
    i64 = jn.int64
    null = c == 0
    cc = jn.where(null, 1, c)
    sa = jn.abs(s)
    zero = sa == 0
    y, ey = _quotient(jn, jn.where(zero, 1, sa), cc)
    t, et = _product(jn, k_sig, y)
    et = et + ey + k_exp
    # |t| = t * 2^et: its floor and whether it has a fraction (|t| is
    # at least 2^52 where et >= 0, past every x: a saturated floor)
    m = jn.clip(-et, 0, 63)
    fl = jn.where(et >= 0, 1 << 62, jn.where(-et >= 63, 0, t >> m))
    frac = jn.where(et >= 0, False,
                    jn.where(-et >= 63, True, (t & ((1 << m) - 1)) != 0))
    ce = fl + frac.astype(i64)
    neg = jn.where(sign > 0, s < 0, s > 0)
    lo = jn.where(zero, 0, jn.where(neg, -ce, fl))
    hi = jn.where(zero, 0, jn.where(neg, -fl, ce))
    if op == "<":
        v = x < hi
    elif op == "<=":
        v = x <= lo
    elif op == ">":
        v = x > lo
    elif op == ">=":
        v = x >= hi
    else:
        v = (lo == hi) & (x == lo)
        if op == "!=":
            v = ~v
    return v & ~null, null
