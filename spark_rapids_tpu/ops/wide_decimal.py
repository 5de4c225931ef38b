"""Emulated 128-bit decimal arithmetic (device decimal128).

The TPU has no int128; the reference gets exact decimal128 from libcudf
(`GpuCast.scala` cast matrix, `DecimalUtil.scala`).  Here a wide decimal
(18 < precision <= 38) is a ``(n, 2)`` int64 limb array ``[lo, hi]`` of
the scaled two's-complement value, and add/subtract/compare/rescale are
built from int64 lane ops:

  * add/sub: lo-lane wraparound add + unsigned-compare carry into hi;
  * compare: signed hi compare, unsigned lo tiebreak;
  * rescale (x 10^k): 16-bit limb schoolbook multiply — products stay
    below 2^32 and column sums below 2^36, so every intermediate fits
    comfortably in int64 lanes even on backends whose int64 is emulated
    (no uint64 needed, no 64-bit bitcasts — see _float_orderable's note
    on the TPU X64 rewrite);
  * multiply (64 x 64, 128 x 64, 128 x 128) and the HALF_UP down-scale
    (/ 10^k) work on MAGNITUDES as 16-bit limbs, the sign apart: the
    product keeps every limb (up to 256 bits), so a product that passes
    128 bits but rounds back into range is still exact; division runs
    in steps of at most 10^4, whose remainders keep every lane under
    2^31.

All ops are elementwise/static — they fuse into the surrounding XLA
stage program like any other expression.  Their constants are numpy
scalars: a 0-d jax array made while a program traces becomes one of its
arguments, and a program that takes such an argument and drops an unused
column was seen to fail its second call ("Execution supplied 8 buffers
but compiled program expected 9").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["from_scaled64", "add", "neg", "sub", "eq", "lt", "le", "gt",
           "ge", "mul_pow10", "multiply", "round_pow10", "fits",
           "add_overflows", "WIDE_LIMBS"]

WIDE_LIMBS = 2
_SIGN = np.int64(np.uint64(1 << 63).astype(np.int64))  # int64 min


def _ult(x: jax.Array, y: jax.Array) -> jax.Array:
    """Unsigned 64-bit x < y via the sign-flip trick."""
    return (x ^ _SIGN) < (y ^ _SIGN)


def from_scaled64(d: jax.Array) -> jax.Array:
    """(n,) scaled int64 -> (n, 2) [lo, hi] limbs (sign-extended)."""
    d = d.astype(jnp.int64)
    hi = jnp.right_shift(d, np.int64(63))  # arithmetic: 0 or -1
    return jnp.stack([d, hi], axis=-1)


def add(a: jax.Array, b: jax.Array) -> jax.Array:
    lo = a[..., 0] + b[..., 0]  # wraps mod 2^64 (two's complement)
    carry = _ult(lo, a[..., 0]).astype(jnp.int64)
    hi = a[..., 1] + b[..., 1] + carry
    return jnp.stack([lo, hi], axis=-1)


def neg(a: jax.Array) -> jax.Array:
    lo = -a[..., 0]
    hi = ~a[..., 1] + (a[..., 0] == 0).astype(jnp.int64)
    return jnp.stack([lo, hi], axis=-1)


def sub(a: jax.Array, b: jax.Array) -> jax.Array:
    return add(a, neg(b))


def eq(a: jax.Array, b: jax.Array) -> jax.Array:
    return (a[..., 0] == b[..., 0]) & (a[..., 1] == b[..., 1])


def lt(a: jax.Array, b: jax.Array) -> jax.Array:
    return (a[..., 1] < b[..., 1]) | (
        (a[..., 1] == b[..., 1]) & _ult(a[..., 0], b[..., 0]))


def le(a: jax.Array, b: jax.Array) -> jax.Array:
    return lt(a, b) | eq(a, b)


def gt(a: jax.Array, b: jax.Array) -> jax.Array:
    return lt(b, a)


def ge(a: jax.Array, b: jax.Array) -> jax.Array:
    return le(b, a)


_M16 = np.int64(0xFFFF)


def _to_limbs16(a: jax.Array):
    """(n, 2) limbs -> eight (n,) int64 lanes in [0, 2^16) (raw two's-
    complement bits; logical shifts extract them sign-free)."""
    out = []
    for w in (a[..., 0], a[..., 1]):
        for k in range(4):
            out.append(jax.lax.shift_right_logical(
                w, np.int64(16 * k)) & _M16)
    return out


def _from_cols16(cols):
    """Carry-propagate eight >=0 int64 column sums (< 2^48) back into
    (n, 2) [lo, hi] limbs, mod 2^128."""
    carry = jnp.zeros_like(cols[0])
    lanes = []
    for k in range(8):
        tot = cols[k] + carry
        lanes.append(tot & _M16)
        carry = jax.lax.shift_right_logical(tot, np.int64(16))
    lo = (lanes[0] | (lanes[1] << 16) | (lanes[2] << 32)
          | (lanes[3] << 48))
    hi = (lanes[4] | (lanes[5] << 16) | (lanes[6] << 32)
          | (lanes[7] << 48))
    return jnp.stack([lo, hi], axis=-1)


def mul_pow10(a: jax.Array, k: int) -> jax.Array:
    """a * 10^k mod 2^128 (k >= 0 static).  Exact when the true product
    fits 128 bits — guaranteed by the result type's precision <= 38."""
    if k == 0:
        return a
    m = 10 ** k
    ml = [(m >> (16 * j)) & 0xFFFF for j in range(8)]
    al = _to_limbs16(a)
    cols = []
    for c in range(8):
        acc = None
        for i in range(8):
            j = c - i
            if 0 <= j < 8 and ml[j]:
                term = al[i] * np.int64(ml[j])
                acc = term if acc is None else acc + term
        cols.append(acc if acc is not None
                    else jnp.zeros_like(al[0]))
    return _from_cols16(cols)


# -- magnitudes as 16-bit limbs (multiply, HALF_UP down-scale, bounds) --------

def _magnitude(x: jax.Array):
    """(n,) int64 or (n, 2) limbs -> (negative mask, |x| as 16-bit limbs,
    least significant first): four limbs for int64 (|int64 min| = 2^63
    reads right through the logical shifts), eight for 128 bits."""
    if x.ndim == 1:
        x = x.astype(jnp.int64)
        negative = x < 0
        m = jnp.where(negative, -x, x)
        return negative, [
            jax.lax.shift_right_logical(m, np.int64(16 * k)) & _M16
            for k in range(4)]
    negative = x[..., 1] < 0
    return negative, _to_limbs16(jnp.where(negative[..., None], neg(x), x))


def _carry(cols):
    """Column sums (>= 0, < 2^62) -> 16-bit limbs; the carry out of the
    top column becomes limbs of its own, so nothing is dropped."""
    carry = jnp.zeros_like(cols[0])
    out = []
    for c in cols:
        tot = c + carry
        out.append(tot & _M16)
        carry = jax.lax.shift_right_logical(tot, np.int64(16))
    for _ in range(3):  # column sums stay below 2^36: three limbs hold it
        out.append(carry & _M16)
        carry = jax.lax.shift_right_logical(carry, np.int64(16))
    return out


def _mul_limbs(al, bl):
    """Schoolbook product of two limb lists: every product < 2^32, every
    column sum < 2^35."""
    cols = []
    for c in range(len(al) + len(bl) - 1):
        acc = None
        for i in range(max(0, c - len(bl) + 1), min(c + 1, len(al))):
            term = al[i] * bl[c - i]
            acc = term if acc is None else acc + term
        cols.append(acc)
    return _carry(cols)


def _const_limbs(c: int, n: int):
    return [(c >> (16 * j)) & 0xFFFF for j in range(n)]


def _add_const(limbs, c: int):
    return _carry([x + np.int64(k)
                   for x, k in zip(limbs, _const_limbs(c, len(limbs)))])


def _div_small(limbs, d: int):
    """floor(limbs / d) for 1 < d <= 10^4: the remainder stays under d, so
    (remainder << 16) + limb stays under 2^31."""
    rem = jnp.zeros_like(limbs[0])
    out = [None] * len(limbs)
    for i in reversed(range(len(limbs))):
        cur = (rem << 16) + limbs[i]
        out[i] = cur // d
        rem = cur - out[i] * d
    return out


def _div_pow10_half_up(limbs, k: int):
    """round(|x| / 10^k) with ties away from zero (HALF_UP on a
    magnitude): add half the divisor, then divide in steps of 10^4."""
    if k <= 0:
        return limbs
    limbs = _add_const(limbs, 5 * 10 ** (k - 1))
    while k > 0:
        step = min(k, 4)
        limbs = _div_small(limbs, 10 ** step)
        k -= step
    return limbs


def _lt_const(limbs, c: int):
    """limbs < c, compared from the top limb down (c may need more limbs
    than are given: then every value is below it)."""
    if c >> (16 * len(limbs)):
        return limbs[0] >= 0
    lt = limbs[0] < 0
    eq = limbs[0] >= 0
    for x, k in zip(reversed(limbs), reversed(_const_limbs(c, len(limbs)))):
        lt = lt | (eq & (x < k))
        eq = eq & (x == k)
    return lt


def _signed(negative, limbs):
    """A magnitude's limbs and its sign -> ((n, 2) limbs, overflow): the
    overflow mask is set where the magnitude needs more than 127 bits."""
    limbs = limbs + [jnp.zeros_like(limbs[0])] * max(0, 8 - len(limbs))
    over = limbs[7] >= 0x8000
    for x in limbs[8:]:
        over = over | (x != 0)
    lo = limbs[0] | (limbs[1] << 16) | (limbs[2] << 32) | (limbs[3] << 48)
    hi = limbs[4] | (limbs[5] << 16) | (limbs[6] << 32) | (limbs[7] << 48)
    mag = jnp.stack([lo, hi], axis=-1)
    return jnp.where(negative[..., None], neg(mag), mag), over


def multiply(a: jax.Array, b: jax.Array, drop: int = 0):
    """round(a * b / 10^drop) HALF_UP, exactly, for operands that are each
    (n,) int64 or (n, 2) limbs: the 64 x 64 -> 128, 128 x 64 -> 128 and
    128 x 128 products.  Returns ((n, 2) limbs, overflow): overflow is set
    where the rounded product does not fit a signed 128-bit value (the
    result lanes are then meaningless)."""
    na, al = _magnitude(a)
    nb, bl = _magnitude(b)
    prod = _div_pow10_half_up(_mul_limbs(al, bl), drop)
    return _signed(na ^ nb, prod)


def round_pow10(a: jax.Array, k: int) -> jax.Array:
    """(n, 2) limbs / 10^k, rounded HALF_UP (k >= 0 static): the down-scale
    of a wide decimal; it never overflows."""
    if k <= 0:
        return a
    negative, mag = _magnitude(a)
    return _signed(negative, _div_pow10_half_up(mag, k))[0]


def fits(a: jax.Array, precision: int) -> jax.Array:
    """|a| < 10^precision, for (n,) int64 or (n, 2) limbs."""
    return _lt_const(_magnitude(a)[1], 10 ** precision)


def add_overflows(a: jax.Array, b: jax.Array, s: jax.Array) -> jax.Array:
    """Whether ``s = add(a, b)`` wrapped past the signed 128-bit range:
    the operands agree in sign and the sum does not."""
    sa, sb, ss = a[..., 1] < 0, b[..., 1] < 0, s[..., 1] < 0
    return (sa == sb) & (ss != sa)

