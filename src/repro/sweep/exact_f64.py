"""IEEE-754 binary64 arithmetic on uint64 bit patterns, in integer ops.

A TPU has no float64 unit: XLA emulates f64 as a pair of f32 (high and
low parts), which holds 48 bits of significand and rounds differently
from IEEE arithmetic. The fill kernel must reproduce the scalar
allocator's CPython floats bit for bit on every backend, so it carries
each float64 as its bit pattern in a ``uint64`` and computes with the
functions below. Integer operations are exact everywhere; each function
rounds to nearest, ties to even, as IEEE division, multiplication and
subtraction do.

Scope: the fill's operands are non-negative (capacities, shares, rates,
member counts), so the functions take non-negative finite operands
unless stated, and never see NaN. Results that overflow become +inf;
results below the normal range round to subnormals. For non-negative
values, the unsigned order of bit patterns is the numeric order, so
``<``, ``==``, ``min`` and ``argmin`` work on the patterns directly.
Everything here needs ``jax.enable_x64``.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

U64 = jnp.uint64
#: +inf
INF = 0x7FF0000000000000
_FRAC = (1 << 52) - 1
_HIDDEN = 1 << 52
_SIGN = 1 << 63
#: extra low bits carried below the 53-bit significand while rounding
_GUARD = 10


def _u(x: int):
    return jnp.asarray(x, U64)


def _shr(x, d):
    """``x >> d`` for any ``d >= 0`` (0 once ``d`` reaches 64)."""
    return jnp.where(d >= 64, _u(0), x >> jnp.minimum(d, 63).astype(U64))


def _unpack(x):
    """Biased exponent and significand of a positive finite pattern,
    normalized so the significand's leading 1 is bit 52 (subnormals get
    an exponent below 1): the value is ``m * 2**(e - 1075)``."""
    e = (x >> 52).astype(jnp.int32)
    m = x & _u(_FRAC)
    normal = e > 0
    m = jnp.where(normal, m | _u(_HIDDEN), m)
    e = jnp.where(normal, e, 1)
    shift = lax.clz(m).astype(jnp.int32) - 11
    return e - shift, m << shift.astype(U64)


def _round_pack(e, m, sticky):
    """Round ``m * 2**(e - 1023 - 62)`` to a pattern, where ``m`` has
    its leading 1 at bit 62 and ``sticky`` says whether nonzero bits
    were dropped below ``m``."""
    # below the normal range: shift into subnormal position first
    d = jnp.clip(1 - e, 0, 64)
    lost = (m & _shr(_u(0xFFFFFFFFFFFFFFFF), 64 - d)) != 0
    m = _shr(m, d)
    sticky = sticky | lost
    e = jnp.maximum(e, 1)
    mant = m >> _GUARD
    low = m & _u((1 << _GUARD) - 1)
    half = _u(1 << (_GUARD - 1))
    up = (low > half) | ((low == half) & (sticky | ((mant & 1) == 1)))
    mant = mant + up.astype(U64)
    carry = mant >> 53
    mant = jnp.where(carry == 1, mant >> 1, mant)
    e = e + carry.astype(jnp.int32)
    bits = jnp.where(mant >= _HIDDEN,
                     (e.astype(U64) << 52) | (mant & _u(_FRAC)), mant)
    return jnp.where(e >= 2047, _u(INF), bits)


def from_int(k):
    """Pattern of the float64 equal to a non-negative integer below
    2**53."""
    m = k.astype(U64)
    shift = lax.clz(m).astype(jnp.int32) - 11
    bits = (((1075 - shift).astype(U64) << 52)
            | ((m << jnp.maximum(shift, 0).astype(U64)) & _u(_FRAC)))
    return jnp.where(k == 0, _u(0), bits)


def div(a, b):
    """``a / b`` for ``a >= 0`` (``+inf`` allowed) and finite
    ``b > 0``, by restoring long division of the significands."""
    ea, ma = _unpack(a)
    eb, mb = _unpack(b)
    lt = ma < mb
    r = jnp.where(lt, ma << 1, ma)
    e = ea - eb + 1023 - lt.astype(jnp.int32)
    def step(_, rq):
        r, q = rq
        bit = r >= mb
        return (jnp.where(bit, r - mb, r) << 1,
                (q << 1) | bit.astype(U64))

    # 55 quotient bits: the 53 kept, the half bit and one more, the
    # remainder standing for the rest. Five per loop trip: written out
    # as 55 straight-line steps, the TPU compile of the fill takes
    # minutes
    r, q = lax.fori_loop(0, 55, step, (r, jnp.zeros_like(r)), unroll=5)
    out = _round_pack(e, q << 8, r != 0)
    out = jnp.where(a == 0, _u(0), out)
    return jnp.where(a == _u(INF), _u(INF), out)


def mul(a, b):
    """``a * b`` for ``a, b >= 0``, at most one of them ``+inf`` and
    then the other nonzero."""
    ea, ma = _unpack(a)
    eb, mb = _unpack(b)
    lo32 = _u(0xFFFFFFFF)
    a1, a0 = ma >> 32, ma & lo32
    b1, b0 = mb >> 32, mb & lo32
    p00 = a0 * b0
    mid = a0 * b1 + a1 * b0                    # < 2**54
    lo = p00 + (mid << 32)
    hi = a1 * b1 + (mid >> 32) + (lo < p00).astype(U64)
    # the 106-bit product hi:lo has its leading 1 at bit 104 or 105
    big = (hi >> 41) != 0
    s = jnp.where(big, 43, 42)
    su = s.astype(U64)
    m = (hi << (64 - su)) | (lo >> su)
    sticky = (lo & ((_u(1) << su) - 1)) != 0
    out = _round_pack(ea + eb + s - 1065, m, sticky)
    out = jnp.where((a == 0) | (b == 0), _u(0), out)
    return jnp.where((a == _u(INF)) | (b == _u(INF)), _u(INF), out)


def sub(a, b):
    """``a - b`` for finite ``a > b >= 0``."""
    ea, ma = _unpack(a)
    eb, mb = _unpack(b)
    A = ma << _GUARD
    B = mb << _GUARD
    d = ea - eb
    # shift B right by d, jamming every dropped bit into its lowest bit
    mask = jnp.where(d >= 64, _u(0xFFFFFFFFFFFFFFFF),
                     (_u(1) << jnp.clip(d, 0, 63).astype(U64)) - 1)
    Bs = _shr(B, d) | ((B & mask) != 0).astype(U64)
    D = A - Bs
    lz = lax.clz(D).astype(jnp.int32) - 1
    return _round_pack(ea - lz, D << lz.astype(U64), False)


def signed_div(a, b):
    """``a / b`` for any finite ``a`` and finite ``b > 0``."""
    sign = a & _u(_SIGN)
    return div(a & _u(_SIGN - 1), b) | sign


def order_key(x):
    """A uint64 whose unsigned order is the numeric order of the
    float64 ``x`` of either sign (``-0`` sorts just below ``+0``)."""
    neg = (x >> 63) == 1
    return jnp.where(neg, ~x, x | _u(_SIGN))


def from_order_key(k):
    """Inverse of :func:`order_key`."""
    pos = (k >> 63) == 1
    return jnp.where(pos, k & _u(_SIGN - 1), ~k)
