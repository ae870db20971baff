"""u128 arithmetic on (lo, hi) uint64 lane pairs, traceable under jit.

The reference does native u128 arithmetic with overflow checks
(state_machine.zig:1308-1320, sum_overflows at state_machine.zig:1645-1650).
JAX/XLA has no 128-bit integers and the TPU scalar/vector units are 32-bit, so
u128 values live as two uint64 lanes.  All functions below are elementwise,
shape-polymorphic, and wrap modulo 2**128 exactly like hardware would; overflow
is reported explicitly where the reference checks it.

Everything here requires ``jax_enable_x64`` (set in the package __init__).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp


class U128(NamedTuple):
    """A (possibly batched) 128-bit unsigned integer as two uint64 lanes."""

    lo: jnp.ndarray
    hi: jnp.ndarray


def lit(value: int) -> U128:
    """A scalar u128 literal."""
    return U128(
        jnp.uint64(value & 0xFFFF_FFFF_FFFF_FFFF),
        jnp.uint64((value >> 64) & 0xFFFF_FFFF_FFFF_FFFF),
    )


def zeros_like(x: U128) -> U128:
    return U128(jnp.zeros_like(x.lo), jnp.zeros_like(x.hi))


def add(a: U128, b: U128) -> Tuple[U128, jnp.ndarray]:
    """a + b mod 2**128, plus an overflow flag (mirrors sum_overflows u128)."""
    lo = a.lo + b.lo
    carry = (lo < a.lo).astype(jnp.uint64)
    hi_nc = a.hi + b.hi
    c1 = hi_nc < a.hi
    hi = hi_nc + carry
    c2 = hi < hi_nc
    return U128(lo, hi), c1 | c2


def add_wrap(a: U128, b: U128) -> U128:
    return add(a, b)[0]


def sub(a: U128, b: U128) -> Tuple[U128, jnp.ndarray]:
    """a - b mod 2**128, plus an underflow (borrow) flag."""
    lo = a.lo - b.lo
    borrow = (a.lo < b.lo).astype(jnp.uint64)
    hi_nb = a.hi - b.hi
    b1 = a.hi < b.hi
    hi = hi_nb - borrow
    b2 = hi_nb < borrow
    return U128(lo, hi), b1 | b2


def sub_wrap(a: U128, b: U128) -> U128:
    return sub(a, b)[0]


def sub_saturate(a: U128, b: U128) -> U128:
    """a -| b (saturating subtraction, Zig's ``-|`` in state_machine.zig:1296)."""
    diff, under = sub(a, b)
    z = jnp.uint64(0)
    return U128(jnp.where(under, z, diff.lo), jnp.where(under, z, diff.hi))


def eq(a: U128, b: U128) -> jnp.ndarray:
    return (a.lo == b.lo) & (a.hi == b.hi)


def ne(a: U128, b: U128) -> jnp.ndarray:
    return ~eq(a, b)


def gt(a: U128, b: U128) -> jnp.ndarray:
    return (a.hi > b.hi) | ((a.hi == b.hi) & (a.lo > b.lo))


def ge(a: U128, b: U128) -> jnp.ndarray:
    return (a.hi > b.hi) | ((a.hi == b.hi) & (a.lo >= b.lo))


def lt(a: U128, b: U128) -> jnp.ndarray:
    return gt(b, a)


def le(a: U128, b: U128) -> jnp.ndarray:
    return ge(b, a)


def min_(a: U128, b: U128) -> U128:
    take_a = le(a, b)
    return U128(jnp.where(take_a, a.lo, b.lo), jnp.where(take_a, a.hi, b.hi))


def is_zero(x: U128) -> jnp.ndarray:
    return (x.lo == 0) & (x.hi == 0)


def is_max(x: U128) -> jnp.ndarray:
    m = jnp.uint64(0xFFFF_FFFF_FFFF_FFFF)
    return (x.lo == m) & (x.hi == m)


def select(pred: jnp.ndarray, a: U128, b: U128) -> U128:
    return U128(jnp.where(pred, a.lo, b.lo), jnp.where(pred, a.hi, b.hi))


def limbs16(x: jnp.ndarray) -> list:
    """The four 16-bit limbs of a uint64 array, low first, as uint32: sums
    of up to 2^15 of them stay below 2^31, so scans and scatter-adds can run
    in native 32-bit (a TPU emulates 64-bit ones as uint32 pairs)."""
    m16 = jnp.uint64(0xFFFF)
    return [
        (x & m16).astype(jnp.uint32),
        ((x >> jnp.uint64(16)) & m16).astype(jnp.uint32),
        ((x >> jnp.uint64(32)) & m16).astype(jnp.uint32),
        (x >> jnp.uint64(48)).astype(jnp.uint32),
    ]


def from_limbs32(lo_limb: jnp.ndarray, hi_limb: jnp.ndarray) -> U128:
    """lo_limb + hi_limb * 2^32 as u128, for uint64 sums of 32-bit limbs
    (each < 2**47 for <= 32k terms)."""
    low = lo_limb + ((hi_limb & jnp.uint64(0xFFFFFFFF)) << jnp.uint64(32))
    carry = (low < lo_limb).astype(jnp.uint64)
    return U128(low, (hi_limb >> jnp.uint64(32)) + carry)


def mix64(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Mix a u128 key's lanes into one well-distributed u64 (for hashing).

    splitmix64 finalizer over a xor-fold of the lanes — cheap on TPU (shifts,
    xors, one multiply pair) and adequate for open-addressing table hashing.
    """
    x = lo ^ (hi * jnp.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))
