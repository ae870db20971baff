"""Shared in-jit batch generators for the measurement tools.

tools/copyhound.py (compiled-HLO copy audit) and any device cost forensics
must lower THE SAME program: a batch derived
inside jit from the batch index, in the flagship bench's workload shape.
Two hand-rolled copies drifted within a day of each other (different
amount formulas, post lanes keeping ledger/code); one definition cannot.
"""

from __future__ import annotations

import jax.numpy as jnp

from .. import u128
from ..ops.state_machine import TF_PENDING, TF_POST


def gen_plain(b, *, lanes, count, n_accounts, id_base=1 << 35):
    """Plain-transfer batch derived from batch index ``b`` (a traced
    uint64): uniform accounts, mixed amounts."""
    lane = jnp.arange(lanes, dtype=jnp.uint64)
    gid = b.astype(jnp.uint64) * jnp.uint64(count) + lane
    h1 = u128.mix64(gid, jnp.uint64(0x1234))
    h2 = u128.mix64(gid, jnp.uint64(0x9876))
    dr = h1 % jnp.uint64(n_accounts)
    off = jnp.uint64(1) + h2 % jnp.uint64(n_accounts - 1)
    cr = (dr + off) % jnp.uint64(n_accounts)
    amount = jnp.uint64(1) + ((h1 >> jnp.uint64(32)) & jnp.uint64(0xFFFF))
    active = lane < jnp.uint64(count)
    z64 = jnp.zeros((lanes,), jnp.uint64)
    z32 = jnp.zeros((lanes,), jnp.uint32)
    return {
        "id_lo": jnp.where(active, jnp.uint64(id_base) + gid, 0),
        "id_hi": z64,
        "debit_account_id_lo": jnp.where(active, dr + 1, 0),
        "debit_account_id_hi": z64,
        "credit_account_id_lo": jnp.where(active, cr + 1, 0),
        "credit_account_id_hi": z64,
        "amount_lo": jnp.where(active, amount, 0),
        "amount_hi": z64,
        "pending_id_lo": z64, "pending_id_hi": z64,
        "user_data_128_lo": z64, "user_data_128_hi": z64,
        "user_data_64": z64, "user_data_32": z32, "timeout": z32,
        "ledger": jnp.where(active, jnp.uint32(1), z32),
        "code": jnp.where(active, jnp.uint32(10), z32),
        "flags": z32, "timestamp": z64,
    }


def gen_twop(b, *, lanes, count, n_accounts, id_base=1 << 36):
    """Two-phase batch: half pending creates, half posts of THOSE pendings
    (the bench's --two-phase in-batch resolution shape)."""
    half = count // 2
    lane = jnp.arange(lanes, dtype=jnp.uint64)
    base = b.astype(jnp.uint64) * jnp.uint64(count)
    is_post = lane >= jnp.uint64(half)
    gid = base + jnp.where(is_post, lane - jnp.uint64(half), lane)
    h1 = u128.mix64(gid, jnp.uint64(0x1234))
    dr = h1 % jnp.uint64(n_accounts)
    cr = (dr + jnp.uint64(3)) % jnp.uint64(n_accounts)
    amount = jnp.uint64(1) + (h1 & jnp.uint64(0xFF))
    active = lane < jnp.uint64(2 * half)
    tid = jnp.uint64(id_base) + base + lane
    ptid = jnp.uint64(id_base) + base + (lane - jnp.uint64(half))
    z64 = jnp.zeros((lanes,), jnp.uint64)
    z32 = jnp.zeros((lanes,), jnp.uint32)
    return {
        "id_lo": jnp.where(active, tid, 0), "id_hi": z64,
        "debit_account_id_lo": jnp.where(active & ~is_post, dr + 1, 0),
        "debit_account_id_hi": z64,
        "credit_account_id_lo": jnp.where(active & ~is_post, cr + 1, 0),
        "credit_account_id_hi": z64,
        "amount_lo": jnp.where(active & ~is_post, amount, 0),
        "amount_hi": z64,
        "pending_id_lo": jnp.where(active & is_post, ptid, 0),
        "pending_id_hi": z64,
        "user_data_128_lo": z64, "user_data_128_hi": z64,
        "user_data_64": z64, "user_data_32": z32, "timeout": z32,
        "ledger": jnp.where(active & ~is_post, jnp.uint32(1), z32),
        "code": jnp.where(active & ~is_post, jnp.uint32(10), z32),
        "flags": jnp.where(
            active,
            jnp.where(is_post, jnp.uint32(TF_POST), jnp.uint32(TF_PENDING)),
            z32,
        ),
        "timestamp": z64,
    }
