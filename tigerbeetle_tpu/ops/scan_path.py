"""Fully-general sequential commit path: full semantics as a lax.scan.

The vectorized fast path (state_machine.py) excludes the order-dependent
features: balancing transfers, two-phase post/void, balance limits, and
linked-chain rollback interacting with duplicates.  This module executes the
batch event-at-a-time *on device* inside one compiled ``lax.scan``, reproducing
the reference's strict in-order semantics exactly
(state_machine.zig:1002-1088 execute, :1239-1368 create_transfer,
:1391-1498 post_or_void_pending_transfer).

Linked-chain rollback (the reference's groove scopes, groove.zig scope_open/
scope_close + state_machine.zig:972-1000) is implemented as an undo log:
- every successful event records its account-balance writes, its transfer-table
  slot, and its posted-table slot;
- when a chain breaks, a fori_loop replays the undo records in reverse,
  restoring balances and tombstoning inserts (hash-table probes walk past
  tombstones, so lookups stay correct).

Raw per-event codes from the scan are then passed through the same
_chain_codes post-pass as the fast path to produce final result codes.

This path is latency-bound (~N sequential steps) and exists for correctness
completeness; the dispatcher sends hot batches to the vectorized kernels.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .. import types, u128
from ..u128 import U128
from . import hash_table as ht
from . import staging
from .state_machine import (
    ACCOUNT_COLS,
    AF_CREDITS_MUST_NOT_EXCEED_DEBITS,
    AF_DEBITS_MUST_NOT_EXCEED_CREDITS,
    AF_HISTORY,
    AF_PADDING,
    Ledger,
    MAX_PROBE,
    NS_PER_S,
    TF_BALANCING_CREDIT,
    TF_BALANCING_DEBIT,
    TF_LINKED,
    TF_PADDING,
    TF_PENDING,
    TF_POST,
    TF_VOID,
    TRANSFER_COLS,
    _chain_codes,
)

U64M = jnp.uint64(0xFFFF_FFFF_FFFF_FFFF)


def _staged_program(dtype):
    """jit a sequential kernel as a program over ``staging.stage_batch``'s
    operands, the ledger donated."""
    def jit(impl):
        return jax.jit(
            staging.staged(impl, dtype), donate_argnames=("ledger",)
        )
    return jit

BALANCE_FIELDS = (
    "debits_pending_lo",
    "debits_pending_hi",
    "debits_posted_lo",
    "debits_posted_hi",
    "credits_pending_lo",
    "credits_pending_hi",
    "credits_posted_lo",
    "credits_posted_hi",
)


def _first_code(checks) -> jnp.ndarray:
    """First firing (condition, code) wins — scalar precedence ladder."""
    code = jnp.uint32(0)
    for cond, c in reversed(checks):
        code = jnp.where(cond, jnp.uint32(c), code)
    return code


def _slookup(table: ht.Table, lo, hi):
    """Scalar lookup: returns (found, slot)."""
    res = ht.lookup(table, lo[None], hi[None], MAX_PROBE)
    return res.found[0], res.slot[0]


def _sprobe_free(table: ht.Table, lo, hi):
    """Scalar probe for the insert slot of a new key (first truly-empty slot
    in the key's probe sequence, skipping tombstones)."""
    cap = table.capacity
    mask = jnp.uint64(cap - 1)
    home = u128.mix64(lo, hi) & mask

    def cond(state):
        i, done, _ = state
        return ~done & (i < MAX_PROBE)

    def body(state):
        i, done, slot = state
        cur = (home + jnp.uint64(i)) & mask
        empty = (
            (table.key_lo[cur] == 0)
            & (table.key_hi[cur] == 0)
            & ~table.tombstone[cur]
        )
        slot = jnp.where(~done & empty, cur, slot)
        done = done | empty
        return i + 1, done, slot

    _, _, slot = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.bool_(False), jnp.uint64(0)))
    return slot


def _gather_row(table: ht.Table, slot, valid) -> Dict[str, jnp.ndarray]:
    safe = jnp.where(valid, slot, jnp.uint64(0))
    return {
        name: jnp.where(valid, col[safe], jnp.zeros((), col.dtype))
        for name, col in table.cols.items()
    }


def _set_row(table: ht.Table, slot, do, lo, hi, row: Dict[str, jnp.ndarray]) -> ht.Table:
    idx = jnp.where(do, slot, jnp.uint64(table.capacity))
    cols = {
        name: table.cols[name].at[idx].set(row[name].astype(table.cols[name].dtype), mode="drop")
        for name in table.cols
    }
    return table.replace(
        key_lo=table.key_lo.at[idx].set(lo, mode="drop"),
        key_hi=table.key_hi.at[idx].set(hi, mode="drop"),
        tombstone=table.tombstone.at[idx].set(False, mode="drop"),
        cols=cols,
        count=table.count + do.astype(jnp.uint64),
    )


def _update_cols(table: ht.Table, slot, do, updates: Dict[str, jnp.ndarray]) -> ht.Table:
    idx = jnp.where(do, slot, jnp.uint64(table.capacity))
    cols = dict(table.cols)
    for name, val in updates.items():
        cols[name] = cols[name].at[idx].set(val.astype(cols[name].dtype), mode="drop")
    return table.replace(cols=cols)


def _tombstone(table: ht.Table, slot, do) -> ht.Table:
    idx = jnp.where(do, slot, jnp.uint64(table.capacity))
    return table.replace(
        key_lo=table.key_lo.at[idx].set(jnp.uint64(0), mode="drop"),
        key_hi=table.key_hi.at[idx].set(jnp.uint64(0), mode="drop"),
        tombstone=table.tombstone.at[idx].set(True, mode="drop"),
        count=table.count - do.astype(jnp.uint64),
    )


def _balances(row: Dict[str, jnp.ndarray]) -> Dict[str, U128]:
    return {
        "dp": U128(row["debits_pending_lo"], row["debits_pending_hi"]),
        "dpo": U128(row["debits_posted_lo"], row["debits_posted_hi"]),
        "cp": U128(row["credits_pending_lo"], row["credits_pending_hi"]),
        "cpo": U128(row["credits_posted_lo"], row["credits_posted_hi"]),
    }


def _balance_updates(b: Dict[str, U128]) -> Dict[str, jnp.ndarray]:
    return {
        "debits_pending_lo": b["dp"].lo,
        "debits_pending_hi": b["dp"].hi,
        "debits_posted_lo": b["dpo"].lo,
        "debits_posted_hi": b["dpo"].hi,
        "credits_pending_lo": b["cp"].lo,
        "credits_pending_hi": b["cp"].hi,
        "credits_posted_lo": b["cpo"].lo,
        "credits_posted_hi": b["cpo"].hi,
    }


def _balance_lanes(b: Dict[str, U128]) -> jnp.ndarray:
    return jnp.stack(
        [b["dp"].lo, b["dp"].hi, b["dpo"].lo, b["dpo"].hi,
         b["cp"].lo, b["cp"].hi, b["cpo"].lo, b["cpo"].hi]
    )


# ---------------------------------------------------------------------------
# create_transfers — sequential
# ---------------------------------------------------------------------------


@_staged_program(types.TRANSFER_DTYPE)
def create_transfers_seq(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
) -> Tuple[Ledger, jax.Array]:
    n = batch["id_lo"].shape[0]
    count_i = count.astype(jnp.int32)
    ts_base = timestamp - count + jnp.uint64(1)
    sent = jnp.uint64(1) << jnp.uint64(63)  # undo-slot sentinel

    undo0 = {
        "acc_slot": jnp.full((n, 2), sent, jnp.uint64),
        "acc_vals": jnp.zeros((n, 2, 8), jnp.uint64),
        "tr_slot": jnp.full((n,), sent, jnp.uint64),
        "posted_slot": jnp.full((n,), sent, jnp.uint64),
        "hist": jnp.zeros((n,), jnp.bool_),
    }

    def step(carry, x):
        ledger, chain_start, chain_broken, undo = carry
        ev, i = x
        i = i.astype(jnp.int32)
        active = i < count_i

        linked = active & ((ev["flags"] & TF_LINKED) != 0)
        # Chain opening (execute, state_machine.zig:1022-1027).
        opens = linked & (chain_start < 0)
        chain_start = jnp.where(opens, i, chain_start)
        in_chain = chain_start >= 0

        chain_open_err = linked & (i == count_i - 1)
        ev_ts = ts_base + i.astype(jnp.uint64)

        code, effects = _transfer_logic(ledger, ev, ev_ts, timestamp)
        # execute()-level preemptions, in order (state_machine.zig:1021-1041).
        code = jnp.where(ev["timestamp"] != 0, jnp.uint32(3), code)
        code = jnp.where(chain_broken, jnp.uint32(1), code)
        code = jnp.where(chain_open_err, jnp.uint32(2), code)
        code = jnp.where(~active, jnp.uint32(0), code)

        ok = active & (code == 0)

        # Apply effects.
        ledger, undo_entry = _apply_transfer(ledger, effects, ok)
        undo = {
            "acc_slot": undo["acc_slot"].at[i].set(undo_entry["acc_slot"]),
            "acc_vals": undo["acc_vals"].at[i].set(undo_entry["acc_vals"]),
            "tr_slot": undo["tr_slot"].at[i].set(undo_entry["tr_slot"]),
            "posted_slot": undo["posted_slot"].at[i].set(undo_entry["posted_slot"]),
            "hist": undo["hist"].at[i].set(undo_entry["hist"]),
        }

        # Chain break -> rollback chain_start..i-1 in reverse
        # (state_machine.zig:1051-1066).
        breaks = active & (code != 0) & in_chain & ~chain_broken

        def rollback(ledger):
            def body(j, led):
                idx = (i - 1 - j).astype(jnp.int32)
                a_slots = undo["acc_slot"][idx]
                a_vals = undo["acc_vals"][idx]
                for leg in (1, 0):
                    slot = a_slots[leg]
                    do = slot < sent
                    led = led.replace(
                        accounts=_update_cols(
                            led.accounts,
                            slot,
                            do,
                            {
                                f: a_vals[leg, k]
                                for k, f in enumerate(BALANCE_FIELDS)
                            },
                        )
                    )
                t_slot = undo["tr_slot"][idx]
                led = led.replace(
                    transfers=_tombstone(led.transfers, t_slot, t_slot < sent)
                )
                p_slot = undo["posted_slot"][idx]
                led = led.replace(
                    posted=_tombstone(led.posted, p_slot, p_slot < sent)
                )
                # Pop the history append (the rolled-back row falls outside
                # the live window; the groove scope_close analogue,
                # state_machine.zig:981-996).
                led = led.replace(
                    history=led.history.replace(
                        count=led.history.count
                        - undo["hist"][idx].astype(jnp.uint64)
                    )
                )
                return led

            return jax.lax.fori_loop(0, (i - chain_start).astype(jnp.int32), body, ledger)

        ledger = jax.lax.cond(breaks, rollback, lambda l: l, ledger)
        chain_broken = chain_broken | breaks

        # Chain termination (state_machine.zig:1074-1082).
        ends = in_chain & (~linked | chain_open_err)
        chain_start = jnp.where(ends, jnp.int32(-1), chain_start)
        chain_broken = jnp.where(ends, jnp.bool_(False), chain_broken)

        return (ledger, chain_start, chain_broken, undo), code

    lanes = jnp.arange(n, dtype=jnp.int32)
    (ledger, _, _, _), raw_codes = jax.lax.scan(
        step,
        (ledger, jnp.int32(-1), jnp.bool_(False), undo0),
        (batch, lanes),
    )

    linked_mask = ((batch["flags"] & TF_LINKED) != 0) & (lanes < count_i)
    codes = _chain_codes(linked_mask, raw_codes, count)
    return ledger, codes


def _transfer_logic(ledger: Ledger, ev, ev_ts, batch_ts):
    """Full create_transfer decision logic for one event (scalar).

    Returns (code, effects). Effects carry everything _apply_transfer needs;
    all gathers/probes happen here so application is pure scatter."""
    tid = U128(ev["id_lo"], ev["id_hi"])
    flags = ev["flags"]
    post = (flags & TF_POST) != 0
    void = (flags & TF_VOID) != 0
    postvoid = post | void
    pending_f = (flags & TF_PENDING) != 0
    bal_dr = (flags & TF_BALANCING_DEBIT) != 0
    bal_cr = (flags & TF_BALANCING_CREDIT) != 0
    t_amount = U128(ev["amount_lo"], ev["amount_hi"])
    pend_id = U128(ev["pending_id_lo"], ev["pending_id_hi"])
    t_dr_id = U128(ev["debit_account_id_lo"], ev["debit_account_id_hi"])
    t_cr_id = U128(ev["credit_account_id_lo"], ev["credit_account_id_hi"])

    # Pending-transfer gather (post/void path, state_machine.zig:1409-1419).
    p_found, p_slot = _slookup(ledger.transfers, pend_id.lo, pend_id.hi)
    p = _gather_row(ledger.transfers, p_slot, p_found)
    p_is_pending = (p["flags"] & TF_PENDING) != 0
    p_amount = U128(p["amount_lo"], p["amount_hi"])
    p_ts = p["timestamp"]

    # Which accounts do we operate on?
    dr_id = u128.select(
        postvoid, U128(p["debit_account_id_lo"], p["debit_account_id_hi"]),
        t_dr_id,
    )
    cr_id = u128.select(
        postvoid, U128(p["credit_account_id_lo"], p["credit_account_id_hi"]),
        t_cr_id,
    )
    dr_found, dr_slot = _slookup(ledger.accounts, dr_id.lo, dr_id.hi)
    cr_found, cr_slot = _slookup(ledger.accounts, cr_id.lo, cr_id.hi)
    dr = _gather_row(ledger.accounts, dr_slot, dr_found)
    cr = _gather_row(ledger.accounts, cr_slot, cr_found)
    drb = _balances(dr)
    crb = _balances(cr)

    # Existing transfer with our id (state_machine.zig:1284, 1438).
    e_found, e_slot = _slookup(ledger.transfers, tid.lo, tid.hi)
    e = _gather_row(ledger.transfers, e_slot, e_found)

    # Posted groove (state_machine.zig:1440-1445).
    posted_found, posted_slot = _slookup(ledger.posted, p_ts, jnp.uint64(0))
    posted_val = _gather_row(ledger.posted, posted_slot, posted_found)["fulfillment"]

    zero = jnp.uint64(0)

    # ---------------- regular path (state_machine.zig:1239-1368) ----------
    # Balancing clamp (:1286-1306).
    amount0 = u128.select(
        (bal_dr | bal_cr) & u128.is_zero(t_amount), U128(U64M, zero), t_amount
    )
    dr_balance = u128.add_wrap(drb["dpo"], drb["dp"])
    avail_dr = u128.sub_saturate(drb["cpo"], dr_balance)
    amount1 = u128.select(bal_dr, u128.min_(amount0, avail_dr), amount0)
    exceeds_credits_bal = bal_dr & u128.is_zero(amount1)
    cr_balance = u128.add_wrap(crb["cpo"], crb["cp"])
    avail_cr = u128.sub_saturate(crb["dpo"], cr_balance)
    amount2 = u128.select(bal_cr, u128.min_(amount1, avail_cr), amount1)
    exceeds_debits_bal = bal_cr & ~exceeds_credits_bal & u128.is_zero(amount2)
    amount = amount2

    # Overflow ladder (:1308-1322).
    _, ov_dp = u128.add(amount, drb["dp"])
    _, ov_cp = u128.add(amount, crb["cp"])
    _, ov_dpo = u128.add(amount, drb["dpo"])
    _, ov_cpo = u128.add(amount, crb["cpo"])
    dr_total, ov_a = u128.add(drb["dp"], drb["dpo"])
    _, ov_d = u128.add(amount, dr_total)
    cr_total, ov_b = u128.add(crb["cp"], crb["cpo"])
    _, ov_c = u128.add(amount, cr_total)
    timeout_ns = ev["timeout"].astype(jnp.uint64) * jnp.uint64(NS_PER_S)
    ts_sum = ev_ts + timeout_ns
    ov_timeout = ts_sum < ev_ts

    # Limits (tigerbeetle.zig:31-39).
    dr_lim = (dr["flags"] & AF_DEBITS_MUST_NOT_EXCEED_CREDITS) != 0
    new_dr_tot, _ = u128.add(dr_total, amount)
    exceeds_credits_lim = dr_lim & u128.gt(new_dr_tot, drb["cpo"])
    cr_lim = (cr["flags"] & AF_CREDITS_MUST_NOT_EXCEED_DEBITS) != 0
    new_cr_tot, _ = u128.add(cr_total, amount)
    exceeds_debits_lim = cr_lim & u128.gt(new_cr_tot, crb["dpo"])

    exists_code = _exists_transfer_scalar(ev, e)

    regular_code = _first_code([
        ((flags & TF_PADDING) != 0, 4),
        (u128.is_zero(tid), 5),
        (u128.is_max(tid), 6),
        (u128.is_zero(t_dr_id), 8),
        (u128.is_max(t_dr_id), 9),
        (u128.is_zero(t_cr_id), 10),
        (u128.is_max(t_cr_id), 11),
        (u128.eq(t_dr_id, t_cr_id), 12),
        (~u128.is_zero(pend_id), 13),
        (~pending_f & (ev["timeout"] != 0), 17),
        (~bal_dr & ~bal_cr & u128.is_zero(t_amount), 18),
        (ev["ledger"] == 0, 19),
        (ev["code"] == 0, 20),
        (~dr_found, 21),
        (~cr_found, 22),
        (dr["ledger"] != cr["ledger"], 23),
        (ev["ledger"] != dr["ledger"], 24),
        (e_found, exists_code),
        (exceeds_credits_bal, 54),
        (exceeds_debits_bal, 55),
        (pending_f & ov_dp, 47),
        (pending_f & ov_cp, 48),
        (ov_dpo, 49),
        (ov_cpo, 50),
        (ov_d, 51),
        (ov_c, 52),
        (ov_timeout, 53),
        (exceeds_credits_lim, 54),
        (exceeds_debits_lim, 55),
    ])

    # ---------------- post/void path (state_machine.zig:1391-1498) --------
    pv_amount = u128.select(~u128.is_zero(t_amount), t_amount, p_amount)
    pv_exists_code = _exists_postvoid_scalar(ev, e, p)
    expiry_ns = p["timeout"].astype(jnp.uint64) * jnp.uint64(NS_PER_S)
    expired = (p["timeout"] != 0) & (ev_ts >= p_ts + expiry_ns)

    pv_code = _first_code([
        ((flags & TF_PADDING) != 0, 4),
        (u128.is_zero(tid), 5),
        (u128.is_max(tid), 6),
        (post & void, 7),
        (pending_f, 7),
        (bal_dr, 7),
        (bal_cr, 7),
        (u128.is_zero(pend_id), 14),
        (u128.is_max(pend_id), 15),
        (u128.eq(pend_id, tid), 16),
        (ev["timeout"] != 0, 17),
        (~p_found, 25),
        (~p_is_pending, 26),
        (
            ~u128.is_zero(t_dr_id)
            & ~u128.eq(t_dr_id, U128(p["debit_account_id_lo"], p["debit_account_id_hi"])),
            27,
        ),
        (
            ~u128.is_zero(t_cr_id)
            & ~u128.eq(t_cr_id, U128(p["credit_account_id_lo"], p["credit_account_id_hi"])),
            28,
        ),
        ((ev["ledger"] != 0) & (ev["ledger"] != p["ledger"]), 29),
        ((ev["code"] != 0) & (ev["code"] != p["code"]), 30),
        (u128.gt(pv_amount, p_amount), 31),
        (void & u128.lt(pv_amount, p_amount), 32),
        (e_found, pv_exists_code),
        (posted_found & (posted_val == 1), 33),
        (posted_found & (posted_val == 2), 34),
        (expired, 35),
    ])

    code = jnp.where(postvoid, pv_code, regular_code)

    # ---------------- effects --------------------------------------------
    # New transfer row.
    def pick(name, default):
        v = ev[name]
        return jnp.where(v != 0, v, default)

    row = {}
    for name in TRANSFER_COLS:
        row[name] = ev[name]
    row["timestamp"] = ev_ts
    # Regular path stores the clamped amount (state_machine.zig:1326-1328).
    row["amount_lo"] = jnp.where(postvoid, pv_amount.lo, amount.lo)
    row["amount_hi"] = jnp.where(postvoid, pv_amount.hi, amount.hi)
    # Post/void row composition (state_machine.zig:1455-1469).
    for side in ("debit_account_id", "credit_account_id"):
        for lane in ("_lo", "_hi"):
            row[side + lane] = jnp.where(
                postvoid, p[side + lane], ev[side + lane]
            )
    ud128_nz = (ev["user_data_128_lo"] != 0) | (ev["user_data_128_hi"] != 0)
    row["user_data_128_lo"] = jnp.where(
        postvoid,
        jnp.where(ud128_nz, ev["user_data_128_lo"], p["user_data_128_lo"]),
        ev["user_data_128_lo"],
    )
    row["user_data_128_hi"] = jnp.where(
        postvoid,
        jnp.where(ud128_nz, ev["user_data_128_hi"], p["user_data_128_hi"]),
        ev["user_data_128_hi"],
    )
    row["user_data_64"] = jnp.where(
        postvoid, pick("user_data_64", p["user_data_64"]), ev["user_data_64"]
    )
    row["user_data_32"] = jnp.where(
        postvoid, pick("user_data_32", p["user_data_32"]), ev["user_data_32"]
    )
    row["ledger"] = jnp.where(postvoid, p["ledger"], ev["ledger"])
    row["code"] = jnp.where(postvoid, p["code"], ev["code"])
    row["timeout"] = jnp.where(postvoid, jnp.uint32(0), ev["timeout"])

    # Balance deltas.
    eff_amount = u128.select(postvoid, pv_amount, amount)
    new_drb = dict(drb)
    new_crb = dict(crb)
    # Regular: pending -> dp/cp else dpo/cpo (state_machine.zig:1330-1338).
    reg_dp = u128.add_wrap(drb["dp"], eff_amount)
    reg_dpo = u128.add_wrap(drb["dpo"], eff_amount)
    reg_cp = u128.add_wrap(crb["cp"], eff_amount)
    reg_cpo = u128.add_wrap(crb["cpo"], eff_amount)
    # Post/void: release pending, post adds posted (state_machine.zig:1481-1491).
    pv_dp = u128.sub_wrap(drb["dp"], p_amount)
    pv_cp = u128.sub_wrap(crb["cp"], p_amount)
    pv_dpo = u128.add_wrap(drb["dpo"], u128.select(post, eff_amount, u128.lit(0)))
    pv_cpo = u128.add_wrap(crb["cpo"], u128.select(post, eff_amount, u128.lit(0)))

    new_drb["dp"] = u128.select(postvoid, pv_dp, u128.select(pending_f, reg_dp, drb["dp"]))
    new_drb["dpo"] = u128.select(postvoid, pv_dpo, u128.select(pending_f, drb["dpo"], reg_dpo))
    new_crb["cp"] = u128.select(postvoid, pv_cp, u128.select(pending_f, reg_cp, crb["cp"]))
    new_crb["cpo"] = u128.select(postvoid, pv_cpo, u128.select(pending_f, crb["cpo"], reg_cpo))

    effects = {
        "tid": tid,
        "row": row,
        "dr_slot": dr_slot,
        "cr_slot": cr_slot,
        "old_dr": _balance_lanes(drb),
        "old_cr": _balance_lanes(crb),
        "new_dr": _balance_updates(new_drb),
        "new_cr": _balance_updates(new_crb),
        "postvoid": postvoid,
        "posted_key": p_ts,
        "posted_val": jnp.where(post, jnp.uint32(1), jnp.uint32(2)),
        # History recording inputs (state_machine.zig:1342-1364).
        "dr_id": dr_id,
        "cr_id": cr_id,
        "dr_hist": (dr["flags"] & AF_HISTORY) != 0,
        "cr_hist": (cr["flags"] & AF_HISTORY) != 0,
        "ev_ts": ev_ts,
    }
    return code, effects


def _apply_transfer(ledger: Ledger, eff, ok):
    """Apply one event's effects (when ok) and return its undo entry."""
    sent = jnp.uint64(1) << jnp.uint64(63)

    # Account balance updates (two legs).
    accounts = _update_cols(ledger.accounts, eff["dr_slot"], ok, eff["new_dr"])
    accounts = _update_cols(accounts, eff["cr_slot"], ok, eff["new_cr"])

    # Transfer insert.
    t_slot = _sprobe_free(ledger.transfers, eff["tid"].lo, eff["tid"].hi)
    transfers = _set_row(
        ledger.transfers, t_slot, ok, eff["tid"].lo, eff["tid"].hi, eff["row"]
    )

    # Posted insert (post/void only).
    do_posted = ok & eff["postvoid"]
    p_slot = _sprobe_free(ledger.posted, eff["posted_key"], jnp.uint64(0))
    posted = _set_row(
        ledger.posted,
        p_slot,
        do_posted,
        eff["posted_key"],
        jnp.uint64(0),
        {"fulfillment": eff["posted_val"]},
    )

    # History append (state_machine.zig:1342-1364): regular path only, when
    # either account carries the history flag.  Sides without the flag stay
    # zeroed (std.mem.zeroInit there).
    h = ledger.history
    do_hist = ok & ~eff["postvoid"] & (eff["dr_hist"] | eff["cr_hist"])
    cap = jnp.uint64(h.capacity)
    # Append at count; the host guarantees capacity headroom before the batch
    # (machine.py grows the log), so count < cap whenever do_hist fires.
    h_idx = jnp.where(do_hist, jnp.minimum(h.count, cap), cap)  # cap -> dropped
    hist_row = {"timestamp": eff["ev_ts"]}
    for prefix, on, id128, bal in (
        ("dr", eff["dr_hist"], eff["dr_id"], eff["new_dr"]),
        ("cr", eff["cr_hist"], eff["cr_id"], eff["new_cr"]),
    ):
        z = jnp.uint64(0)
        hist_row[f"{prefix}_id_lo"] = jnp.where(on, id128.lo, z)
        hist_row[f"{prefix}_id_hi"] = jnp.where(on, id128.hi, z)
        for short, field in (
            ("dp", "debits_pending"), ("dpo", "debits_posted"),
            ("cp", "credits_pending"), ("cpo", "credits_posted"),
        ):
            hist_row[f"{prefix}_{short}_lo"] = jnp.where(on, bal[field + "_lo"], z)
            hist_row[f"{prefix}_{short}_hi"] = jnp.where(on, bal[field + "_hi"], z)
    history = h.replace(
        cols={
            name: h.cols[name].at[h_idx].set(hist_row[name], mode="drop")
            for name in h.cols
        },
        count=h.count + do_hist.astype(jnp.uint64),
    )

    undo_entry = {
        "acc_slot": jnp.stack(
            [
                jnp.where(ok, eff["dr_slot"], sent),
                jnp.where(ok, eff["cr_slot"], sent),
            ]
        ),
        "acc_vals": jnp.stack([eff["old_dr"], eff["old_cr"]]),
        "tr_slot": jnp.where(ok, t_slot, sent),
        "posted_slot": jnp.where(do_posted, p_slot, sent),
        "hist": do_hist,
    }
    return (
        ledger.replace(
            accounts=accounts, transfers=transfers, posted=posted, history=history
        ),
        undo_entry,
    )


def _exists_transfer_scalar(t, e):
    """create_transfer_exists (state_machine.zig:1370-1389), scalar."""

    def ne128(name):
        return (t[name + "_lo"] != e[name + "_lo"]) | (t[name + "_hi"] != e[name + "_hi"])

    c = jnp.uint32(46)
    c = jnp.where(t["code"] != e["code"], jnp.uint32(45), c)
    c = jnp.where(t["timeout"] != e["timeout"], jnp.uint32(44), c)
    c = jnp.where(t["user_data_32"] != e["user_data_32"], jnp.uint32(43), c)
    c = jnp.where(t["user_data_64"] != e["user_data_64"], jnp.uint32(42), c)
    c = jnp.where(ne128("user_data_128"), jnp.uint32(41), c)
    c = jnp.where(ne128("pending_id"), jnp.uint32(40), c)
    c = jnp.where(ne128("amount"), jnp.uint32(39), c)
    c = jnp.where(ne128("credit_account_id"), jnp.uint32(38), c)
    c = jnp.where(ne128("debit_account_id"), jnp.uint32(37), c)
    c = jnp.where(t["flags"] != e["flags"], jnp.uint32(36), c)
    return c


def _exists_postvoid_scalar(t, e, p):
    """post_or_void_pending_transfer_exists (state_machine.zig:1500-1561)."""

    def tz(name):
        return t[name] == 0

    def pair_ne(a, b, name):
        return (a[name + "_lo"] != b[name + "_lo"]) | (a[name + "_hi"] != b[name + "_hi"])

    t_amount_zero = (t["amount_lo"] == 0) & (t["amount_hi"] == 0)
    amount_ne = jnp.where(
        t_amount_zero, pair_ne(e, p, "amount"), pair_ne(t, e, "amount")
    )
    ud128_zero = (t["user_data_128_lo"] == 0) & (t["user_data_128_hi"] == 0)
    ud128_ne = jnp.where(
        ud128_zero, pair_ne(e, p, "user_data_128"), pair_ne(t, e, "user_data_128")
    )
    ud64_ne = jnp.where(
        tz("user_data_64"), e["user_data_64"] != p["user_data_64"],
        t["user_data_64"] != e["user_data_64"],
    )
    ud32_ne = jnp.where(
        tz("user_data_32"), e["user_data_32"] != p["user_data_32"],
        t["user_data_32"] != e["user_data_32"],
    )

    c = jnp.uint32(46)
    c = jnp.where(ud32_ne, jnp.uint32(43), c)
    c = jnp.where(ud64_ne, jnp.uint32(42), c)
    c = jnp.where(ud128_ne, jnp.uint32(41), c)
    c = jnp.where(pair_ne(t, e, "pending_id"), jnp.uint32(40), c)
    c = jnp.where(amount_ne, jnp.uint32(39), c)
    c = jnp.where(t["flags"] != e["flags"], jnp.uint32(36), c)
    return c


# ---------------------------------------------------------------------------
# create_accounts — sequential
# ---------------------------------------------------------------------------


@_staged_program(types.ACCOUNT_DTYPE)
def create_accounts_seq(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
) -> Tuple[Ledger, jax.Array]:
    n = batch["id_lo"].shape[0]
    count_i = count.astype(jnp.int32)
    ts_base = timestamp - count + jnp.uint64(1)
    sent = jnp.uint64(1) << jnp.uint64(63)

    undo0 = {"acc_ins_slot": jnp.full((n,), sent, jnp.uint64)}

    def step(carry, x):
        ledger, chain_start, chain_broken, undo = carry
        ev, i = x
        i = i.astype(jnp.int32)
        active = i < count_i

        linked = active & ((ev["flags"] & 1) != 0)
        opens = linked & (chain_start < 0)
        chain_start = jnp.where(opens, i, chain_start)
        in_chain = chain_start >= 0
        chain_open_err = linked & (i == count_i - 1)
        ev_ts = ts_base + i.astype(jnp.uint64)

        code = _account_logic(ledger, ev)
        code = jnp.where(ev["timestamp"] != 0, jnp.uint32(3), code)
        code = jnp.where(chain_broken, jnp.uint32(1), code)
        code = jnp.where(chain_open_err, jnp.uint32(2), code)
        code = jnp.where(~active, jnp.uint32(0), code)
        ok = active & (code == 0)

        aid_lo, aid_hi = ev["id_lo"], ev["id_hi"]
        slot = _sprobe_free(ledger.accounts, aid_lo, aid_hi)
        row = {name: ev[name] for name in ACCOUNT_COLS if name != "timestamp"}
        row["timestamp"] = ev_ts
        accounts = _set_row(ledger.accounts, slot, ok, aid_lo, aid_hi, row)
        ledger = ledger.replace(accounts=accounts)
        undo = {"acc_ins_slot": undo["acc_ins_slot"].at[i].set(jnp.where(ok, slot, sent))}

        breaks = active & (code != 0) & in_chain & ~chain_broken

        def rollback(ledger):
            def body(j, led):
                idx = (i - 1 - j).astype(jnp.int32)
                s = undo["acc_ins_slot"][idx]
                return led.replace(accounts=_tombstone(led.accounts, s, s < sent))

            return jax.lax.fori_loop(0, (i - chain_start).astype(jnp.int32), body, ledger)

        ledger = jax.lax.cond(breaks, rollback, lambda l: l, ledger)
        chain_broken = chain_broken | breaks

        ends = in_chain & (~linked | chain_open_err)
        chain_start = jnp.where(ends, jnp.int32(-1), chain_start)
        chain_broken = jnp.where(ends, jnp.bool_(False), chain_broken)

        return (ledger, chain_start, chain_broken, undo), code

    lanes = jnp.arange(n, dtype=jnp.int32)
    (ledger, _, _, _), raw_codes = jax.lax.scan(
        step,
        (ledger, jnp.int32(-1), jnp.bool_(False), undo0),
        (batch, lanes),
    )
    linked_mask = ((batch["flags"] & 1) != 0) & (lanes < count_i)
    codes = _chain_codes(linked_mask, raw_codes, count)
    return ledger, codes


def _account_logic(ledger: Ledger, ev):
    """create_account checks (state_machine.zig:1198-1237), scalar."""
    aid = U128(ev["id_lo"], ev["id_hi"])
    flags = ev["flags"]
    found, slot = _slookup(ledger.accounts, aid.lo, aid.hi)
    e = _gather_row(ledger.accounts, slot, found)

    exists_code = jnp.uint32(21)
    exists_code = jnp.where(ev["code"] != e["code"], jnp.uint32(20), exists_code)
    exists_code = jnp.where(ev["ledger"] != e["ledger"], jnp.uint32(19), exists_code)
    exists_code = jnp.where(ev["user_data_32"] != e["user_data_32"], jnp.uint32(18), exists_code)
    exists_code = jnp.where(ev["user_data_64"] != e["user_data_64"], jnp.uint32(17), exists_code)
    ud128_ne = (ev["user_data_128_lo"] != e["user_data_128_lo"]) | (
        ev["user_data_128_hi"] != e["user_data_128_hi"]
    )
    exists_code = jnp.where(ud128_ne, jnp.uint32(16), exists_code)
    exists_code = jnp.where(ev["flags"] != e["flags"], jnp.uint32(15), exists_code)

    nz = lambda name: (ev[name + "_lo"] != 0) | (ev[name + "_hi"] != 0)
    return _first_code([
        (ev["reserved"] != 0, 4),
        ((flags & AF_PADDING) != 0, 5),
        (u128.is_zero(aid), 6),
        (u128.is_max(aid), 7),
        (
            ((flags & AF_DEBITS_MUST_NOT_EXCEED_CREDITS) != 0)
            & ((flags & AF_CREDITS_MUST_NOT_EXCEED_DEBITS) != 0),
            8,
        ),
        (nz("debits_pending"), 9),
        (nz("debits_posted"), 10),
        (nz("credits_pending"), 11),
        (nz("credits_posted"), 12),
        (ev["ledger"] == 0, 13),
        (ev["code"] == 0, 14),
        (found, exists_code),
    ])
