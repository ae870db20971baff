"""Vectorized create_accounts / create_transfers commit kernels (the fast path).

The reference executes a batch one event at a time with hash lookups
(state_machine.zig:1002-1088, the per-event create_transfer loop :1239-1368).
These kernels execute the whole 8190-event batch as data-parallel device code:

- every validation check becomes an independent vector mask;
- the final result code per event is the *minimum* over failing checks' codes —
  sound because the result enums are precedence-ordered to match the exact
  sequential check order (tigerbeetle.zig:122-124, and see types.py);
- intra-batch duplicate ids are resolved with a sort + segmented-min "winner"
  pass (the first standalone-ok occurrence inserts; later occurrences compare
  against it with the exists ladder), mirroring in-order execution;
- linked chains become a segmented first-failure propagation
  (state_machine.zig:1015-1082);
- balance updates become exact u128 segment-sums via 16-bit limbs (no carries
  are lost: limb partial sums of <= 2*8190 terms stay under 2^30), applied with one
  deterministic scatter per column.

Preconditions (enforced by the host dispatcher in machine.py, which otherwise
routes the batch to the fully-general sequential path):
  P1 no account in the table carries limit or history flags;
  P2 the batch has no balancing_debit/balancing_credit/post/void flags;
  P3 all amounts < 2**64 and every account balance is bounded away from
     2**128 overflow (host tracks a global bound), so the overflow ladder
     (state_machine.zig:1308-1320) cannot fire;
  P4 the batch does not combine linked chains with intra-batch duplicate ids.

Under P1-P4 these kernels are bit-identical to the reference semantics — the
differential tests against testing/model.py check exactly that.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from .. import types, u128
from ..obs.metrics import registry as _metrics
from ..u128 import U128
from . import hash_table as ht
from . import staging

MAX_PROBE = 1 << 12


def _obs_jit(impl, name: str, **jit_kwargs):
    """jit an entry-point kernel with a per-kernel dispatch counter.

    The counter lives OUTSIDE the traced function (incrementing a tracer-
    side Python int inside jit would either fail or bake in a constant);
    the wrapper costs one attribute load + branch per dispatch when the
    registry is disabled.  The raw jitted callable rides along as
    ``.jitted`` for callers that need jit-object APIs (lower/clear_cache)."""
    jitted = jax.jit(impl, **jit_kwargs)

    @functools.wraps(impl)
    def dispatch(*args, **kwargs):
        if _metrics.enabled:
            _metrics.counter("ops.kernel." + name).inc()
        return jitted(*args, **kwargs)

    dispatch.jitted = jitted
    return dispatch

# Account value columns (table stores everything but the id key; `reserved` is
# validated to zero and not stored).
ACCOUNT_COLS = {
    "debits_pending_lo": jnp.uint64,
    "debits_pending_hi": jnp.uint64,
    "debits_posted_lo": jnp.uint64,
    "debits_posted_hi": jnp.uint64,
    "credits_pending_lo": jnp.uint64,
    "credits_pending_hi": jnp.uint64,
    "credits_posted_lo": jnp.uint64,
    "credits_posted_hi": jnp.uint64,
    "user_data_128_lo": jnp.uint64,
    "user_data_128_hi": jnp.uint64,
    "user_data_64": jnp.uint64,
    "user_data_32": jnp.uint32,
    "ledger": jnp.uint32,
    "code": jnp.uint32,
    "flags": jnp.uint32,
    "timestamp": jnp.uint64,
}

TRANSFER_COLS = {
    "debit_account_id_lo": jnp.uint64,
    "debit_account_id_hi": jnp.uint64,
    "credit_account_id_lo": jnp.uint64,
    "credit_account_id_hi": jnp.uint64,
    "amount_lo": jnp.uint64,
    "amount_hi": jnp.uint64,
    "pending_id_lo": jnp.uint64,
    "pending_id_hi": jnp.uint64,
    "user_data_128_lo": jnp.uint64,
    "user_data_128_hi": jnp.uint64,
    "user_data_64": jnp.uint64,
    "user_data_32": jnp.uint32,
    "timeout": jnp.uint32,
    "ledger": jnp.uint32,
    "code": jnp.uint32,
    "flags": jnp.uint32,
    "timestamp": jnp.uint64,
}

# The stored columns the secondary index keys a transfer by (ops/index.py): a
# commit program returns them for the rows it wrote, so the index never reads
# the table back.
INDEX_KEY_COLS = (
    "debit_account_id_lo", "debit_account_id_hi",
    "credit_account_id_lo", "credit_account_id_hi",
    "timestamp",
)

# Posted groove: pending-transfer timestamp -> fulfillment (1 posted, 2 voided)
# (state_machine.zig:1471-1479).
POSTED_COLS = {"fulfillment": jnp.uint32}

# Account flag bits (tigerbeetle.zig:42-57).
AF_LINKED = 1
AF_DEBITS_MUST_NOT_EXCEED_CREDITS = 2
AF_CREDITS_MUST_NOT_EXCEED_DEBITS = 4
AF_HISTORY = 8
AF_PADDING = 0xFFF0

# Transfer flag bits (tigerbeetle.zig:107-120).
TF_LINKED = 1
TF_PENDING = 2
TF_POST = 4
TF_VOID = 8
TF_BALANCING_DEBIT = 16
TF_BALANCING_CREDIT = 32
TF_PADDING = 0xFFC0

NS_PER_S = 1_000_000_000


# History rows mirror the reference's AccountHistoryGrooveValue
# (state_machine.zig:275-294): post-update balances of the debit and credit
# accounts of one committed transfer (sides zeroed unless that account carries
# the HISTORY flag), keyed by the transfer's timestamp.
HISTORY_COLS = {
    name: jnp.uint64
    for name in (
        "dr_id_lo", "dr_id_hi",
        "dr_dp_lo", "dr_dp_hi", "dr_dpo_lo", "dr_dpo_hi",
        "dr_cp_lo", "dr_cp_hi", "dr_cpo_lo", "dr_cpo_hi",
        "cr_id_lo", "cr_id_hi",
        "cr_dp_lo", "cr_dp_hi", "cr_dpo_lo", "cr_dpo_hi",
        "cr_cp_lo", "cr_cp_hi", "cr_cpo_lo", "cr_cpo_hi",
        "timestamp",
    )
}


@struct.dataclass
class History:
    """Append-only device log of history rows (the account_history groove,
    state_machine.zig:108,275-294).  Slots [0, count) are live; appends write
    at ``count`` and linked-chain rollback pops by decrementing it.  The log
    never wraps: the host grows the arrays before a batch could overflow them
    (grow_history), the way the reference's LSM absorbs unbounded inserts."""

    cols: Dict[str, jax.Array]
    count: jax.Array  # uint64 scalar

    @property
    def capacity(self) -> int:
        return self.cols["timestamp"].shape[0]


def make_history(capacity: int) -> History:
    assert capacity & (capacity - 1) == 0
    return History(
        cols={name: jnp.zeros((capacity,), dt) for name, dt in HISTORY_COLS.items()},
        count=jnp.uint64(0),
    )


def grow_history(history: History, min_capacity: int) -> History:
    """Host-side capacity doubling (keeps power-of-two sizing)."""
    cap = history.capacity
    while cap < min_capacity:
        cap *= 2
    if cap == history.capacity:
        return history
    return History(
        cols={
            name: jnp.concatenate(
                [col, jnp.zeros((cap - history.capacity,), col.dtype)]
            )
            for name, col in history.cols.items()
        },
        count=history.count,
    )


@struct.dataclass
class Ledger:
    """The full device-resident ledger state."""

    accounts: ht.Table
    transfers: ht.Table
    posted: ht.Table
    history: History


def make_ledger(
    accounts_capacity: int,
    transfers_capacity: int,
    posted_capacity: int,
    history_capacity: int = 1 << 16,
) -> Ledger:
    return Ledger(
        accounts=ht.make_table(accounts_capacity, ACCOUNT_COLS),
        transfers=ht.make_table(transfers_capacity, TRANSFER_COLS),
        posted=ht.make_table(posted_capacity, POSTED_COLS),
        history=make_history(history_capacity),
    )


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------


def _min_code(n: int, *checks: Tuple[jax.Array, int]) -> jax.Array:
    """Combine (mask, code) checks into the minimum firing code (0 if none).

    Sound because result enums are precedence-ordered to match the sequential
    check order (tigerbeetle.zig:122-124)."""
    big = jnp.uint32(0xFFFFFFFF)
    acc = jnp.full((n,), big, jnp.uint32)
    for mask, code in checks:
        acc = jnp.minimum(acc, jnp.where(mask, jnp.uint32(code), big))
    return jnp.where(acc == big, jnp.uint32(0), acc)


def _merge_code(primary: jax.Array, secondary: jax.Array) -> jax.Array:
    """min(primary, secondary) treating 0 as 'ok' (no failure)."""
    big = jnp.uint32(0xFFFFFFFF)
    p = jnp.where(primary == 0, big, primary)
    s = jnp.where(secondary == 0, big, secondary)
    m = jnp.minimum(p, s)
    return jnp.where(m == big, jnp.uint32(0), m)


class DupInfo(NamedTuple):
    winner_lane: jax.Array  # int32[N]: first standalone-ok lane of the id group
    has_winner: jax.Array  # bool[N]
    after_winner: jax.Array  # bool[N]: lane strictly after its group's winner


def _resolve_duplicates(
    id_lo: jax.Array, id_hi: jax.Array, standalone_ok: jax.Array, valid: jax.Array
) -> DupInfo:
    """Intra-batch duplicate-id resolution.

    In-order execution means: among events sharing an id, the first that passes
    validation inserts; subsequent ones see it as existing. We recover that
    order-dependence vectorized: group lanes by id (stable lexsort keeps lane
    order), take the segmented-min ok lane as winner."""
    n = id_lo.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    inf = jnp.int32(n)

    # Push invalid/padding lanes into a dedicated tail group via key munging
    # is unnecessary: their standalone_ok is False and ids may be 0; grouping
    # them together is harmless because winner selection requires ok.
    order = jnp.lexsort((lane, id_lo, id_hi))
    s_lo, s_hi, s_lane = id_lo[order], id_hi[order], lane[order]
    s_ok = standalone_ok[order] & valid[order]

    new_group = jnp.concatenate(
        [
            jnp.ones((1,), jnp.bool_),
            (s_lo[1:] != s_lo[:-1]) | (s_hi[1:] != s_hi[:-1]),
        ]
    )
    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1

    winner_g = jax.ops.segment_min(
        jnp.where(s_ok, s_lane, inf), gid, num_segments=n
    )
    winner_sorted = winner_g[gid]
    winner_lane = jnp.zeros((n,), jnp.int32).at[order].set(winner_sorted)
    has_winner = winner_lane < inf
    after_winner = has_winner & (lane > winner_lane)
    return DupInfo(winner_lane, has_winner, after_winner)


def _chain_codes(
    linked: jax.Array, codes: jax.Array, count: jax.Array
) -> jax.Array:
    """Linked-chain failure propagation (state_machine.zig:1015-1082).

    A chain is a maximal run of linked events plus one terminator. The first
    failing member keeps its own code; members before it roll back to
    linked_event_failed(1); members after it get linked_event_failed, except a
    linked batch-final event which gets linked_event_chain_open(2) regardless
    (checked before chain_broken in execute, state_machine.zig:1022-1032)."""
    n = linked.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    last_lane = count.astype(jnp.int32) - 1
    prev_linked = jnp.concatenate([jnp.zeros((1,), jnp.bool_), linked[:-1]])
    in_chain = linked | prev_linked
    start = linked & ~prev_linked
    chain_id = jnp.cumsum(start.astype(jnp.int32)) - 1

    # A linked batch-final event breaks its chain with chain_open.
    is_last = lane == last_lane
    codes_o = jnp.where(is_last & linked, jnp.uint32(2), codes)

    inf = jnp.int32(n)
    # Non-chain lanes route to a dummy segment (index n).
    seg = jnp.where(in_chain, chain_id, jnp.int32(n))
    fail_lane_g = jax.ops.segment_min(
        jnp.where(in_chain & (codes_o != 0), lane, inf), seg, num_segments=n + 1
    )
    f = fail_lane_g[seg]  # per-lane: first failing lane of my chain (inf if none)

    chain_failed = in_chain & (f < inf)
    out = jnp.where(
        chain_failed,
        jnp.where(
            lane < f,
            jnp.uint32(1),
            jnp.where(
                lane == f,
                codes_o,
                jnp.where(is_last & linked, jnp.uint32(2), jnp.uint32(1)),
            ),
        ),
        codes_o,
    )
    return out


def _u128_col(cols: Dict[str, jax.Array], name: str) -> U128:
    return U128(cols[name + "_lo"], cols[name + "_hi"])


def _timestamps(count: jax.Array, timestamp: jax.Array, n: int) -> jax.Array:
    # event.timestamp = batch_timestamp - len + index + 1 (state_machine.zig:1035)
    lane = jnp.arange(n, dtype=jnp.uint64)
    return timestamp - count + lane + jnp.uint64(1)


# ---------------------------------------------------------------------------
# create_accounts
# ---------------------------------------------------------------------------


def account_codes(
    batch: Dict[str, jax.Array],
    found: jax.Array,
    e: Dict[str, jax.Array],
    count: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Pure create_accounts validation (state_machine.zig:1198-1237): returns
    (codes, ok). ``found``/``e`` are the table-existence gather, however the
    table is sharded — replicated compute."""
    n = batch["id_lo"].shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    valid = lane < count.astype(jnp.int32)

    bid = _u128_col(batch, "id")
    flags = batch["flags"]
    linked = (flags & AF_LINKED).astype(jnp.bool_) & valid

    dp = _u128_col(batch, "debits_pending")
    dpo = _u128_col(batch, "debits_posted")
    cp = _u128_col(batch, "credits_pending")
    cpo = _u128_col(batch, "credits_posted")

    exists_code = _exists_ladder_accounts(batch, e, n)

    standalone = _min_code(
        n,
        ((batch["timestamp"] != 0), 3),  # execute(): timestamp_must_be_zero
        ((batch["reserved"] != 0), 4),
        ((flags & AF_PADDING) != 0, 5),
        (u128.is_zero(bid), 6),
        (u128.is_max(bid), 7),
        (
            ((flags & AF_DEBITS_MUST_NOT_EXCEED_CREDITS) != 0)
            & ((flags & AF_CREDITS_MUST_NOT_EXCEED_DEBITS) != 0),
            8,
        ),
        (~u128.is_zero(dp), 9),
        (~u128.is_zero(dpo), 10),
        (~u128.is_zero(cp), 11),
        (~u128.is_zero(cpo), 12),
        ((batch["ledger"] == 0), 13),
        ((batch["code"] == 0), 14),
    )
    standalone = _merge_code(standalone, jnp.where(found, exists_code, 0))

    # Intra-batch duplicates: later lanes compare against the winner's event.
    dup = _resolve_duplicates(bid.lo, bid.hi, standalone == 0, valid)
    intra = _exists_ladder_accounts(
        batch, {k: v[dup.winner_lane.clip(0, n - 1)] for k, v in batch.items()}, n
    )
    codes = jnp.where(
        dup.after_winner, jnp.where(standalone == 0, intra, standalone), standalone
    )

    codes = _chain_codes(linked, codes, count)
    ok = (codes == 0) & valid
    return codes, ok


def account_rows(
    batch: Dict[str, jax.Array], count: jax.Array, timestamp: jax.Array
) -> Dict[str, jax.Array]:
    """Rows to insert for accepted create_accounts events (assigned timestamps)."""
    n = batch["id_lo"].shape[0]
    ts = _timestamps(count, timestamp, n)
    return {
        name: (batch[name] if name != "timestamp" else ts).astype(dt)
        for name, dt in ACCOUNT_COLS.items()
    }


def create_accounts_impl(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
) -> Tuple[Ledger, jax.Array]:
    """Vectorized create_accounts (state_machine.zig:1198-1237).

    ``batch`` is the SoA of ACCOUNT_DTYPE columns padded to a fixed lane count;
    ``count`` is the true event count; ``timestamp`` the batch prepare
    timestamp. Returns (ledger, result codes uint32[N]) — 0 is ok, and lanes
    >= count are don't-care."""
    n = batch["id_lo"].shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    valid = lane < count.astype(jnp.int32)

    bid = _u128_col(batch, "id")

    # Table existence + exists ladder (state_machine.zig:1218-1237).
    look = ht.lookup(ledger.accounts, bid.lo, bid.hi, MAX_PROBE)
    found = look.found & valid
    e = ht.gather_cols(ledger.accounts, look.slot, found)

    codes, ok = account_codes(batch, found, e, count)
    rows = account_rows(batch, count, timestamp)
    accounts, _ = ht.insert(ledger.accounts, bid.lo, bid.hi, ok, rows, MAX_PROBE)
    return ledger.replace(accounts=accounts), codes


create_accounts = _obs_jit(
    staging.staged(create_accounts_impl, types.ACCOUNT_DTYPE),
    "create_accounts", donate_argnames=("ledger",),
)


def _exists_ladder_accounts(
    t: Dict[str, jax.Array], e: Dict[str, jax.Array], n: int
) -> jax.Array:
    """create_account_exists comparison ladder (state_machine.zig:1227-1237),
    evaluated in reverse so higher-precedence checks overwrite."""
    c = jnp.full((n,), 21, jnp.uint32)  # exists
    c = jnp.where(t["code"] != e["code"], jnp.uint32(20), c)
    c = jnp.where(t["ledger"] != e["ledger"], jnp.uint32(19), c)
    c = jnp.where(t["user_data_32"] != e["user_data_32"], jnp.uint32(18), c)
    c = jnp.where(t["user_data_64"] != e["user_data_64"], jnp.uint32(17), c)
    ud128_ne = (t["user_data_128_lo"] != e["user_data_128_lo"]) | (
        t["user_data_128_hi"] != e["user_data_128_hi"]
    )
    c = jnp.where(ud128_ne, jnp.uint32(16), c)
    c = jnp.where(t["flags"] != e["flags"], jnp.uint32(15), c)
    return c


# ---------------------------------------------------------------------------
# create_transfers (fast path)
# ---------------------------------------------------------------------------


class TransferCtx(NamedTuple):
    """Gathered context for transfer validation: everything the (replicated)
    validation pass needs, independent of how the tables are sharded."""

    dr_found: jax.Array
    cr_found: jax.Array
    dr_slot: jax.Array  # global slot ids (sharding-aware callers encode owner)
    cr_slot: jax.Array
    dr: Dict[str, jax.Array]
    cr: Dict[str, jax.Array]
    ex_found: jax.Array
    e: Dict[str, jax.Array]


def transfer_codes(
    batch: Dict[str, jax.Array],
    ctx: TransferCtx,
    count: jax.Array,
    timestamp: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pure validation pass: (codes, ok, ts, pending). Identical whether the
    gathers came from a local table or a sharded one (replicated compute)."""
    n = batch["id_lo"].shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    valid = lane < count.astype(jnp.int32)

    tid = _u128_col(batch, "id")
    dr_id = _u128_col(batch, "debit_account_id")
    cr_id = _u128_col(batch, "credit_account_id")
    amt = _u128_col(batch, "amount")
    pend = _u128_col(batch, "pending_id")
    flags = batch["flags"]
    linked = (flags & TF_LINKED).astype(jnp.bool_) & valid
    pending = (flags & TF_PENDING).astype(jnp.bool_)

    ts = _timestamps(count, timestamp, n)
    both = ctx.dr_found & ctx.cr_found

    exists_code = _exists_ladder_transfers(batch, ctx.e, n)

    # overflows_timeout (state_machine.zig:1322): ts + timeout*1e9 > u64 max.
    timeout_ns = batch["timeout"].astype(jnp.uint64) * jnp.uint64(NS_PER_S)
    ts_sum = ts + timeout_ns
    timeout_overflow = ts_sum < ts

    standalone = _min_code(
        n,
        ((batch["timestamp"] != 0), 3),
        (((flags & TF_PADDING) != 0), 4),
        (u128.is_zero(tid), 5),
        (u128.is_max(tid), 6),
        (u128.is_zero(dr_id), 8),
        (u128.is_max(dr_id), 9),
        (u128.is_zero(cr_id), 10),
        (u128.is_max(cr_id), 11),
        (u128.eq(dr_id, cr_id), 12),
        (~u128.is_zero(pend), 13),
        (~pending & (batch["timeout"] != 0), 17),
        (u128.is_zero(amt), 18),
        ((batch["ledger"] == 0), 19),
        ((batch["code"] == 0), 20),
        (valid & ~ctx.dr_found, 21),
        (valid & ~ctx.cr_found, 22),
        (both & (ctx.dr["ledger"] != ctx.cr["ledger"]), 23),
        (both & (batch["ledger"] != ctx.dr["ledger"]), 24),
        (timeout_overflow, 53),
    )
    standalone = _merge_code(
        standalone, jnp.where(ctx.ex_found, exists_code, 0)
    )

    # Intra-batch duplicate ids.
    dup = _resolve_duplicates(tid.lo, tid.hi, standalone == 0, valid)
    w = dup.winner_lane.clip(0, n - 1)
    winner_event = {k: v[w] for k, v in batch.items()}
    intra = _exists_ladder_transfers(batch, winner_event, n)
    codes = jnp.where(
        dup.after_winner, jnp.where(standalone == 0, intra, standalone), standalone
    )

    codes = _chain_codes(linked, codes, count)
    ok = (codes == 0) & valid
    return codes, ok, ts, pending


class BalancePlan(NamedTuple):
    """Sorted, segment-summed balance deltas keyed by global account slot.

    ``s_slot[i]`` is the sorted global slot for sorted-lane i; ``head`` marks
    the first lane of each slot group; ``deltas[field] = (d_lo, d_hi)`` is the
    u128 total delta for the lane's group."""

    s_slot: jax.Array
    head: jax.Array
    deltas: Dict[str, Tuple[jax.Array, jax.Array]]


def balance_plan(
    dr_slot: jax.Array,
    cr_slot: jax.Array,
    ok: jax.Array,
    amt_lo: jax.Array,
    pending: jax.Array,
    sentinel,
) -> BalancePlan:
    """Exact u128 per-account balance deltas via 16-bit limb segment sums.

    Replaces the reference's two sequential balance updates per event
    (state_machine.zig:1330-1338) with sort + segment-sum: limb partial sums of
    <= 2*8190 terms below 2^16 stay under 2^30, so a uint32 holds them and
    no carries are lost.  (uint32 sums, not uint64 sums of 32-bit limbs: a
    64-bit scatter-add is the chip's two-operand form, hash_table docstring.)"""
    n = ok.shape[0]
    assert 2 * n <= 1 << 16, "a 16-bit limb's sum must fit uint32"
    sent = jnp.uint64(sentinel)
    ok2 = jnp.concatenate([ok, ok])
    slots2 = jnp.concatenate([dr_slot, cr_slot])
    slots2 = jnp.where(ok2, slots2, sent)
    amt2 = jnp.concatenate([amt_lo, amt_lo])  # P3: amount_hi == 0
    pending2 = jnp.concatenate([pending, pending])
    is_dr2 = jnp.concatenate(
        [jnp.ones((n,), jnp.bool_), jnp.zeros((n,), jnp.bool_)]
    )

    order = jnp.argsort(slots2)
    s_slot = slots2[order]
    s_amt = amt2[order]
    s_pending = pending2[order]
    s_is_dr = is_dr2[order]
    s_live = s_slot < sent

    head = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), s_slot[1:] != s_slot[:-1]]
    ) & s_live
    gid = jnp.cumsum(head.astype(jnp.int32)) - 1
    gid = jnp.where(s_live, gid, 2 * n)  # dead lanes -> dummy segment

    limbs = u128.limbs16(s_amt)

    # ONE fused segment-sum over a (2N, 16) matrix — (field, limb) pairs as
    # columns — instead of sixteen independent passes over the leg arrays.
    fields = (
        ("debits_pending", s_is_dr & s_pending),
        ("debits_posted", s_is_dr & ~s_pending),
        ("credits_pending", ~s_is_dr & s_pending),
        ("credits_posted", ~s_is_dr & ~s_pending),
    )
    cols = []
    for _name, mask in fields:
        m = mask & s_live
        cols.extend(jnp.where(m, limb, jnp.uint32(0)) for limb in limbs)
    stacked = jnp.stack(cols, axis=1)  # (2N, 16) uint32
    summed = jax.ops.segment_sum(stacked, gid, num_segments=2 * n + 1)
    per_leg = summed[gid].astype(jnp.uint64)  # gathered back to leg domain

    deltas = {}
    for i, (field, _mask) in enumerate(fields):
        s0, s1, s2, s3 = (per_leg[:, 4 * i + k] for k in range(4))
        deltas[field] = u128.from_limbs32(
            s0 + (s1 << jnp.uint64(16)), s2 + (s3 << jnp.uint64(16))
        )
    return BalancePlan(s_slot=s_slot, head=head, deltas=deltas)


def apply_balance_plan(accounts: ht.Table, plan: BalancePlan) -> ht.Table:
    """Gather-old + add-delta + scatter at group heads (unique slots)."""
    sent = jnp.uint64(accounts.capacity)
    head_valid = plan.head & (plan.s_slot < sent)
    acc = ht.gather_cols(
        accounts, jnp.where(head_valid, plan.s_slot, 0), head_valid
    )
    updates = {}
    for field, (d_lo, d_hi) in plan.deltas.items():
        old = U128(acc[field + "_lo"], acc[field + "_hi"])
        new, _ = u128.add(old, U128(d_lo, d_hi))  # P3: cannot overflow
        updates[field + "_lo"] = new.lo
        updates[field + "_hi"] = new.hi
    return ht.scatter_cols(
        accounts, jnp.where(head_valid, plan.s_slot, sent), head_valid, updates
    )


def create_transfers_impl(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
) -> Tuple[Ledger, jax.Array]:
    """Vectorized create_transfers under preconditions P1-P4 (module docstring).

    Mirrors state_machine.zig:1239-1368 with the balancing/post-void/limit/
    overflow branches statically excluded."""
    tid = _u128_col(batch, "id")
    dr_id = _u128_col(batch, "debit_account_id")
    cr_id = _u128_col(batch, "credit_account_id")
    amt = _u128_col(batch, "amount")
    n = batch["id_lo"].shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    valid = lane < count.astype(jnp.int32)

    # The tb/<phase> scopes name the phases in a device trace (an
    # operation's op_name); metadata only.
    with jax.named_scope("tb/probe"):
        dr_look = ht.lookup(ledger.accounts, dr_id.lo, dr_id.hi, MAX_PROBE)
        cr_look = ht.lookup(ledger.accounts, cr_id.lo, cr_id.hi, MAX_PROBE)
        ex_look = ht.lookup(ledger.transfers, tid.lo, tid.hi, MAX_PROBE)
        dr_found = dr_look.found & valid
        cr_found = cr_look.found & valid
        ex_found = ex_look.found & valid
        ctx = TransferCtx(
            dr_found=dr_found,
            cr_found=cr_found,
            dr_slot=dr_look.slot,
            cr_slot=cr_look.slot,
            dr=ht.gather_cols(ledger.accounts, dr_look.slot, dr_found),
            cr=ht.gather_cols(ledger.accounts, cr_look.slot, cr_found),
            ex_found=ex_found,
            e=ht.gather_cols(ledger.transfers, ex_look.slot, ex_found),
        )

    with jax.named_scope("tb/validate"):
        codes, ok, ts, pending = transfer_codes(batch, ctx, count, timestamp)

    with jax.named_scope("tb/balance"):
        plan = balance_plan(
            ctx.dr_slot, ctx.cr_slot, ok, amt.lo, pending,
            ledger.accounts.capacity,
        )
        accounts = apply_balance_plan(ledger.accounts, plan)

    # --- transfer inserts (timestamps recomputed in transfer_rows CSE under jit) ---
    with jax.named_scope("tb/insert"):
        rows = transfer_rows(batch, count, timestamp)
        transfers, _ = ht.insert(
            ledger.transfers, tid.lo, tid.hi, ok, rows, MAX_PROBE
        )

    return ledger.replace(accounts=accounts, transfers=transfers), codes


create_transfers_fast = _obs_jit(
    staging.staged(create_transfers_impl, types.TRANSFER_DTYPE),
    "create_transfers_fast", donate_argnames=("ledger",),
)


def create_transfers_fast_probed_impl(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
) -> Tuple[Ledger, jax.Array, jax.Array, jax.Array, jax.Array,
           Dict[str, jax.Array], jax.Array]:
    """Fast kernel + the transfers probe_overflow flag as a third output.

    The overflow flag is widened to a FRESH uint32 buffer (never aliased
    into the returned ledger's pytree): a deferred readback handle
    (machine.DeviceCommitHandle) must still be able to fetch it after a
    LATER dispatch donates the ledger's buffers — reading
    ``ledger.transfers.probe_overflow`` at resolve time would trip the
    donation check.  Riding the commit dispatch, it costs zero extra syncs
    (the codes D2H carries it along).

    What the caller's index maintenance needs is passed through as
    outputs: the id columns, ``index_keys`` (the account columns and the
    timestamps the kernel stored) and ``written_lanes`` (the lanes it
    stored a row for), so the append costs the host one dispatch and no
    mask or slice of its own (a slice of a staged operand taken on the host
    would be a program the device waits for).  The staged operands are NOT
    donated: the id columns are slices of a packed buffer and could not
    alias it, the 1 MB is fresh for every request and freed with it, and
    on XLA-CPU ``device_put`` may alias the host arrays zero-copy (the
    sharded steps' rule, ``ops/staging.py``)."""
    id_lo, id_hi = batch["id_lo"], batch["id_hi"]
    keys = index_keys(batch, count, timestamp)
    ledger, codes = create_transfers_impl(ledger, batch, count, timestamp)
    return (
        ledger, codes, ledger.transfers.probe_overflow.astype(jnp.uint32),
        id_lo, id_hi, keys, written_lanes(codes, count),
    )


create_transfers_fast_probed = _obs_jit(
    staging.staged(create_transfers_fast_probed_impl, types.TRANSFER_DTYPE),
    "create_transfers_fast_probed", donate_argnames=("ledger",),
)


def transfer_rows(
    batch: Dict[str, jax.Array], count: jax.Array, timestamp: jax.Array
) -> Dict[str, jax.Array]:
    """Rows to insert for accepted create_transfers events."""
    n = batch["id_lo"].shape[0]
    ts = _timestamps(count, timestamp, n)
    return {
        name: (batch[name] if name != "timestamp" else ts).astype(dt)
        for name, dt in TRANSFER_COLS.items()
    }


def index_keys(
    batch: Dict[str, jax.Array], count: jax.Array, timestamp: jax.Array
) -> Dict[str, jax.Array]:
    """INDEX_KEY_COLS of the rows ``transfer_rows`` stores for this batch:
    what a fast commit program hands the secondary index."""
    rows = transfer_rows(batch, count, timestamp)
    return {name: rows[name] for name in INDEX_KEY_COLS}


def written_lanes(codes: jax.Array, count: jax.Array) -> jax.Array:
    """The lanes a commit stored a row for: result code 0, inside the
    batch (the secondary index's ``ok`` mask)."""
    lane = jnp.arange(codes.shape[0], dtype=jnp.uint64)
    return (codes == 0) & (lane < count)


def _exists_ladder_transfers(
    t: Dict[str, jax.Array], e: Dict[str, jax.Array], n: int
) -> jax.Array:
    """create_transfer_exists ladder (state_machine.zig:1370-1389), reverse
    evaluation order so higher-precedence comparisons overwrite."""

    def ne128(name):
        return (t[name + "_lo"] != e[name + "_lo"]) | (
            t[name + "_hi"] != e[name + "_hi"]
        )

    c = jnp.full((n,), 46, jnp.uint32)  # exists
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


# ---------------------------------------------------------------------------
# Lookups (state_machine.zig:1091-1126)
# ---------------------------------------------------------------------------


def lookup_accounts_impl(
    ledger: Ledger, id_lo: jax.Array, id_hi: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    look = ht.lookup(ledger.accounts, id_lo, id_hi, MAX_PROBE)
    cols = ht.gather_cols(ledger.accounts, look.slot, look.found)
    cols["id_lo"] = jnp.where(look.found, id_lo, 0)
    cols["id_hi"] = jnp.where(look.found, id_hi, 0)
    return look.found, cols


lookup_accounts = _obs_jit(lookup_accounts_impl, "lookup_accounts")


def lookup_transfers_impl(
    ledger: Ledger, id_lo: jax.Array, id_hi: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    look = ht.lookup(ledger.transfers, id_lo, id_hi, MAX_PROBE)
    cols = ht.gather_cols(ledger.transfers, look.slot, look.found)
    cols["id_lo"] = jnp.where(look.found, id_lo, 0)
    cols["id_hi"] = jnp.where(look.found, id_hi, 0)
    return look.found, cols


lookup_transfers = _obs_jit(lookup_transfers_impl, "lookup_transfers")


# ---------------------------------------------------------------------------
# Parity digest (the testing/hash_log analogue, testing/hash_log.zig:1-5)
# ---------------------------------------------------------------------------


@jax.jit
def ledger_digest(ledger: Ledger) -> jax.Array:
    """Order-independent deterministic digest of all account balances.

    Sum over live slots of mix64 over (id, balances, timestamp) — the on-device
    analogue of the reference's hash_log/StorageChecker parity oracles."""
    a = ledger.accounts
    live = (a.key_lo != 0) | (a.key_hi != 0)
    h = u128.mix64(a.key_lo, a.key_hi)
    for f in (
        "debits_pending",
        "debits_posted",
        "credits_pending",
        "credits_posted",
    ):
        h = u128.mix64(h ^ a.cols[f + "_lo"], h ^ a.cols[f + "_hi"])
    h = u128.mix64(h, a.cols["timestamp"])
    return jnp.sum(jnp.where(live, h, jnp.uint64(0)))
