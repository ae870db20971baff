"""Fully-vectorized create_transfers commit kernel (the round-2/3 fast path).

Covers the COMPLETE order-dependent semantics that round 1 delegated to the
sequential lax.scan path, in one data-parallel dispatch:

- two-phase pending / post_pending / void_pending transfers
  (state_machine.zig:1391-1498), including post/void of a pending transfer
  created EARLIER IN THE SAME BATCH, double-post/void detection within the
  batch (first ok fulfillment wins, later ones get already_posted/voided),
  and expiry (:1449-1453);
- balancing_debit / balancing_credit clamps (state_machine.zig:1286-1306)
  evaluated per event against that event's EXACT running pre-balances;
- balance-limit accounts (tigerbeetle.zig:31-39): exceeds_credits /
  exceeds_debits evaluated per event, exactly;
- per-event-exact overflow checks (:1308-1322) as first-class result codes
  (47..52) — not a host re-route;
- history rows (:1342-1364) with exact post-event balances of BOTH sides of
  every recorded account, from the same running balances;
- intra-batch duplicate ids and linked chains as in the v1 kernel.

Running balances are reconstructed per event without a sequential scan: each
event contributes a debit leg (2i) and a credit leg (2i+1); legs are sorted
by (account slot, leg position) and segmented prefix sums over the slot runs
of all four balance fields (debits_pending/posted, credits_pending/posted)
yield every leg's exact pre- and post-event account state — leg position
order IS event order, so the exclusive prefix at a leg includes precisely
the effects of earlier accepted events, both sides.

Because acceptance (and balancing-clamped amounts) feed back into later
events' balances, the balance machinery lives INSIDE the Jacobi fixpoint
iteration: pass k computes balances from pass k-1's (accepted, amount)
vector, then re-evaluates every ladder.  References only point to earlier
lanes and a stable pass (codes AND amounts unchanged) is a fixpoint of the
exact "evaluate lane i given outcomes of lanes j<i" operator, whose fixpoint
is unique and equal to the sequential answer (induction over lanes).  The
pass runs in a loop that ends at the first stable pass, or at the pass count
the wave schedule proves (docs/waves.md): pass k+1 resolves every batch
whose outcome-change cascade depth is <= k (uncontended batches stabilize
in 2 passes, or run the 1 their wave bound proves; each clamp/rejection
cascade adds 1), up to _MAX_PASSES; deeper cascades set FLAG_SEQ and run
sequentially.  The loop is a static-trip lax.scan whose every pass sits
behind a lax.cond on that exit (see _kernel_core).

The remaining FLAG_SEQ routes are genuinely order-chaotic or out-of-scope
for the u64-limb delta machinery: unconverged fixpoints, u128 amounts,
linked chains interacting with intra-batch references/post-void, failed
linked chains whose members' codes are balance-dependent (the sequential
path sees the chain's transient effects; the fixpoint sees the rollback),
and balance reconstructions that overflow u128.  When any flag bit is set
the kernel applies NOTHING (every scatter is masked off; the returned ledger
equals the input) and the host dispatcher (machine.py) re-routes the batch
to the sequential path or grows a table and retries.

Structure (round-3 refactor for the sharded path, parallel/sharded.py):

    GatherCtx       every table-derived input, assembled either by local
                    ht.lookup (single chip) or masked-probe + psum combine
                    over a device mesh (every shard then holds the full,
                    replicated context);
    _kernel_core    the PURE batch semantics: Jacobi loop, ladders, balance
                    legs — identical replicated math on every shard, no
                    table access;
    apply           claims + scatters, owner-local on a mesh.

Device names (docs/tracing.md; ``jax.named_scope``, metadata only): the
phases of the single-chip program carry ``tb/full_gather`` (the id index,
its in-batch pending join, and every table probe and gather of
build_gather_ctx), ``tb/full_waves`` (_wave_schedule), ``tb/full_pass`` (one
Jacobi pass: _leg_balances and the ladders), ``tb/full_apply`` (the
transfers claim, the balance scatter, the row and history writes) and
``tb/full_posted`` (the posted table's claim and fulfillment write).  The
routing flags and history values taken from the fixpoint carry none.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .. import types, u128
from ..u128 import U128
from . import hash_table as ht
from . import staging
from .state_machine import (
    AF_CREDITS_MUST_NOT_EXCEED_DEBITS,
    AF_DEBITS_MUST_NOT_EXCEED_CREDITS,
    AF_HISTORY,
    INDEX_KEY_COLS,
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
    _timestamps,
    _u128_col,
    written_lanes,
)

# Routing flag bits returned by the kernel (uint32). Nonzero => nothing was
# applied; the host must act and re-dispatch.
FLAG_SEQ = 1  # order-dependent semantics: run the sequential path
FLAG_GROW_ACCOUNTS = 2  # a probe hit MAX_PROBE: grow the table + retry
FLAG_GROW_TRANSFERS = 4
FLAG_GROW_POSTED = 8
FLAG_COLD = 16  # an id/pending_id may live in the cold spill: host resolves

_U64MAX = jnp.uint64(0xFFFF_FFFF_FFFF_FFFF)

# Result codes whose value depends on account balances (clamps, overflow
# ladder, limits). Used for the failed-linked-chain hazard route.
_BALANCE_CODES = (47, 48, 49, 50, 51, 52, 54, 55)

# Jacobi pass budget: pass k is exact for outcome-cascade depth < k, and a
# stable pass is THE answer, so this bounds only how deep accept/reject
# cascades may go before the batch routes to the sequential path.
_MAX_PASSES = 8

# Account balance fields carried through GatherCtx (limb pairs).
_BAL_FIELDS = (
    "debits_pending", "debits_posted", "credits_pending", "credits_posted",
)


class AccountView(NamedTuple):
    """The slice of an account row the kernel core needs."""

    found: jax.Array  # bool[N]
    slot: jax.Array  # uint64[N] — GLOBAL slot id (mesh: owner-offset)
    flags: jax.Array  # uint32[N]
    ledger: jax.Array  # uint32[N]
    bal: Dict[str, jax.Array]  # {field_lo/_hi: uint64[N]}


class GatherCtx(NamedTuple):
    """Every table-derived input of the pure kernel core.

    Single-chip: built by local probes (build_gather_ctx). Mesh: every
    shard probes its partition and psums the masked results, after which
    the ctx is replicated (parallel/sharded.py)."""

    ex_found: jax.Array
    e_tab: Dict[str, jax.Array]
    p_tab_found: jax.Array
    p_tab: Dict[str, jax.Array]
    drT: AccountView  # the event's own debit account
    crT: AccountView
    pdr: AccountView  # the TABLE pending's debit account
    pcr: AccountView
    postedT_found: jax.Array
    postedT_val: jax.Array
    probe_grow: jax.Array  # uint32 scalar: FLAG_GROW_*/FLAG_COLD bits
    accounts_capacity: jax.Array  # uint64 scalar: GLOBAL slot-space bound
    # uint8[N] under the cold tier's filter (else None): the lanes FLAG_COLD
    # is about, bit 0 the lane's id, bit 1 its pending_id.
    cold_lanes: jax.Array = None


class ApplyPlan(NamedTuple):
    """Everything the (single-chip or owner-local) apply phase needs."""

    codes: jax.Array  # uint32[N] final result codes
    route: jax.Array  # uint32 scalar: FLAG_SEQ bit (pure routing only)
    ok: jax.Array  # bool[N]
    row: Dict[str, jax.Array]  # composed transfer rows to insert
    post: jax.Array  # bool[N]
    posted_key: jax.Array  # uint64[N] pending timestamps (0 = none)
    pv_ok: jax.Array  # bool[N]
    # Balance scatter set (sorted leg domain, 2N):
    s_slot: jax.Array  # uint64[2N] global slots (capacity = sentinel)
    scat: jax.Array  # bool[2N] last live leg of each slot run
    bal_incl: Dict[str, jax.Array]  # {field_lo/_hi: uint64[2N]} final values
    # History (single-chip only; sharded mode excludes history accounts):
    do_hist: jax.Array  # bool[N]
    hist_row: Dict[str, jax.Array]
    # Jacobi iterations the fixpoint actually took (instrumentation).
    passes: jax.Array  # int32 scalar
    # Wave scheduler instrumentation (use_waves; zeros when off):
    # wave_bound: proved pass bound (depth_max + 1) when the conflict index
    # certified the batch, else 0.  wave_hist: per-lane wave-depth histogram
    # (buckets 0..7, 8 = deeper), valid lanes only.
    wave_bound: jax.Array  # int32 scalar
    wave_hist: jax.Array  # int32[9]


def _first_code(checks) -> jnp.ndarray:
    """Vector precedence ladder: the FIRST firing (mask, code) wins."""
    code = jnp.uint32(0)
    for cond, c in reversed(checks):
        val = c if isinstance(c, jnp.ndarray) else jnp.uint32(c)
        code = jnp.where(cond, val, code)
    return code


class IdIndex(NamedTuple):
    """Sorted view of the batch's transfer ids, shared by duplicate
    resolution and the pending-id join."""

    order: jax.Array  # int32[N]: lane at each sorted position
    s_lo: jax.Array
    s_hi: jax.Array
    gid: jax.Array  # int32[N]: group id at each sorted position
    group_of_lane: jax.Array  # int32[N]
    any_dup: jax.Array  # bool: some nonzero id occurs twice


@jax.named_scope("tb/full_gather")
def _build_id_index(id_lo, id_hi) -> IdIndex:
    n = id_lo.shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    order = jnp.lexsort((lane, id_lo, id_hi)).astype(jnp.int32)
    s_lo, s_hi = id_lo[order], id_hi[order]
    same = (s_lo[1:] == s_lo[:-1]) & (s_hi[1:] == s_hi[:-1])
    new_group = jnp.concatenate([jnp.ones((1,), jnp.bool_), ~same])
    gid = (jnp.cumsum(new_group.astype(jnp.int32)) - 1).astype(jnp.int32)
    group_of_lane = jnp.zeros((n,), jnp.int32).at[order].set(gid)
    any_dup = jnp.any(same & ((s_lo[1:] != 0) | (s_hi[1:] != 0)))
    return IdIndex(order, s_lo, s_hi, gid, group_of_lane, any_dup)


def _search128(s_hi, s_lo, q_hi, q_lo) -> jax.Array:
    """First sorted index with (s_hi,s_lo) >= (q_hi,q_lo) — batched binary
    search over 128-bit pairs (13 fixed steps for 8k lanes)."""
    n = s_hi.shape[0]
    lo = jnp.zeros(q_lo.shape, jnp.int32)
    hi = jnp.full(q_lo.shape, n, jnp.int32)
    for _ in range(int(n).bit_length()):
        mid = jnp.minimum((lo + hi) // 2, n - 1)
        m_hi, m_lo = s_hi[mid], s_lo[mid]
        less = (m_hi < q_hi) | ((m_hi == q_hi) & (m_lo < q_lo))
        active = lo < hi
        lo = jnp.where(active & less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
    return lo


def _group_winner(idx: IdIndex, ok: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(per-group, per-lane) first ok lane of each id group (n if none)."""
    n = ok.shape[0]
    inf = jnp.int32(n)
    s_ok = ok[idx.order]
    winner_g = jax.ops.segment_min(
        jnp.where(s_ok, idx.order, inf), idx.gid, num_segments=n
    )
    return winner_g, winner_g[idx.group_of_lane]


class _LegBalances(NamedTuple):
    """Per-leg exact account state around each event (sorted leg domain),
    plus the scatter set (final value of every touched slot)."""

    leg_pos: jax.Array  # int32[2N]: leg index -> sorted position
    # exclusive (pre-event) / inclusive (post-event) per field, U128 each:
    dp_pre: U128
    dp_incl: U128
    dpo_pre: U128
    dpo_incl: U128
    cp_pre: U128
    cp_incl: U128
    cpo_pre: U128
    cpo_incl: U128
    s_slot: jax.Array  # uint64[2N] sorted slot (capacity = sentinel)
    s_live: jax.Array  # bool[2N]
    is_last: jax.Array  # bool[2N]: last leg of its slot run
    arith_broken: jax.Array  # bool scalar: reconstruction over/underflowed


def _leg_balances(
    start_bal: Dict[str, jax.Array],
    cap_sentinel: jax.Array,
    ok_lanes: jax.Array,
    amt_lo: jax.Array,
    pamt_lo: jax.Array,
    dr_slot: jax.Array,
    cr_slot: jax.Array,
    dr_live: jax.Array,
    cr_live: jax.Array,
    pending_f: jax.Array,
    post: jax.Array,
    postvoid: jax.Array,
    has_postvoid: bool = True,
) -> _LegBalances:
    """Exact running balances of all four account fields at every leg.

    Legs 2i (debit side) / 2i+1 (credit side) sorted by (slot, leg position);
    leg position order is event order, so segmented prefix sums within slot
    runs reconstruct each account's exact field values before/after every
    event.  Deltas are gated by ``ok_lanes`` (the previous Jacobi iterate);
    ``amt_lo``/``pamt_lo`` are the previous iterate's effective / pending
    amounts (u64 — u128 amounts route to FLAG_SEQ).  ``start_bal`` carries
    each LEG's account start balances ({field_lo/_hi: uint64[2N]}, leg
    domain, pre-sort), composed from the GatherCtx account views — every
    leg of a slot run belongs to the same account, so each leg's own value
    is its run's start."""
    n = ok_lanes.shape[0]

    leg_slot_raw = jnp.stack([dr_slot, cr_slot], axis=1).reshape(-1)
    leg_live_raw = jnp.stack([dr_live, cr_live], axis=1).reshape(-1)
    leg_ok = jnp.repeat(ok_lanes, 2)
    leg_is_dr = (jnp.arange(2 * n, dtype=jnp.int32) & 1) == 0
    leg_slot = jnp.where(leg_live_raw, leg_slot_raw, cap_sentinel)

    amt2 = jnp.repeat(amt_lo, 2)
    pamt2 = jnp.repeat(pamt_lo, 2)
    pend2 = jnp.repeat(pending_f, 2)
    post2 = jnp.repeat(post, 2)
    pv2 = jnp.repeat(postvoid, 2)
    reg2 = ~pend2 & ~pv2

    on = leg_ok  # delta gate
    zero = jnp.uint64(0)
    dp_add = jnp.where(on & leg_is_dr & pend2, amt2, zero)
    dp_sub = jnp.where(on & leg_is_dr & pv2, pamt2, zero)
    dpo_add = jnp.where(on & leg_is_dr & (reg2 | post2), amt2, zero)
    cp_add = jnp.where(on & ~leg_is_dr & pend2, amt2, zero)
    cp_sub = jnp.where(on & ~leg_is_dr & pv2, pamt2, zero)
    cpo_add = jnp.where(on & ~leg_is_dr & (reg2 | post2), amt2, zero)

    # (slot, legpos) sort: n <= 2^14 so legpos < 2^15 fits under the slot.
    leg_pos_id = jnp.arange(2 * n, dtype=jnp.uint64)
    sort_key = (leg_slot << jnp.uint64(15)) | leg_pos_id
    leg_order = jnp.argsort(sort_key)
    s_slot = leg_slot[leg_order]
    s_live = s_slot < cap_sentinel
    s_head = jnp.concatenate([jnp.ones((1,), jnp.bool_), s_slot[1:] != s_slot[:-1]])
    is_last = jnp.concatenate([s_slot[1:] != s_slot[:-1], jnp.ones((1,), jnp.bool_)])
    leg_pos = jnp.zeros((2 * n,), jnp.int32).at[leg_order].set(
        jnp.arange(2 * n, dtype=jnp.int32)
    )

    # ONE stacked segmented prefix sum for all six delta streams, in pure
    # u32: TPU emulates u64 scans as u32-pair reduce-windows whose scoped
    # VMEM scratch blows the 16M budget inside the while_loop body (measured:
    # 64M at 8192 lanes). Instead each u64 delta is split into four 16-bit
    # parts — part sums over <= 2^15 legs stay < 2^31, so a single native
    # (2N, 24) u32 cumsum + one shared run-start cummax computes everything,
    # and the u64 limb sums are recombined per gathered leg afterwards.
    # Streams are permuted 1D BEFORE stacking (2D row gathers lower to
    # per-row DMAs on TPU); run bases come from a columnwise cummax —
    # exclusive sums at run heads are nondecreasing down the array, so
    # max-carry propagates each run's base with no gather.
    # The pv subtraction streams (void/post releasing a pending) exist only
    # when the batch can carry post/void lanes: a static has_postvoid=False
    # shrinks the stacked scan from 24 to 16 columns (1/3 less cumsum +
    # cummax work on the hot plain/limits shapes).
    streams = [u128.limbs16(dp_add[leg_order])]
    if has_postvoid:
        streams.append(u128.limbs16(dp_sub[leg_order]))
    streams.append(u128.limbs16(dpo_add[leg_order]))
    streams.append(u128.limbs16(cp_add[leg_order]))
    if has_postvoid:
        streams.append(u128.limbs16(cp_sub[leg_order]))
    streams.append(u128.limbs16(cpo_add[leg_order]))
    if has_postvoid:
        col_dp, col_dpo, col_cp, col_cpo = 0, 8, 12, 20
    else:
        col_dp, col_dpo, col_cp, col_cpo = 0, 4, 8, 12
    # Streams stack on AXIS 0 — (streams, 2N) with the scans along the
    # MINOR dimension.  The axis-1 layout made XLA flip layouts around
    # every cumsum/cummax: copyhound counted 52-74 MB-scale copies of
    # these very temporaries per compiled kernel (one set per Jacobi
    # pass), all gone in this orientation.
    v = jnp.stack(sum(streams, []), axis=0)
    c = jnp.cumsum(v, axis=1)
    base = jax.lax.cummax(jnp.where(s_head[None, :], c - v, 0), axis=1)
    incl_all = c - base
    excl_all = incl_all - v

    zeros2n = jnp.zeros((2 * n,), jnp.uint64)

    def recombine(limbs, col):
        """u64 limb sum from two adjacent 16-bit part-sum rows."""
        return limbs[col].astype(jnp.uint64) + (
            limbs[col + 1].astype(jnp.uint64) << jnp.uint64(16)
        )

    def field_vals(field, col, has_sub):
        start = U128(
            start_bal[field + "_lo"][leg_order],
            start_bal[field + "_hi"][leg_order],
        )

        def at(limbs):
            add = u128.from_limbs32(recombine(limbs, col), recombine(limbs, col + 2))
            sub = (
                u128.from_limbs32(recombine(limbs, col + 4), recombine(limbs, col + 6))
                if has_sub else U128(zeros2n, zeros2n)
            )
            added, ov = u128.add(start, add)
            val, neg = u128.sub(added, sub)
            return val, ov | neg

        pre, bad_e = at(excl_all)
        incl, bad_i = at(incl_all)
        return pre, incl, bad_e | bad_i

    dp_pre, dp_incl, bad1 = field_vals("debits_pending", col_dp, has_postvoid)
    dpo_pre, dpo_incl, bad2 = field_vals("debits_posted", col_dpo, False)
    cp_pre, cp_incl, bad3 = field_vals("credits_pending", col_cp, has_postvoid)
    cpo_pre, cpo_incl, bad4 = field_vals("credits_posted", col_cpo, False)
    arith_broken = jnp.any(s_live & (bad1 | bad2 | bad3 | bad4))

    return _LegBalances(
        leg_pos=leg_pos,
        dp_pre=dp_pre, dp_incl=dp_incl,
        dpo_pre=dpo_pre, dpo_incl=dpo_incl,
        cp_pre=cp_pre, cp_incl=cp_incl,
        cpo_pre=cpo_pre, cpo_incl=cpo_incl,
        s_slot=s_slot, s_live=s_live, is_last=is_last,
        arith_broken=arith_broken,
    )


@jax.named_scope("tb/full_waves")
def _wave_schedule(
    hazard: jax.Array,
    unschedulable: jax.Array,
    wdr_slot: jax.Array,
    wdr_live: jax.Array,
    wcr_slot: jax.Array,
    wcr_live: jax.Array,
    valid: jax.Array,
    cap_sentinel: jax.Array,
    max_rounds: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Vectorized conflict-index wave scheduler (docs/waves.md).

    Assigns every lane a WAVE DEPTH: 0 for lanes whose outcome is provably
    independent of every other lane's outcome (non-hazard: fixed amount, no
    clamp/limit/overflow/fulfillment/dup/chain sensitivity), and for hazard
    lanes 1 + the maximum depth of any EARLIER hazard lane sharing one of
    its accounts — the index-based schedule of 1911.11329, restricted to
    the lanes whose outcomes can actually change across Jacobi iterates.
    Outcome changes propagate only through shared account balances, and
    only hazard lanes ever change outcome, so pass d+1 of the Jacobi
    fixpoint is exact for every lane of depth <= d (induction over depth;
    non-hazard lanes are exact at pass 1).  max depth + 1 is therefore a
    PROVED pass bound: the loop may commit after that many passes without
    observing stability, skipping the verification pass entirely — wave-0
    batches (no conflicts) commit in one evaluation pass plus the single
    balance-update (aux) pass.

    Depth is the longest chain in a DAG, computed by at most ``max_rounds``
    cheap relaxation rounds over ONE (slot, leg-position) sort — each round
    is a segmented exclusive running-max, ~20x cheaper than a semantic
    Jacobi pass.  A batch whose depth has not stabilized within
    ``max_rounds`` rounds would need more passes than the Jacobi budget
    anyway, so it simply falls back to the stability exit (today's path).

    Returns (proved bool scalar, passes_needed int32 scalar, depth int32[N],
    hist int32[9]).
    """
    n = hazard.shape[0]
    leg_slot = jnp.stack([wdr_slot, wcr_slot], axis=1).reshape(-1)
    leg_live = jnp.stack([wdr_live, wcr_live], axis=1).reshape(-1)
    leg_slot = jnp.where(leg_live, leg_slot, cap_sentinel)
    # (slot, legpos) sort: leg position order IS event order within a slot
    # run (the _leg_balances invariant), so "earlier leg in my run" is
    # exactly "earlier conflicting lane".
    leg_pos_id = jnp.arange(2 * n, dtype=jnp.uint64)
    leg_order = jnp.argsort((leg_slot << jnp.uint64(15)) | leg_pos_id)
    s_slot = leg_slot[leg_order]
    s_head = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), s_slot[1:] != s_slot[:-1]]
    )
    s_lane = (leg_order >> 1).astype(jnp.int32)
    s_live = s_slot < cap_sentinel
    run_id = jnp.cumsum(s_head.astype(jnp.uint64)) - 1

    def relax_round(carry):
        depth, _, rounds = carry
        # Segmented EXCLUSIVE running max of hazard depths within slot
        # runs, via (run_id << 32 | depth) key packing: run_id is
        # nondecreasing down the sorted array, so a plain cummax never
        # leaks a value across runs (an earlier run's key always packs
        # smaller than the current run's zero).  Dead legs (sentinel
        # slot) share one tail run and are masked out of both sides.
        leg_depth = jnp.where(
            s_live, depth[s_lane], jnp.int32(0)
        ).astype(jnp.uint64)
        packed = (run_id << jnp.uint64(32)) | leg_depth
        incl = jax.lax.cummax(packed)
        excl = jnp.concatenate([jnp.zeros((1,), jnp.uint64), incl[:-1]])
        excl_val = jnp.where(
            s_live & ((excl >> jnp.uint64(32)) == run_id),
            (excl & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32),
            jnp.int32(0),
        )
        prior = jnp.zeros((n,), jnp.int32).at[s_lane].max(excl_val)
        new_depth = jnp.where(
            hazard, jnp.maximum(depth, jnp.int32(1) + prior), jnp.int32(0)
        )
        return new_depth, jnp.any(new_depth != depth), rounds + 1

    depth, changed, _ = jax.lax.while_loop(
        lambda c: c[1] & (c[2] < max_rounds),
        relax_round,
        (
            jnp.where(hazard, jnp.int32(1), jnp.int32(0)),
            jnp.bool_(True),
            jnp.int32(0),
        ),
    )
    proved = ~unschedulable & ~changed
    passes_needed = jnp.max(jnp.where(valid, depth, 0)) + jnp.int32(1)
    hist = jnp.zeros((9,), jnp.int32).at[
        jnp.where(valid, jnp.clip(depth, 0, 8), 9)
    ].add(1, mode="drop")
    return proved, passes_needed, depth, hist


def _at(val: U128, pos: jax.Array) -> U128:
    return U128(val.lo[pos], val.hi[pos])


def _account_view(table, look, found, rows=None) -> AccountView:
    rows = rows if rows is not None else ht.gather_cols(table, look.slot, found)
    return AccountView(
        found=found,
        slot=look.slot,
        flags=rows["flags"],
        ledger=rows["ledger"],
        bal={
            f + l: rows[f + l] for f in _BAL_FIELDS for l in ("_lo", "_hi")
        },
    )


@jax.named_scope("tb/full_gather")
def build_gather_ctx(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    valid: jax.Array,
    postvoid: jax.Array,
    bloom: jax.Array = None,
    cold_checked: jax.Array = None,
    has_postvoid: bool = True,
) -> GatherCtx:
    """Single-chip GatherCtx: local probes of the ledger tables.

    ``has_postvoid`` is a STATIC host hint: False means the host proved the
    batch carries no post/void flags, so the four pending-side probe loops
    and gathers (pending row, its two accounts, its fulfillment) compile
    away entirely — the flagship plain-batch shape pays only its own three
    probes."""
    n = batch["id_lo"].shape[0]
    tid = _u128_col(batch, "id")
    pend_id = _u128_col(batch, "pending_id")
    t_dr_id = _u128_col(batch, "debit_account_id")
    t_cr_id = _u128_col(batch, "credit_account_id")

    ex_look = ht.lookup(ledger.transfers, tid.lo, tid.hi, MAX_PROBE)
    ex_found = ex_look.found & valid
    e_tab = ht.gather_cols(ledger.transfers, ex_look.slot, ex_found)

    drT_look = ht.lookup(ledger.accounts, t_dr_id.lo, t_dr_id.hi, MAX_PROBE)
    crT_look = ht.lookup(ledger.accounts, t_cr_id.lo, t_cr_id.hi, MAX_PROBE)
    drT = _account_view(ledger.accounts, drT_look, drT_look.found & valid)
    crT = _account_view(ledger.accounts, crT_look, crT_look.found & valid)

    if has_postvoid:
        p_look = ht.lookup(ledger.transfers, pend_id.lo, pend_id.hi, MAX_PROBE)
        p_tab_found = p_look.found & postvoid
        p_tab = ht.gather_cols(ledger.transfers, p_look.slot, p_tab_found)

        # Accounts of a TABLE pending (post/void operates on the pending's
        # accounts, state_machine.zig:1420-1423).
        pdr_look = ht.lookup(
            ledger.accounts, p_tab["debit_account_id_lo"],
            p_tab["debit_account_id_hi"], MAX_PROBE,
        )
        pcr_look = ht.lookup(
            ledger.accounts, p_tab["credit_account_id_lo"],
            p_tab["credit_account_id_hi"], MAX_PROBE,
        )
        pdr = _account_view(
            ledger.accounts, pdr_look, pdr_look.found & p_tab_found
        )
        pcr = _account_view(
            ledger.accounts, pcr_look, pcr_look.found & p_tab_found
        )

        # Posted-groove fulfillment for a TABLE pending (key: its timestamp).
        postedT_look = ht.lookup(
            ledger.posted, p_tab["timestamp"],
            jnp.zeros_like(p_tab["timestamp"]), MAX_PROBE,
        )
        postedT_found = postedT_look.found & p_tab_found
        postedT_val = ht.gather_cols(
            ledger.posted, postedT_look.slot, postedT_found
        )["fulfillment"]
        pv_overflow = (
            jnp.where(
                pdr_look.overflow | pcr_look.overflow,
                jnp.uint32(FLAG_GROW_ACCOUNTS), jnp.uint32(0),
            )
            | jnp.where(p_look.overflow, jnp.uint32(FLAG_GROW_TRANSFERS),
                        jnp.uint32(0))
            | jnp.where(postedT_look.overflow, jnp.uint32(FLAG_GROW_POSTED),
                        jnp.uint32(0))
        )
        p_found_for_cold = p_look.found
    else:
        zero64 = jnp.zeros((n,), jnp.uint64)
        p_tab_found = jnp.zeros((n,), jnp.bool_)
        p_tab = {
            name: jnp.zeros((n,), dt) for name, dt in TRANSFER_COLS.items()
        }
        pdr = pcr = AccountView(
            found=p_tab_found, slot=zero64,
            flags=jnp.zeros((n,), jnp.uint32),
            ledger=jnp.zeros((n,), jnp.uint32),
            bal={f + l: zero64 for f in _BAL_FIELDS for l in ("_lo", "_hi")},
        )
        postedT_found = p_tab_found
        postedT_val = jnp.zeros((n,), jnp.uint32)
        pv_overflow = jnp.uint32(0)
        p_found_for_cold = p_tab_found

    probe_grow = (
        jnp.where(
            drT_look.overflow | crT_look.overflow,
            jnp.uint32(FLAG_GROW_ACCOUNTS), jnp.uint32(0),
        )
        | jnp.where(
            ex_look.overflow,
            jnp.uint32(FLAG_GROW_TRANSFERS), jnp.uint32(0),
        )
        | pv_overflow
    )

    # Cold-tier membership (ops/cold.py): an id or pending_id missing from
    # the HOT table but hitting the cold Bloom filter needs host resolution
    # (exact exists-precedence demands the cold row). cold_checked lanes were
    # already certified not-cold by the host, so false positives terminate.
    cold_lanes = None
    if bloom is not None:
        from .cold import bloom_check_impl

        checked = (
            cold_checked if cold_checked is not None
            else jnp.zeros((n,), jnp.bool_)
        )
        with jax.named_scope("tb/full_bloom"):
            cold_ids = (
                valid & ~ex_look.found & ~checked
                & bloom_check_impl(bloom, tid.lo, tid.hi)
            )
            # Which of a lane's two ids hit: bit 0 its id, bit 1 its
            # pending_id.  The host resolves exactly these (FLAG_COLD).
            cold_lanes = cold_ids.astype(jnp.uint8)
            if has_postvoid:
                # (No post or void lane, no pending id to look for: the
                # second walk of the filter compiles away.)
                cold_pend = (
                    postvoid & ~p_found_for_cold & ~checked
                    & bloom_check_impl(bloom, pend_id.lo, pend_id.hi)
                )
                cold_lanes = cold_lanes | (
                    cold_pend.astype(jnp.uint8) << jnp.uint8(1)
                )
            probe_grow = probe_grow | jnp.where(
                jnp.any(cold_lanes != 0), jnp.uint32(FLAG_COLD),
                jnp.uint32(0),
            )

    return GatherCtx(
        ex_found=ex_found, e_tab=e_tab,
        p_tab_found=p_tab_found, p_tab=p_tab,
        drT=drT, crT=crT, pdr=pdr, pcr=pcr,
        postedT_found=postedT_found, postedT_val=postedT_val,
        probe_grow=probe_grow,
        accounts_capacity=jnp.uint64(ledger.accounts.capacity),
        cold_lanes=cold_lanes,
    )


def _kernel_core(
    ctx: GatherCtx,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
    max_passes: int = _MAX_PASSES,
    has_postvoid: bool = True,
    use_waves: bool = False,
) -> ApplyPlan:
    """The pure batch semantics: no table access, replicable on a mesh.

    ``has_postvoid`` (STATIC host hint, mirroring build_gather_ctx's): False
    means the batch provably carries no post/void lanes, so the per-pass
    two-phase machinery — the in-batch pending join, the 20-column pending
    row composition, the pv result ladder, and the fulfillment-winner sort —
    compiles away, and _leg_balances drops its pv subtraction streams
    (24 -> 16 scan columns).  The flagship plain and --limits shapes pay
    only the regular ladder per pass."""
    n = batch["id_lo"].shape[0]
    assert n <= 1 << 14, "leg sort key packs (slot, legpos<2^15)"
    lane = jnp.arange(n, dtype=jnp.int32)
    valid = lane < count.astype(jnp.int32)
    ts = _timestamps(count, timestamp, n)

    tid = _u128_col(batch, "id")
    t_dr_id = _u128_col(batch, "debit_account_id")
    t_cr_id = _u128_col(batch, "credit_account_id")
    t_amt = _u128_col(batch, "amount")
    pend_id = _u128_col(batch, "pending_id")
    flags = batch["flags"]
    false_n = jnp.zeros((n,), jnp.bool_)
    if has_postvoid:
        post = ((flags & TF_POST) != 0) & valid
        void = ((flags & TF_VOID) != 0) & valid
        postvoid = post | void
    else:
        # Host-proved: no pv lanes.  Static False gates fold the pv paths.
        post = void = postvoid = false_n
    pending_f = ((flags & TF_PENDING) != 0) & valid
    linked = ((flags & TF_LINKED) != 0) & valid
    bal_dr = ((flags & TF_BALANCING_DEBIT) != 0) & valid
    bal_cr = ((flags & TF_BALANCING_CREDIT) != 0) & valid
    balancing = bal_dr | bal_cr

    ex_found, e_tab = ctx.ex_found, ctx.e_tab
    p_tab_found, p_tab = ctx.p_tab_found, ctx.p_tab
    drT, crT, pdr, pcr = ctx.drT, ctx.crT, ctx.pdr, ctx.pcr
    cap_sentinel = ctx.accounts_capacity

    idx = _build_id_index(tid.lo, tid.hi)

    if has_postvoid:
        # In-batch pending-create candidate group for each pv lane.
        with jax.named_scope("tb/full_gather"):
            pj = _search128(idx.s_hi, idx.s_lo, pend_id.hi, pend_id.lo)
            pj_c = jnp.minimum(pj, n - 1)
            pj_hit = (
                (idx.s_hi[pj_c] == pend_id.hi)
                & (idx.s_lo[pj_c] == pend_id.lo) & (pj < n)
            )
            pj_group = idx.gid[pj_c]

    timeout_ns = batch["timeout"].astype(jnp.uint64) * jnp.uint64(NS_PER_S)
    ov_timeout = (ts + timeout_ns) < ts
    dr_limf = ((drT.flags & AF_DEBITS_MUST_NOT_EXCEED_CREDITS) != 0) & drT.found
    cr_limf = ((crT.flags & AF_CREDITS_MUST_NOT_EXCEED_DEBITS) != 0) & crT.found

    if use_waves:
        # --- conflict-index wave schedule (TB_WAVES; docs/waves.md) -------
        # HAZARD lanes are the only ones whose (code, amount) can change
        # across Jacobi iterates: balancing clamps, balance-limit
        # accounts, and start balances within one batch's delta margin of
        # u128 overflow (the near_ov threshold the failed-chain hazard
        # route already uses).  Everything else has a fixed outcome from
        # pass 1, whatever its account conflicts — including a post/void
        # of a TABLE pending: its whole ladder compares fixed table/batch
        # values (the reference's post_or_void path has no balance
        # checks), so even the fulfillment winner race resolves from codes
        # that never change across iterates.  A post/void whose pending
        # may resolve IN BATCH is the exception (it reads another lane's
        # composed row) and is excluded batch-wide below.
        #
        # The margin is stricter than near_ov's: any start field >=
        # 2^127 - 2^80 is hazard, so for non-hazard lanes every overflow
        # operand (single fields AND the dp+dpo / cp+cpo pair sums, whose
        # u128 wrap boundary the ladder is sensitive to) sits further from
        # 2^128 than one batch's total delta (< n * 2^64 <= 2^77) can
        # move it — no overflow code can change across iterates.
        near_w = jnp.uint64(0x7FFF_FFFF_FFFF_0000)

        def _near_start(v: AccountView):
            return v.found & (
                (v.bal["debits_pending_hi"] >= near_w)
                | (v.bal["debits_posted_hi"] >= near_w)
                | (v.bal["credits_pending_hi"] >= near_w)
                | (v.bal["credits_posted_hi"] >= near_w)
            )

        hazard = valid & (
            balancing | dr_limf | cr_limf
            | _near_start(drT) | _near_start(crT)
        )
        # Unschedulable couplings fall back to the stability exit (today's
        # behavior, bit-for-bit): linked chains propagate failure BACKWARD
        # (a cycle in the dependency DAG), duplicate ids couple through
        # winner selection rather than accounts, and the in-batch pending
        # reference above.
        unschedulable = jnp.any(linked) | idx.any_dup
        if has_postvoid:
            hazard = hazard | (
                postvoid & (_near_start(pdr) | _near_start(pcr))
            )
            unschedulable = unschedulable | jnp.any(postvoid & pj_hit)
            wdr_slot = jnp.where(postvoid, pdr.slot, drT.slot)
            wdr_live = jnp.where(postvoid, pdr.found, drT.found & valid)
            wcr_slot = jnp.where(postvoid, pcr.slot, crT.slot)
            wcr_live = jnp.where(postvoid, pcr.found, crT.found & valid)
        else:
            wdr_slot, wdr_live = drT.slot, drT.found & valid
            wcr_slot, wcr_live = crT.slot, crT.found & valid
        sched_proved, passes_needed, _wave_depth, wave_hist = _wave_schedule(
            hazard, unschedulable, wdr_slot, wdr_live, wcr_slot, wcr_live,
            valid, cap_sentinel, max_passes,
        )
        wave_bound = jnp.where(sched_proved, passes_needed, jnp.int32(0))
    else:
        sched_proved = jnp.bool_(False)
        passes_needed = jnp.int32(_MAX_PASSES + 1)
        wave_bound = jnp.int32(0)
        wave_hist = jnp.zeros((9,), jnp.int32)

    # ------------------------------------------------------------------
    # One Jacobi pass of the sequential semantics.
    # ------------------------------------------------------------------

    @jax.named_scope("tb/full_pass")
    def one_pass(ok_prev: jax.Array, amt_prev: U128):
        inf = jnp.int32(n)
        winner_g, winner_of_lane = _group_winner(idx, ok_prev)

        if has_postvoid:
            # --- resolve each pv lane's pending row ----------------------
            pw = winner_g[pj_group]
            pwc = jnp.minimum(
                jnp.where(pj_hit, pw, inf), n - 1
            ).astype(jnp.int32)
            # Any inserted transfer resolves the reference (a non-pending
            # one then fails the p_is_pending check with code 26, like the
            # table path — state_machine.zig:1417).
            in_batch_ref = (
                postvoid & pj_hit & (pw < inf) & (pw < lane) & ok_prev[pwc]
            )

            p_found = p_tab_found | in_batch_ref
            p = {}
            for name in TRANSFER_COLS:
                if name == "timestamp":
                    p[name] = jnp.where(in_batch_ref, ts[pwc], p_tab[name])
                elif name == "amount_lo":
                    # The stored amount of an in-batch pending is its
                    # CLAMPED amount (balancing pending): the previous
                    # iterate's effective amount — exact at the fixpoint.
                    p[name] = jnp.where(
                        in_batch_ref, amt_prev.lo[pwc], p_tab[name]
                    )
                elif name == "amount_hi":
                    p[name] = jnp.where(
                        in_batch_ref, amt_prev.hi[pwc], p_tab[name]
                    )
                else:
                    p[name] = jnp.where(
                        in_batch_ref, batch[name][pwc], p_tab[name]
                    )
            p_is_pending = ((p["flags"] & TF_PENDING) != 0) & p_found
            p_amt = U128(p["amount_lo"], p["amount_hi"])
            p_dr_id = U128(
                p["debit_account_id_lo"], p["debit_account_id_hi"]
            )
            p_cr_id = U128(
                p["credit_account_id_lo"], p["credit_account_id_hi"]
            )

            # Effective accounts (regular: own; pv: the pending's),
            # composed from the gathered views — no table access.
            def compose(own: AccountView, pend_side: AccountView):
                def pick(o, pv_):
                    return jnp.where(
                        in_batch_ref, o[pwc], jnp.where(postvoid, pv_, o)
                    )

                return (
                    pick(own.slot, pend_side.slot),
                    pick(own.found, pend_side.found) & valid,
                    pick(own.flags, pend_side.flags),
                    {k: pick(own.bal[k], pend_side.bal[k]) for k in own.bal},
                )

            dr_slot, dr_live, acc_flags_dr, dr_bal = compose(drT, pdr)
            cr_slot, cr_live, acc_flags_cr, cr_bal = compose(crT, pcr)
        else:
            in_batch_ref = false_n
            p_found = p_tab_found
            p = p_tab
            p_amt = U128(p["amount_lo"], p["amount_hi"])
            dr_slot, dr_live = drT.slot, drT.found & valid
            cr_slot, cr_live = crT.slot, crT.found & valid
            acc_flags_dr, acc_flags_cr = drT.flags, crT.flags
            dr_bal, cr_bal = drT.bal, crT.bal

        # --- exact running balances from the previous iterate -------------
        start_bal = {
            k: jnp.stack([dr_bal[k], cr_bal[k]], axis=1).reshape(-1)
            for k in dr_bal
        }
        legs = _leg_balances(
            start_bal, cap_sentinel, ok_prev, amt_prev.lo, p_amt.lo,
            dr_slot, cr_slot, dr_live, cr_live, pending_f, post, postvoid,
            has_postvoid=has_postvoid,
        )
        dpos = legs.leg_pos[2 * lane]
        cpos = legs.leg_pos[2 * lane + 1]
        a_dp = _at(legs.dp_pre, dpos)      # dr account, pre-event
        a_dpo = _at(legs.dpo_pre, dpos)
        a_cpo = _at(legs.cpo_pre, dpos)
        b_cp = _at(legs.cp_pre, cpos)      # cr account, pre-event
        b_cpo = _at(legs.cpo_pre, cpos)
        b_dpo = _at(legs.dpo_pre, cpos)

        # --- balancing clamps (state_machine.zig:1286-1306) ----------------
        zero = jnp.uint64(0)
        amount0 = u128.select(
            balancing & u128.is_zero(t_amt), U128(_U64MAX, zero), t_amt
        )
        dr_balance = u128.add_wrap(a_dpo, a_dp)
        avail_dr = u128.sub_saturate(a_cpo, dr_balance)
        amount1 = u128.select(bal_dr, u128.min_(amount0, avail_dr), amount0)
        exceeds_credits_bal = bal_dr & u128.is_zero(amount1)
        cr_balance = u128.add_wrap(b_cpo, b_cp)
        avail_cr = u128.sub_saturate(b_dpo, cr_balance)
        amount2 = u128.select(bal_cr, u128.min_(amount1, avail_cr), amount1)
        exceeds_debits_bal = bal_cr & ~exceeds_credits_bal & u128.is_zero(amount2)
        reg_amount = amount2

        # --- overflow ladder (:1308-1322) ----------------------------------
        _, ov_dp = u128.add(reg_amount, a_dp)
        _, ov_cp = u128.add(reg_amount, b_cp)
        _, ov_dpo = u128.add(reg_amount, a_dpo)
        _, ov_cpo = u128.add(reg_amount, b_cpo)
        dr_total, _ = u128.add(a_dp, a_dpo)
        _, ov_d = u128.add(reg_amount, dr_total)
        cr_total, _ = u128.add(b_cp, b_cpo)
        _, ov_c = u128.add(reg_amount, cr_total)

        # --- balance limits (tigerbeetle.zig:31-39) ------------------------
        new_dr_tot, _ = u128.add(dr_total, reg_amount)
        exceeds_credits_lim = dr_limf & u128.gt(new_dr_tot, a_cpo)
        new_cr_tot, _ = u128.add(cr_total, reg_amount)
        exceeds_debits_lim = cr_limf & u128.gt(new_cr_tot, b_dpo)

        # --- effective amount + composed insert rows -----------------------
        # (state_machine.zig:1326-1328, 1431, 1455-1469)
        row = {name: batch[name] for name in TRANSFER_COLS}
        row["timestamp"] = ts
        if has_postvoid:
            pv_amount = u128.select(u128.is_zero(t_amt), p_amt, t_amt)
            amount = u128.select(postvoid, pv_amount, reg_amount)
            for name in ("debit_account_id", "credit_account_id"):
                for l_ in ("_lo", "_hi"):
                    row[name + l_] = jnp.where(
                        postvoid, p[name + l_], batch[name + l_]
                    )
            ud128_nz = (
                (batch["user_data_128_lo"] != 0)
                | (batch["user_data_128_hi"] != 0)
            )
            for l_ in ("_lo", "_hi"):
                row["user_data_128" + l_] = jnp.where(
                    postvoid & ~ud128_nz, p["user_data_128" + l_],
                    batch["user_data_128" + l_],
                )
            for name in ("user_data_64", "user_data_32"):
                row[name] = jnp.where(
                    postvoid & (batch[name] == 0), p[name], batch[name]
                )
            row["ledger"] = jnp.where(postvoid, p["ledger"], batch["ledger"])
            row["code"] = jnp.where(postvoid, p["code"], batch["code"])
            row["timeout"] = jnp.where(
                postvoid, jnp.uint32(0), batch["timeout"]
            )
        else:
            amount = reg_amount
        row["amount_lo"] = amount.lo
        row["amount_hi"] = amount.hi

        # --- regular-path ladder (state_machine.zig:1239-1368) -------------
        # The exists check compares the RAW event amount against the stored
        # (possibly clamped) amount (:1379).
        exists_tab_reg = _exists_regular(batch, e_tab, t_amt, n)
        reg_code = _first_code([
            (((flags & TF_PADDING) != 0), 4),
            (u128.is_zero(tid), 5),
            (u128.is_max(tid), 6),
            (u128.is_zero(t_dr_id), 8),
            (u128.is_max(t_dr_id), 9),
            (u128.is_zero(t_cr_id), 10),
            (u128.is_max(t_cr_id), 11),
            (u128.eq(t_dr_id, t_cr_id), 12),
            (~u128.is_zero(pend_id), 13),
            (~pending_f & (batch["timeout"] != 0), 17),
            (~balancing & u128.is_zero(t_amt), 18),
            ((batch["ledger"] == 0), 19),
            ((batch["code"] == 0), 20),
            (~drT.found, 21),
            (~crT.found, 22),
            ((drT.ledger != crT.ledger), 23),
            ((batch["ledger"] != drT.ledger), 24),
            (ex_found, exists_tab_reg),
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

        if has_postvoid:
            # --- post/void ladder (state_machine.zig:1391-1453) ------------
            exists_tab_pv = _exists_postvoid(batch, e_tab, p, n)
            expiry_ns = p["timeout"].astype(jnp.uint64) * jnp.uint64(NS_PER_S)
            expired = (p["timeout"] != 0) & (
                ts >= p["timestamp"] + expiry_ns
            )
            pv_code = _first_code([
                (((flags & TF_PADDING) != 0), 4),
                (u128.is_zero(tid), 5),
                (u128.is_max(tid), 6),
                (post & void, 7),
                (pending_f, 7),
                (balancing, 7),
                (u128.is_zero(pend_id), 14),
                (u128.is_max(pend_id), 15),
                (u128.eq(pend_id, tid), 16),
                ((batch["timeout"] != 0), 17),
                (~p_found, 25),
                (~p_is_pending, 26),
                (~u128.is_zero(t_dr_id) & ~u128.eq(t_dr_id, p_dr_id), 27),
                (~u128.is_zero(t_cr_id) & ~u128.eq(t_cr_id, p_cr_id), 28),
                (((batch["ledger"] != 0) & (batch["ledger"] != p["ledger"])),
                 29),
                (((batch["code"] != 0) & (batch["code"] != p["code"])), 30),
                (u128.gt(amount, p_amt), 31),
                (void & u128.lt(amount, p_amt), 32),
                (ex_found, exists_tab_pv),
                (ctx.postedT_found & (ctx.postedT_val == 1), 33),
                (ctx.postedT_found & (ctx.postedT_val == 2), 34),
                (expired, 35),
            ])
            code = jnp.where(postvoid, pv_code, reg_code)
        else:
            code = reg_code
        code = jnp.where(batch["timestamp"] != 0, jnp.uint32(3), code)

        # --- intra-batch duplicate ids ------------------------------------
        # In sequential order the exists check sits BEFORE the balance-
        # dependent tail (clamps/overflows/limits, pv fulfillment/expiry),
        # so the in-batch override replaces exactly those post-exists codes.
        after_winner = (winner_of_lane < inf) & (lane > winner_of_lane)
        wc = jnp.minimum(winner_of_lane, n - 1).astype(jnp.int32)
        w_row = {k: v[wc] for k, v in row.items()}
        intra_reg = _exists_regular(batch, w_row, t_amt, n)
        balance_code = jnp.zeros((n,), jnp.bool_)
        for bc in _BALANCE_CODES:
            balance_code = balance_code | (code == bc)
        if has_postvoid:
            intra_pv = _exists_postvoid(batch, w_row, p, n)
            intra = jnp.where(postvoid, intra_pv, intra_reg)
            dup_overridable = jnp.where(
                postvoid,
                (code == 0) | (code == 33) | (code == 34) | (code == 35),
                (code == 0) | (code == 53) | balance_code,
            )
        else:
            intra = intra_reg
            dup_overridable = (code == 0) | (code == 53) | balance_code
        code = jnp.where(after_winner & dup_overridable, intra, code)

        if has_postvoid:
            # --- intra-batch double post/void -----------------------------
            # Group pv lanes by resolved pending timestamp; the first lane
            # whose pre-fulfillment checks pass records the fulfillment;
            # later ones get already_posted/voided. (Linked chains cannot
            # interact: batches with linked AND post/void route to the
            # sequential path.)
            p_ts_key = jnp.where(postvoid & p_found, p["timestamp"], 0)
            f_order = jnp.lexsort((lane, p_ts_key)).astype(jnp.int32)
            f_ts = p_ts_key[f_order]
            f_head = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), f_ts[1:] != f_ts[:-1]]
            )
            f_gid = (jnp.cumsum(f_head.astype(jnp.int32)) - 1).astype(
                jnp.int32
            )
            f_ok = (code[f_order] == 0) & (f_ts != 0)
            f_winner_g = jax.ops.segment_min(
                jnp.where(f_ok, f_order, inf), f_gid, num_segments=n
            )
            f_winner = jnp.zeros((n,), jnp.int32).at[f_order].set(
                f_winner_g[f_gid]
            )
            fulfil_after = (
                (f_winner < inf) & (lane > f_winner) & (p_ts_key != 0)
            )
            fwc = jnp.minimum(f_winner, n - 1).astype(jnp.int32)
            fulfil_code = jnp.where(
                post[fwc], jnp.uint32(33), jnp.uint32(34)
            )
            code = jnp.where(
                fulfil_after & ((code == 0) | (code == 35)), fulfil_code,
                code
            )

        # --- linked chains -------------------------------------------------
        code = jnp.where(~valid, 0, code)
        pre_chain_code = code
        code = _chain_codes(linked, code, count)
        ok = (code == 0) & valid

        # Overflow checks of a lane inside a FAILED chain may depend on the
        # chain's transient sibling effects (< n * 2^64 total): if any such
        # lane's balances sit within that margin of 2^128, the sequential
        # path could fire an overflow code the rolled-back fixpoint cannot
        # see. Flag "near overflow" = any involved hi limb in the top 2^15
        # values (margin 2^79 >= n * 2^64 for n <= 2^14).
        near = jnp.uint64(0xFFFF_FFFF_FFFF_0000)
        near_ov = (
            (a_dp.hi >= near) | (a_dpo.hi >= near)
            | (b_cp.hi >= near) | (b_cpo.hi >= near)
        )
        aux = dict(
            in_batch_ref=in_batch_ref, p=p, p_found=p_found, p_amt=p_amt,
            dr_slot=dr_slot, cr_slot=cr_slot, row=row,
            acc_flags_dr=acc_flags_dr, acc_flags_cr=acc_flags_cr,
            legs=legs, pre_chain_code=pre_chain_code, near_ov=near_ov,
        )
        return ok, code, amount, aux

    # Jacobi iteration: a pass whose codes and accepted amounts equal the
    # previous pass's is a fixpoint => THE sequential answer (induction
    # over lanes).  The loop ends after a stable pass, or once the wave
    # schedule's certified count has run (the iterate then IS the fixpoint,
    # docs/waves.md: no verification pass), or when max_passes are spent.
    # With use_waves off, sched_proved is a False constant and the exit
    # folds to stability alone.  The loop is a STATIC trip on every backend:
    # lax.scan(length=max_passes) whose body is a lax.cond on the exit
    # condition, so a pass after the exit is skipped on the device, not
    # evaluated, and `passes` counts the passes run.
    #
    # There is no second form because the chip measured none better (one
    # v5e, PERF.md section 6, PR 29: 7,780 posts/voids of table pendings,
    # proved bound 1, the served table sizes at 2.0-2.5 M rows, device ms an
    # execution): this scan 84.9, a plain lax.while_loop 86.0, and 120.8 for
    # an ungated scan(4) with 4 more passes behind one gate, which evaluates
    # 4 passes + the aux pass where the batch needs 1 + 1.
    #
    # The carry holds ONLY the iterate (k, stable, ok, code, amount), ~170
    # KB to cond over — aux (legs, composed rows, pending views: ~6 MB at
    # 8k lanes) stays OUT of the loop state and is recomputed ONCE from the
    # final iterate afterwards.  At a fixpoint a pass reproduces its input
    # bit-for-bit, so every downstream consumer sees exactly the converged
    # pass's values; unconverged batches route FLAG_SEQ and apply nothing,
    # so their aux values are never observable.
    ok0 = jnp.zeros((n,), jnp.bool_)
    code_sentinel = jnp.full((n,), 0xFFFFFFFF, jnp.uint32)
    carry0 = (jnp.int32(0), jnp.bool_(False), ok0, code_sentinel, t_amt)

    def step_pass(carry):
        k, _stable, ok_p, code_p, amt_p = carry
        ok_n, code_n, amt_n, _aux = one_pass(ok_p, amt_p)
        # The pass consumed (ok_p, amt_p); equality of codes and of accepted
        # amounts makes the next pass a no-op. Amounts of rejected lanes are
        # irrelevant downstream.
        stable = ~(
            jnp.any(code_n != code_p)
            | jnp.any(ok_n & ((amt_n.lo != amt_p.lo) | (amt_n.hi != amt_p.hi)))
        )
        # k counts the passes run (waves.jacobi_passes).
        return (k + 1, stable, ok_n, code_n, amt_n)

    def done(c):
        return c[1] | (sched_proved & (c[0] >= passes_needed))

    def gated(c, _):
        return jax.lax.cond(done(c), lambda c_: c_, step_pass, c), None

    c, _ = jax.lax.scan(gated, carry0, None, length=max_passes)
    k_passes, converged, ok_f, code_f, amt_f = c
    proved_done = sched_proved & (k_passes >= passes_needed)
    unconverged = ~converged & ~proved_done

    # The single aux-bearing pass from the fixpoint (see the carry note).
    ok, codes, amount, aux = one_pass(ok_f, amt_f)

    row = aux["row"]
    in_batch_ref = aux["in_batch_ref"]
    legs = aux["legs"]

    # ---------------- history (state_machine.zig:1342-1364) ----------------
    dr_hist = ((aux["acc_flags_dr"] & AF_HISTORY) != 0) & ok
    cr_hist = ((aux["acc_flags_cr"] & AF_HISTORY) != 0) & ok
    do_hist = (dr_hist | cr_hist) & ~postvoid

    # ---------------- routing flags ---------------------------------------
    any_u128_amount = jnp.any(
        valid & ((batch["amount_hi"] != 0) | (postvoid & (aux["p"]["amount_hi"] != 0)))
    )
    any_linked = jnp.any(linked)
    linked_x_intra = any_linked & (
        idx.any_dup | jnp.any(in_batch_ref) | jnp.any(postvoid)
    )
    # A FAILED linked chain rolls back members whose transient effects the
    # sequential path's balance checks DID see; if any member of a failed
    # chain carries a balance-dependent code (or the chain contains
    # balancing/limit-sensitive members), the fixpoint's codes may differ
    # from the sequential ones — route for exactness. Successful chains are
    # exact (all members' contributions present at the fixpoint). Chain
    # membership includes the terminator (linked flag false, previous lane
    # linked) — mirroring _chain_codes.
    prev_linked = jnp.concatenate([jnp.zeros((1,), jnp.bool_), linked[:-1]])
    in_chain = linked | prev_linked
    chain_failed = in_chain & (codes != 0)
    failed_member_balance = jnp.zeros((n,), jnp.bool_)
    for bc in _BALANCE_CODES:
        failed_member_balance = failed_member_balance | (
            chain_failed & (aux["pre_chain_code"] == bc)
        )
    chain_hazard = jnp.any(
        chain_failed & (balancing | dr_limf | cr_limf | aux["near_ov"])
    ) | jnp.any(failed_member_balance)

    route = jnp.where(
        unconverged | any_u128_amount | linked_x_intra | chain_hazard
        | legs.arith_broken,
        jnp.uint32(FLAG_SEQ), jnp.uint32(0),
    )

    # ---------------- history rows (values; apply decides placement) -------
    # Each recorded account's post-event snapshot of ALL FOUR fields is the
    # inclusive value at that event's leg (leg order = event order within the
    # slot run, and cross-side legs of the same account share the run).
    dpos = legs.leg_pos[2 * lane]
    cpos = legs.leg_pos[2 * lane + 1]

    def hv(val: U128, pos, mask):
        return (
            jnp.where(mask, val.lo[pos], 0),
            jnp.where(mask, val.hi[pos], 0),
        )

    dr_dp_lo, dr_dp_hi = hv(legs.dp_incl, dpos, dr_hist)
    dr_dpo_lo, dr_dpo_hi = hv(legs.dpo_incl, dpos, dr_hist)
    dr_cp_lo, dr_cp_hi = hv(legs.cp_incl, dpos, dr_hist)
    dr_cpo_lo, dr_cpo_hi = hv(legs.cpo_incl, dpos, dr_hist)
    cr_cp_lo, cr_cp_hi = hv(legs.cp_incl, cpos, cr_hist)
    cr_cpo_lo, cr_cpo_hi = hv(legs.cpo_incl, cpos, cr_hist)
    cr_dp_lo, cr_dp_hi = hv(legs.dp_incl, cpos, cr_hist)
    cr_dpo_lo, cr_dpo_hi = hv(legs.dpo_incl, cpos, cr_hist)
    hist_row = {
        "timestamp": ts,
        "dr_id_lo": jnp.where(dr_hist, row["debit_account_id_lo"], 0),
        "dr_id_hi": jnp.where(dr_hist, row["debit_account_id_hi"], 0),
        "dr_dp_lo": dr_dp_lo, "dr_dp_hi": dr_dp_hi,
        "dr_dpo_lo": dr_dpo_lo, "dr_dpo_hi": dr_dpo_hi,
        "dr_cp_lo": dr_cp_lo, "dr_cp_hi": dr_cp_hi,
        "dr_cpo_lo": dr_cpo_lo, "dr_cpo_hi": dr_cpo_hi,
        "cr_id_lo": jnp.where(cr_hist, row["credit_account_id_lo"], 0),
        "cr_id_hi": jnp.where(cr_hist, row["credit_account_id_hi"], 0),
        "cr_cp_lo": cr_cp_lo, "cr_cp_hi": cr_cp_hi,
        "cr_cpo_lo": cr_cpo_lo, "cr_cpo_hi": cr_cpo_hi,
        "cr_dp_lo": cr_dp_lo, "cr_dp_hi": cr_dp_hi,
        "cr_dpo_lo": cr_dpo_lo, "cr_dpo_hi": cr_dpo_hi,
    }

    pv_ok = ok & postvoid
    posted_key = jnp.where(pv_ok, aux["p"]["timestamp"], 0)
    bal_incl = {
        "debits_pending_lo": legs.dp_incl.lo, "debits_pending_hi": legs.dp_incl.hi,
        "debits_posted_lo": legs.dpo_incl.lo, "debits_posted_hi": legs.dpo_incl.hi,
        "credits_pending_lo": legs.cp_incl.lo, "credits_pending_hi": legs.cp_incl.hi,
        "credits_posted_lo": legs.cpo_incl.lo, "credits_posted_hi": legs.cpo_incl.hi,
    }
    return ApplyPlan(
        codes=codes, route=route, ok=ok, row=row, post=post,
        posted_key=posted_key, pv_ok=pv_ok,
        s_slot=legs.s_slot, scat=legs.is_last & legs.s_live,
        bal_incl=bal_incl, do_hist=do_hist, hist_row=hist_row,
        passes=k_passes,
        wave_bound=wave_bound, wave_hist=wave_hist,
    )


def create_transfers_full_impl(
    ledger: Ledger,
    batch: Dict[str, jax.Array],
    count: jax.Array,
    timestamp: jax.Array,
    bloom: jax.Array = None,
    cold_checked: jax.Array = None,
    max_passes: int = _MAX_PASSES,
    has_postvoid: bool = True,
    has_history: bool = True,
    use_waves: bool = False,
) -> Tuple[jax.Array, ...]:
    """Returns (ledger', codes uint32[N], flags uint32 scalar), a fourth
    wave-profile vector when ``use_waves`` (see below), and LAST what the
    secondary index (ops/index.py) takes, as the fast programs give it: the
    batch's two id columns, the INDEX_KEY_COLS of the rows it wrote (a post
    or void lane: the PENDING transfer's accounts) and the lanes it wrote
    (``sm.written_lanes``), so the host slices no staged operand and
    uploads no mask.  Under the cold tier (``bloom`` given) the lanes
    FLAG_COLD is about come just before those four (uint8[N]: bit 0 the
    lane's id hit the filter, bit 1 its pending_id), read with the flags.

    flags == 0: the batch was applied and ``codes`` are the final results.
    flags != 0: NOTHING was applied (ledger' == ledger value-wise); the host
    must grow the flagged tables, resolve cold ids (FLAG_COLD: ``bloom`` is
    the cold-id filter, ``cold_checked`` marks lanes the host already
    certified), and/or re-route to the sequential path.

    ``use_waves`` (STATIC; TB_WAVES at the machine level) arms the
    conflict-index wave scheduler: bit-identical codes/ledger, fewer
    Jacobi passes on batches the conflict index certifies, and a FOURTH
    return — int32[11] = (passes, wave_bound, hist[9 wave-depth buckets])
    — for the metrics surface.  Off compiles exactly the pre-waves
    program, without that vector.
    """
    n = batch["id_lo"].shape[0]
    lane = jnp.arange(n, dtype=jnp.int32)
    valid = lane < count.astype(jnp.int32)
    flags = batch["flags"]
    postvoid = (((flags & TF_POST) != 0) | ((flags & TF_VOID) != 0)) & valid
    tid = _u128_col(batch, "id")

    ctx = build_gather_ctx(
        ledger, batch, valid, postvoid, bloom, cold_checked,
        has_postvoid=has_postvoid,
    )
    plan = _kernel_core(ctx, batch, count, timestamp, max_passes,
                        has_postvoid=has_postvoid, use_waves=use_waves)

    # Insert slots are claimed (no writes) BEFORE the flags are finalized so
    # an insert-probe overflow also routes the batch with nothing applied.
    with jax.named_scope("tb/full_apply"):
        t_claim, t_ovf = ht.claim_slots(
            ledger.transfers, tid.lo, tid.hi, plan.ok, MAX_PROBE
        )
    if has_postvoid:
        with jax.named_scope("tb/full_posted"):
            p_claim, p_ovf = ht.claim_slots(
                ledger.posted, plan.posted_key, jnp.zeros((n,), jnp.uint64),
                plan.pv_ok, MAX_PROBE,
            )
    else:
        # Host proved no post/void lanes: plan.pv_ok is all-False, so the
        # probe loop and the fulfillment write below compile away.
        p_claim = jnp.zeros((n,), jnp.uint64)
        p_ovf = jnp.bool_(False)
    kflags = (
        ctx.probe_grow
        | plan.route
        | jnp.where(t_ovf, jnp.uint32(FLAG_GROW_TRANSFERS), jnp.uint32(0))
        | jnp.where(p_ovf, jnp.uint32(FLAG_GROW_POSTED), jnp.uint32(0))
    )
    commit = kflags == jnp.uint32(0)

    # ---------------- apply: balances (one scatter over slot runs) ---------
    # The final pass's inclusive values were computed from the second-to-
    # last iterate, which equals the final (ok, amount) whenever the batch
    # commits (stability), so the last leg of each slot run carries the
    # slot's exact final field values.
    with jax.named_scope("tb/full_apply"):
        scat = plan.scat & commit
        cap_sentinel = jnp.uint64(ledger.accounts.capacity)
        accounts = ht.scatter_cols(
            ledger.accounts, jnp.where(scat, plan.s_slot, cap_sentinel), scat,
            plan.bal_incl,
        )

        # ------------- apply: transfer + posted inserts -------------------
        ins_rows = {
            name: plan.row[name].astype(dt)
            for name, dt in TRANSFER_COLS.items()
        }
        transfers = ht.write_rows(
            ledger.transfers, tid.lo, tid.hi, t_claim, plan.ok & commit,
            ins_rows,
        )
    if has_postvoid:
        with jax.named_scope("tb/full_posted"):
            posted = ht.write_rows(
                ledger.posted,
                plan.posted_key,
                jnp.zeros((n,), jnp.uint64),
                p_claim,
                plan.pv_ok & commit,
                {"fulfillment": jnp.where(
                    plan.post, jnp.uint32(1), jnp.uint32(2))},
            )
    else:
        posted = ledger.posted

    # ---------------- apply: history rows ---------------------------------
    if has_history:
        with jax.named_scope("tb/full_apply"):
            do_hist_c = plan.do_hist & commit
            h = ledger.history
            h_off = (
                jnp.cumsum(do_hist_c.astype(jnp.uint64))
                - do_hist_c.astype(jnp.uint64)
            )
            h_idx = jnp.where(do_hist_c, h.count + h_off, jnp.uint64(h.capacity))
            history = h.replace(
                cols={
                    name: h.cols[name].at[h_idx].set(
                        plan.hist_row[name], mode="drop"
                    )
                    for name in h.cols
                },
                count=h.count + jnp.sum(do_hist_c.astype(jnp.uint64)),
            )
    else:
        # Host proved no account carries the HISTORY flag: the 21-column
        # append scatter compiles away.
        history = ledger.history

    out = Ledger(
        accounts=accounts, transfers=transfers, posted=posted, history=history
    )
    # What the host's index append takes, in the fast programs' order: the
    # id columns, the key columns of the rows written, the lanes written.
    index_feed = (
        batch["id_lo"], batch["id_hi"],
        {name: ins_rows[name] for name in INDEX_KEY_COLS},
        written_lanes(plan.codes, count),
    )
    head = (out, plan.codes, kflags)
    if use_waves:
        head += (jnp.concatenate([
            plan.passes.reshape(1), plan.wave_bound.reshape(1),
            plan.wave_hist,
        ]),)
    if bloom is not None:
        head += (ctx.cold_lanes,)
    return head + index_feed


def _exists_regular(t, e, t_amount: U128, n) -> jax.Array:
    """create_transfer_exists (state_machine.zig:1370-1389): ``t`` the raw
    event, ``e`` the stored/winner row, ``t_amount`` the RAW event amount
    (the stored side may be clamped; the reference compares t.amount)."""

    def ne128(name):
        return (t[name + "_lo"] != e[name + "_lo"]) | (
            t[name + "_hi"] != e[name + "_hi"]
        )

    c = jnp.full((n,), 46, jnp.uint32)
    c = jnp.where(t["code"] != e["code"], jnp.uint32(45), c)
    c = jnp.where(t["timeout"] != e["timeout"], jnp.uint32(44), c)
    c = jnp.where(t["user_data_32"] != e["user_data_32"], jnp.uint32(43), c)
    c = jnp.where(t["user_data_64"] != e["user_data_64"], jnp.uint32(42), c)
    c = jnp.where(ne128("user_data_128"), jnp.uint32(41), c)
    amount_ne = (t_amount.lo != e["amount_lo"]) | (t_amount.hi != e["amount_hi"])
    c = jnp.where(ne128("pending_id"), jnp.uint32(40), c)
    c = jnp.where(amount_ne, jnp.uint32(39), c)
    c = jnp.where(ne128("credit_account_id"), jnp.uint32(38), c)
    c = jnp.where(ne128("debit_account_id"), jnp.uint32(37), c)
    c = jnp.where(t["flags"] != e["flags"], jnp.uint32(36), c)
    return c


def _exists_postvoid(t, e, p, n) -> jax.Array:
    """post_or_void_pending_transfer_exists (state_machine.zig:1500-1561)."""

    def pair_ne(a, b, name):
        return (a[name + "_lo"] != b[name + "_lo"]) | (
            a[name + "_hi"] != b[name + "_hi"]
        )

    t_amount_zero = (t["amount_lo"] == 0) & (t["amount_hi"] == 0)
    amount_ne = jnp.where(
        t_amount_zero, pair_ne(e, p, "amount"), pair_ne(t, e, "amount")
    )
    ud128_zero = (t["user_data_128_lo"] == 0) & (t["user_data_128_hi"] == 0)
    ud128_ne = jnp.where(
        ud128_zero, pair_ne(e, p, "user_data_128"), pair_ne(t, e, "user_data_128")
    )
    ud64_ne = jnp.where(
        t["user_data_64"] == 0, e["user_data_64"] != p["user_data_64"],
        t["user_data_64"] != e["user_data_64"],
    )
    ud32_ne = jnp.where(
        t["user_data_32"] == 0, e["user_data_32"] != p["user_data_32"],
        t["user_data_32"] != e["user_data_32"],
    )
    c = jnp.full((n,), 46, jnp.uint32)
    c = jnp.where(ud32_ne, jnp.uint32(43), c)
    c = jnp.where(ud64_ne, jnp.uint32(42), c)
    c = jnp.where(ud128_ne, jnp.uint32(41), c)
    c = jnp.where(pair_ne(t, e, "pending_id"), jnp.uint32(40), c)
    c = jnp.where(amount_ne, jnp.uint32(39), c)
    c = jnp.where(t["flags"] != e["flags"], jnp.uint32(36), c)
    return c


create_transfers_full = jax.jit(
    staging.staged(create_transfers_full_impl, types.TRANSFER_DTYPE),
    donate_argnames=("ledger",),
    static_argnames=(
        "max_passes", "has_postvoid", "has_history", "use_waves",
    ),
)
