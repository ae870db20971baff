"""On-HBM open-addressing SoA hash table — the device analogue of an LSM Groove.

The reference resolves object lookups through an LSM tree hierarchy with a
set-associative cache in front (src/lsm/groove.zig:138+, cache_map.zig:10-25).
On TPU the working set lives resident in HBM as one struct-of-arrays
open-addressing table: lookups are a batched vectorized linear probe (a few
gathers over 8k lanes), and inserts are a batched claim protocol — both O(1)
expected per key at load factor < 0.5, fully inside jit, no host round trips.

Design:
- Capacity is a static power of two; slot = splitmix64(key) & (C-1).
- Empty slot: key == 0 (valid ids are nonzero: id_must_not_be_zero).
- Tombstones (from linked-chain rollback of inserts) keep ``tombstone=True``
  with key cleared; probes continue past them, inserts may not reuse them
  (wastes a slot per rolled-back insert; rollbacks are rare).
- Batched insert resolves intra-batch slot collisions by lane order: among
  unplaced lanes probing the same slot, the lowest batch index wins; losers
  advance their probe. Deterministic (a pure function of the batch).
- Both probe loops run in TWO PHASES over the same per-lane state: all N
  lanes only while more than a window of them (``_window``: 1024 of 8192)
  is still open, then one compaction and the surviving tail at window
  width.  Linear probing has a geometric tail, a trip costs its gathers at
  the width it runs, and a 1024-index gather costs the chip a sixth of an
  8192-index one (0.009-0.017 against 0.066-0.100 ms), a scatter a
  sixteenth (PERF.md PR 42, PR 32): ``claim_slots`` since PR 11,
  ``lookup`` since PR 42.  Results are those of the single loop, bit for
  bit (the tests keep both single loops as numpy oracles).

- A uint64 column is WRITTEN by halves: two independent one-operand uint32
  scatters (``_set_at``).  A TPU holds a 64-bit array as a pair of 32-bit
  arrays and compiles a 64-bit scatter to ONE scatter with two operands,
  which ran 1.02 ms over 8192 indices of a 2^23-slot column where the two
  one-operand uint32 scatters run 0.09-0.10 ms each (one TPU v5 lite,
  PERF.md PR 32).  Extracting the high half is the one conversion that
  compiler does not fold away (a table-sized ``hi >> 0`` pass, 0.05 ms a
  2^23 column); carrying the halves through the grouped loop, to pay it
  once a dispatch, was built and bought nothing end to end (same place).

All entry points are shape-stable and jit-traceable.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..u128 import mix64


def _halves(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(low, high) uint32 halves of a uint64 array."""
    return x.astype(jnp.uint32), (x >> jnp.uint64(32)).astype(jnp.uint32)


def _joined(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """The uint64 array of two uint32 halves.  ``* 2**32`` and not ``<< 32``:
    the TPU compiler folds the product into "the pair (lo, hi)" and leaves
    a shift behind as an elementwise pass."""
    return lo.astype(jnp.uint64) | hi.astype(jnp.uint64) * jnp.uint64(1 << 32)


def _set_at(col: jax.Array, idx: jax.Array, val) -> jax.Array:
    """``col.at[idx].set(val, mode="drop")``; a uint64 column as two
    one-operand uint32 scatters, low half and high half (module docstring:
    the chip's two-operand scatter costs five times the two together)."""
    if col.dtype != jnp.uint64:
        return col.at[idx].set(val, mode="drop")
    lo, hi = _halves(col)
    val_lo, val_hi = _halves(jnp.asarray(val, jnp.uint64))
    return _joined(
        lo.at[idx].set(val_lo, mode="drop"), hi.at[idx].set(val_hi, mode="drop")
    )


@struct.dataclass
class Table:
    """SoA open-addressing table. ``cols`` holds the value columns."""

    key_lo: jax.Array  # uint64[C]; 0 = empty/tombstone
    key_hi: jax.Array  # uint64[C]
    tombstone: jax.Array  # bool[C]
    cols: Dict[str, jax.Array]
    count: jax.Array  # uint64 scalar: live entries
    probe_overflow: jax.Array  # bool scalar: a probe exceeded max_probe (host must grow)

    @property
    def capacity(self) -> int:
        return self.key_lo.shape[0]


def make_table(capacity: int, col_specs: Dict[str, jnp.dtype]) -> Table:
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    # claim_slots carries slots (and the sentinel, == capacity) as uint32.
    assert capacity <= 1 << 31, "capacity must fit a uint32 slot"
    return Table(
        key_lo=jnp.zeros((capacity,), jnp.uint64),
        key_hi=jnp.zeros((capacity,), jnp.uint64),
        tombstone=jnp.zeros((capacity,), jnp.bool_),
        cols={name: jnp.zeros((capacity,), dt) for name, dt in col_specs.items()},
        count=jnp.uint64(0),
        probe_overflow=jnp.bool_(False),
    )


class LookupResult(NamedTuple):
    found: jax.Array  # bool[N]
    slot: jax.Array  # uint64[N] — valid where found
    overflow: jax.Array  # bool scalar — some lane exhausted max_probe


def _window(n: int) -> int:
    """Static width of a probe loop's narrow phase over ``n`` lanes: small
    enough that a narrow trip is ~an order cheaper than a wide one, large
    enough that the wide phase exits after the first few probes at load
    <= 0.5 (the open lanes decay geometrically with probe depth)."""
    return min(n, max(64, n // 8))


def compact_lanes(open_lanes: jax.Array, window: int) -> jax.Array:
    """int32[window]: the indices of the first ``window`` set lanes of
    ``open_lanes``, in lane order; fill lanes carry ``n`` (out of range:
    dropped by scatters).  ``jnp.nonzero(size=window, fill_value=n)`` by
    hand, in int32: under x64 jnp.nonzero cumsums in int64, which a TPU
    emulates as a u32-pair reduce-window whose scoped-vmem stack the v5e
    compiler cannot place inside the grouped dispatch's scan
    (RESOURCE_EXHAUSTED at compile)."""
    n = open_lanes.shape[0]
    pos = jnp.cumsum(open_lanes.astype(jnp.int32)) - 1
    return jnp.full((window,), n, jnp.int32).at[
        jnp.where(open_lanes & (pos < window), pos, window)
    ].set(jnp.arange(n, dtype=jnp.int32), mode="drop")


@functools.partial(jax.jit, static_argnames=("max_probe", "hash_shift"))
def lookup(
    table: Table,
    key_lo: jax.Array,
    key_hi: jax.Array,
    max_probe: int,
    hash_shift: int = 0,
) -> LookupResult:
    """Batched linear probe: for each key, find its slot or prove absence.

    ``hash_shift`` discards low hash bits before slotting — sharded tables use
    the low bits as the owner-shard index (parallel/sharded.py) and the rest
    for the local slot, so shard-local probes never cross devices.

    TWO-PHASE probing, as ``claim_slots``: the loop trips until the LAST
    lane has met its row or an empty slot, but linear probing has a
    geometric tail (at load 0.25-0.49 at most an eighth of 8192 lanes is
    open after 2-5 trips, the last after 9-29), and a trip costs its five
    gathers at whatever width it runs.  So a wide phase (all N lanes) runs
    only while more than ``_window(N)`` lanes are open, ONE compaction
    gathers the open lanes' homes and keys, a narrow phase finishes them at
    window width from the trip the wide phase stopped at, and one uint32
    scatter merges their slots back.  No resolved lane ever rejoins and
    every open lane advances one slot a trip in either phase, so the trip
    on which a lane resolves, and with it ``found``, ``slot`` and
    ``overflow``, is that of the single loop (tests/test_hash_table.py
    keeps the single loop as a numpy oracle).  Scopes ``tb/lookup_wide``,
    ``tb/lookup_compact`` (compaction and merge) and ``tb/lookup_narrow``
    name the parts in a profile (docs/tracing.md)."""
    capacity = table.capacity
    n = key_lo.shape[0]
    window = _window(n)
    mask = jnp.uint64(capacity - 1)
    home = (mix64(key_lo, key_hi) >> jnp.uint64(hash_shift)) & mask
    # A found slot rides the loops as uint32, "not found" as the capacity
    # (as ``claimed`` in claim_slots), so the merge is ONE one-operand
    # 32-bit scatter; it widens at exit.
    sentinel = jnp.uint32(capacity)

    def cond_above(open_limit):
        def cond(state):
            i, done, _ = state
            return (jnp.sum(~done, dtype=jnp.int32) > open_limit) & (
                i < max_probe)
        return cond

    def body_over(home, key_lo, key_hi):
        def body(state):
            i, done, slot = state
            cur = (home + jnp.uint64(i)) & mask
            t_lo = table.key_lo[cur]
            t_hi = table.key_hi[cur]
            tomb = table.tombstone[cur]
            match = ~done & (t_lo == key_lo) & (t_hi == key_hi) & ~tomb
            empty = ~done & (t_lo == 0) & (t_hi == 0) & ~tomb
            slot = jnp.where(match, cur.astype(jnp.uint32), slot)
            return i + 1, done | match | empty, slot
        return body

    # Lanes probing key 0 (invalid id / padding lanes) resolve immediately.
    is_null = (key_lo == 0) & (key_hi == 0)
    with jax.named_scope("tb/lookup_wide"):
        i, done, slot = jax.lax.while_loop(
            cond_above(window), body_over(home, key_lo, key_hi),
            (jnp.int32(0), is_null, jnp.full((n,), sentinel)),
        )

    # Exactly the open lanes (<= window unless the wide phase ended at
    # max_probe, in which case the narrow loop runs no trip, the window is
    # full of open lanes and overflow reads true, as it must).
    with jax.named_scope("tb/lookup_compact"):
        idx = compact_lanes(~done, window)
        active = idx < n
        idx_safe = jnp.where(active, idx, 0)
        home_w, lo_w, hi_w = home[idx_safe], key_lo[idx_safe], key_hi[idx_safe]

    with jax.named_scope("tb/lookup_narrow"):
        _, done_w, slot_w = jax.lax.while_loop(
            cond_above(0), body_over(home_w, lo_w, hi_w),
            (i, ~active, jnp.full((window,), sentinel)),
        )

    with jax.named_scope("tb/lookup_compact"):
        slot = slot.at[idx].set(slot_w, mode="drop")
    found = slot != sentinel
    return LookupResult(
        found=found,
        slot=jnp.where(found, slot, 0).astype(jnp.uint64),
        overflow=jnp.any(~done_w),
    )


def claim_slots(
    table: Table,
    key_lo: jax.Array,
    key_hi: jax.Array,
    insert_mask: jax.Array,
    max_probe: int,
    hash_shift: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Compute the insert slot for each masked key WITHOUT writing.

    Returns (claimed_slot[N], overflow).  Lets callers detect probe overflow
    BEFORE committing any state (the transfer kernel folds it into its
    routing flags so 'flags != 0 => nothing applied' holds exactly), then
    apply via write_rows.

    Placement protocol (unchanged since v1; this is a cost rewrite):
    every still-unplaced lane probes home+i at iteration i, and among
    unplaced lanes sharing a slot the lowest batch index wins.  Because ALL
    unplaced lanes advance together, two lanes can only collide when they
    share the same HOME slot — group membership is static.  So the winner
    of any iteration is simply the group's next lane in batch order: ONE
    upfront sort assigns each lane its rank within its home group, and the
    loop body just compares rank against a per-group placed counter.  The
    previous per-iteration argsort (an XLA comparator sort of all N lanes,
    the dominant term of the commit hot path at realistic table fills —
    BENCH_r08 vs_baseline) is gone; occupancy rides a 1-bit-per-slot packed
    bitmap so the loop carry is capacity/32 words, not a capacity-wide
    bool column.  Claimed slots are bit-identical to the sort-based
    protocol — tests/test_hash_table.py keeps that protocol as an inline
    numpy oracle and pins claim parity against it (random fills, masked
    lanes, forced same-home collisions).

    WINDOWED probing (the remaining PR 7 hot-path term): the loop trips
    to the MAX cluster depth over the batch, but after the first few
    probes only a geometric tail of lanes is still unplaced — paying
    N-lane gathers/scatters per trip for that tail is the per-iteration
    floor BENCH_r08 left on the table.  The loop therefore runs in two
    phases over the SAME protocol state: a wide phase (all N lanes) only
    while more than ``window`` lanes remain unplaced, then ONE compaction
    (jnp.nonzero at a static size) gathers exactly the surviving lanes
    and a narrow phase finishes them at window-width cost.  No placed
    lane ever rejoins and all unplaced lanes still advance together, so
    the iteration-by-iteration evolution — and every claimed slot — is
    bit-identical to the single-loop protocol (the same parity tests pin
    it).
    """
    capacity = table.capacity
    n = key_lo.shape[0]
    mask = jnp.uint64(capacity - 1)
    home = (mix64(key_lo, key_hi) >> jnp.uint64(hash_shift)) & mask
    sentinel = jnp.uint64(capacity)  # out-of-range: dropped by scatters
    lane = jnp.arange(n, dtype=jnp.uint32)

    # Home-group ranks (one sort per call, outside the probe loop): masked
    # lanes key to a shared tail group and never win, so their ranks are
    # inert.  rank = position within the group in batch-lane order.
    gkey = jnp.where(insert_mask, home, sentinel)
    order = jnp.lexsort((lane, gkey))
    s_home = gkey[order]
    s_head = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), s_home[1:] != s_home[:-1]]
    )
    gid_sorted = (jnp.cumsum(s_head.astype(jnp.int32)) - 1).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    gstart = jax.lax.cummax(jnp.where(s_head, pos, 0))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(pos - gstart)
    gid = jnp.zeros((n,), jnp.int32).at[order].set(gid_sorted)

    # Packed occupancy bitmap (1 bit/slot).  Tiny test tables may be
    # narrower than one word; pad with zero bits the probe mask never
    # addresses.
    occ_bool = (table.key_lo != 0) | (table.key_hi != 0) | table.tombstone
    pad = (-capacity) % 32
    if pad:
        occ_bool = jnp.concatenate(
            [occ_bool, jnp.zeros((pad,), jnp.bool_)]
        )
    occ0 = jnp.sum(
        occ_bool.reshape(-1, 32).astype(jnp.uint32)
        << jnp.arange(32, dtype=jnp.uint32)[None, :],
        axis=1, dtype=jnp.uint32,
    )
    nwords = jnp.uint64(occ0.shape[0])

    window = _window(n)

    def wide_cond(state):
        _, _, unplaced, _, overflow, _ = state
        return (jnp.sum(unplaced) > window) & ~overflow

    def wide_body(state):
        occ, offset, unplaced, claimed, _, next_rank = state
        cur = (home + offset) & mask
        word = cur >> jnp.uint64(5)
        bit = (cur & jnp.uint64(31)).astype(jnp.uint32)
        occupied = ((occ[word] >> bit) & jnp.uint32(1)).astype(jnp.bool_)

        # The group's next unclaimed lane in batch order is THE winner
        # (lanes sharing a slot always share a home — see docstring).
        is_winner = rank == next_rank[gid]
        win = unplaced & ~occupied & is_winner
        claimed = jnp.where(win, cur.astype(jnp.uint32), claimed)
        # Winners' slots are unique, but two winners may share a WORD:
        # distinct bits make the add an OR with no carries.
        occ = occ.at[jnp.where(win, word, nwords)].add(
            jnp.uint32(1) << bit, mode="drop"
        )
        next_rank = next_rank.at[jnp.where(win, gid, n)].add(1, mode="drop")

        unplaced = unplaced & ~win
        offset = jnp.where(unplaced, offset + jnp.uint64(1), offset)
        overflow = jnp.any(offset >= jnp.uint64(max_probe))
        return occ, offset, unplaced, claimed, overflow, next_rank

    offset0 = jnp.zeros((n,), jnp.uint64)
    unplaced0 = insert_mask
    # Slots ride the loops as uint32 (a slot is below the capacity, the
    # sentinel IS the capacity, make_table bounds it by 2^31) and widen at
    # exit: the narrow loop's scatter of a uint64 ``claimed`` was the
    # chip's two-operand form (hash_table docstring).
    claimed0 = jnp.full((n,), capacity, jnp.uint32)
    overflow0 = jnp.bool_(False)
    next_rank0 = jnp.zeros((n,), jnp.int32)

    occ, offset, unplaced, claimed, overflow, next_rank = jax.lax.while_loop(
        wide_cond, wide_body,
        (occ0, offset0, unplaced0, claimed0, overflow0, next_rank0),
    )

    # Compaction: exactly the surviving unplaced lanes (<= window unless
    # the wide phase exited on overflow, in which case the narrow cond is
    # already false and the truncation is inert).  Fill lanes carry index
    # n: inactive in the narrow body, dropped by its scatters.
    idx = compact_lanes(unplaced, window)
    active = idx < n
    idx_safe = jnp.where(active, idx, 0)
    home_w = home[idx_safe]
    rank_w = rank[idx_safe]
    gid_w = gid[idx_safe]

    def narrow_cond(state):
        _, _, unplaced_w, _, overflow, _ = state
        return jnp.any(unplaced_w) & ~overflow

    def narrow_body(state):
        occ, off_w, unplaced_w, claimed, _, next_rank = state
        cur = (home_w + off_w) & mask
        word = cur >> jnp.uint64(5)
        bit = (cur & jnp.uint64(31)).astype(jnp.uint32)
        occupied = ((occ[word] >> bit) & jnp.uint32(1)).astype(jnp.bool_)
        is_winner = rank_w == next_rank[gid_w]
        win = unplaced_w & ~occupied & is_winner
        claimed = claimed.at[jnp.where(win, idx, n)].set(
            cur.astype(jnp.uint32), mode="drop"
        )
        occ = occ.at[jnp.where(win, word, nwords)].add(
            jnp.uint32(1) << bit, mode="drop"
        )
        next_rank = next_rank.at[jnp.where(win, gid_w, n)].add(
            1, mode="drop"
        )
        unplaced_w = unplaced_w & ~win
        off_w = jnp.where(unplaced_w, off_w + jnp.uint64(1), off_w)
        overflow = jnp.any(off_w >= jnp.uint64(max_probe))
        return occ, off_w, unplaced_w, claimed, overflow, next_rank

    _, _, _, claimed, overflow, _ = jax.lax.while_loop(
        narrow_cond, narrow_body,
        (occ, offset[idx_safe], unplaced[idx_safe] & active,
         claimed, overflow, next_rank),
    )
    return claimed.astype(jnp.uint64), overflow


def claim_slots_empty(
    capacity: int,
    key_lo: jax.Array,
    key_hi: jax.Array,
    insert_mask: jax.Array,
    hash_shift: int = 0,
) -> jax.Array:
    """claim_slots into an EMPTY table of ``capacity`` slots, for lanes by
    the million (a rehash: ops/cold.drop_evicted): the same protocol, so the
    same slots, without the sort.

    claim_slots ranks each lane within its home group by one sort of all the
    lanes, which a batch pays gladly and a rehash cannot: the v5e compiler
    takes 207 s over the sort of 2^24 lanes and 100 s over one of 2^16.
    Here the winner of a contested slot, the lowest lane, is found by a
    scatter-min of the bidders' lane numbers into a per-slot ``owner``
    column, which is the occupancy as well (the table starts empty), and
    since every unplaced lane advances at every trip the probe offset is
    the trip's number.  Wide phase, one compaction, narrow phase, as in
    claim_slots.  Returns the claimed slots (``capacity`` for a masked
    lane); the table's load is at most 0.5, so every lane is placed."""
    n = key_lo.shape[0]
    assert n < 0xFFFFFFFF
    free = jnp.uint32(0xFFFFFFFF)
    slot_mask = jnp.uint32(capacity - 1)
    home = (
        (mix64(key_lo, key_hi) >> jnp.uint64(hash_shift))
        & jnp.uint64(capacity - 1)
    ).astype(jnp.uint32)

    def probe(home, lane, state, stop):
        def open_lanes(state):
            _, trip, unplaced, _ = state
            return (jnp.sum(unplaced) > stop) & (trip < capacity)

        def trip_body(state):
            owner, trip, unplaced, claimed = state
            cur = (home + trip) & slot_mask
            bid = unplaced & (owner[cur] == free)
            owner = owner.at[jnp.where(bid, cur, capacity)].min(
                lane, mode="drop")
            win = bid & (owner[cur] == lane)
            return (owner, trip + jnp.uint32(1), unplaced & ~win,
                    jnp.where(win, cur, claimed))

        return jax.lax.while_loop(open_lanes, trip_body, state)

    window = _window(n)
    owner, trip, unplaced, claimed = probe(
        home, jnp.arange(n, dtype=jnp.uint32),
        (jnp.full((capacity,), free), jnp.uint32(0), insert_mask,
         jnp.full((n,), capacity, jnp.uint32)),
        window,
    )
    idx = compact_lanes(unplaced, window)
    active = idx < n
    at = jnp.where(active, idx, 0)
    _, _, _, claimed_w = probe(
        home[at], idx.astype(jnp.uint32),
        (owner, trip, active, jnp.full((window,), capacity, jnp.uint32)),
        0,
    )
    return claimed.at[jnp.where(active, idx, n)].set(
        claimed_w, mode="drop").astype(jnp.uint64)


def write_rows(
    table: Table,
    key_lo: jax.Array,
    key_hi: jax.Array,
    claimed: jax.Array,
    write_mask: jax.Array,
    rows: Dict[str, jax.Array],
) -> Table:
    """Write keys + value columns at slots from claim_slots (unique across
    the batch by construction); ``write_mask`` may be narrower than the
    claim mask (e.g. a commit flag zeroed it).  uint64 columns are written
    by halves (``_set_at``: 2 x 0.09-0.10 ms a column, not 1.02, on the chip)."""
    sentinel = jnp.uint64(table.capacity)
    scatter_idx = jnp.where(write_mask & (claimed < sentinel), claimed, sentinel)
    key_lo_new = _set_at(table.key_lo, scatter_idx, key_lo)
    key_hi_new = _set_at(table.key_hi, scatter_idx, key_hi)
    tomb_new = _set_at(table.tombstone, scatter_idx, False)
    cols_new = {
        name: _set_at(table.cols[name], scatter_idx, rows[name])
        for name in table.cols
    }
    inserted = jnp.sum((scatter_idx < sentinel).astype(jnp.uint64))
    return table.replace(
        key_lo=key_lo_new,
        key_hi=key_hi_new,
        tombstone=tomb_new,
        cols=cols_new,
        count=table.count + inserted,
    )


@functools.partial(jax.jit, static_argnames=("max_probe", "hash_shift"))
def insert(
    table: Table,
    key_lo: jax.Array,
    key_hi: jax.Array,
    insert_mask: jax.Array,
    rows: Dict[str, jax.Array],
    max_probe: int,
    hash_shift: int = 0,
) -> Tuple[Table, jax.Array]:
    """Batched insert of *new, distinct* keys where ``insert_mask`` is set
    (claim_slots + write_rows; probe overflow is recorded on the table)."""
    claimed, overflow = claim_slots(
        table, key_lo, key_hi, insert_mask, max_probe, hash_shift
    )
    table = write_rows(table, key_lo, key_hi, claimed, insert_mask, rows)
    return table.replace(probe_overflow=table.probe_overflow | overflow), claimed


def gather_cols(table: Table, slot: jax.Array, valid: jax.Array) -> Dict[str, jax.Array]:
    """Gather value columns at ``slot``, zeroed where ``valid`` is False."""
    safe = jnp.where(valid, slot, jnp.uint64(0))
    return {
        name: jnp.where(valid, col[safe], jnp.zeros((), col.dtype))
        for name, col in table.cols.items()
    }


def scatter_cols(
    table: Table, slot: jax.Array, valid: jax.Array, updates: Dict[str, jax.Array]
) -> Table:
    """Scatter updated value columns back at ``slot`` where ``valid``.

    Slots must be unique among valid lanes (callers pre-combine per-slot
    updates — see the segment reduction in the commit kernel).  uint64
    columns are written by halves (``_set_at``: the chip's two-operand
    scatter took 1.22 ms over 16384 balance slots)."""
    sentinel = jnp.uint64(table.capacity)
    idx = jnp.where(valid, slot, sentinel)
    cols = dict(table.cols)
    for name, val in updates.items():
        cols[name] = _set_at(cols[name], idx, val)
    return table.replace(cols=cols)


def grow(table: Table, new_capacity: int, hash_shift: int = 0) -> Table:
    """Rehash every live entry into a table of ``new_capacity`` slots.

    The reference absorbs unbounded growth in the LSM tree (lsm/tree.zig:87);
    the device-table analogue is an explicit stop-the-world rehash, run by the
    host between batches when the load factor approaches 0.5 or a probe
    overflows (VERDICT.md round-1 Weak #5).  One batched insert call with all
    old slots as lanes; tombstones are dropped in the process.
    """
    assert new_capacity & (new_capacity - 1) == 0
    assert new_capacity >= table.capacity
    live = (table.key_lo != 0) | (table.key_hi != 0)
    fresh = make_table(new_capacity, {k: v.dtype for k, v in table.cols.items()})
    grown, _ = insert(
        fresh, table.key_lo, table.key_hi, live, table.cols,
        max_probe=new_capacity, hash_shift=hash_shift,
    )
    return grown


def remove_to_tombstone(table: Table, slot: jax.Array, valid: jax.Array) -> Table:
    """Clear keys at ``slot`` (rollback of inserts), leaving tombstones."""
    sentinel = jnp.uint64(table.capacity)
    idx = jnp.where(valid, slot, sentinel)
    removed = jnp.sum(valid.astype(jnp.uint64))
    return table.replace(
        key_lo=_set_at(table.key_lo, idx, jnp.uint64(0)),
        key_hi=_set_at(table.key_hi, idx, jnp.uint64(0)),
        tombstone=_set_at(table.tombstone, idx, True),
        count=table.count - removed,
    )

