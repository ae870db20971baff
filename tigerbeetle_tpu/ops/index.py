"""Device-side secondary index for transfer queries (round-2, VERDICT #4).

The reference answers get_account_transfers with per-field CompositeKey index
trees walked by a ScanBuilder (lsm/scan_tree.zig:31-33, scan_builder.zig).
Round 1 approximated that with an argsort over the WHOLE transfers table per
query — O(capacity log capacity) per call.  This module is the TPU-native
index: the logarithmic method (Bentley–Saxe) over sorted runs.

Structure: per side (debit / credit) a pyramid of sorted runs; level k holds
B·2^k entries sorted by (account_hi, account_lo, timestamp), B = one batch of
lanes.  Each committed batch appends one sorted run at level 0; when a level
is occupied the runs carry upward binary-counter style, each merge one
concat+sort of static shape (compiled once per level; above level 9 the
sort stops there and the levels above are merged in by compare-exchange
passes, _merge2: a sort's executable grows with its rows).  Amortized append cost
is O(log N) sorts of geometric sizes; a query binary-searches every level
(static unroll) and gathers a bounded candidate window, so query cost is
O(levels · K) — FLAT in table capacity.

What a sort moves: the rows themselves.  _sort_level orders a level by three
stable single-key passes (ts, then acct_lo, then acct_hi: jnp.lexsort's
order) and each pass carries the other four columns through its sorts as
payload, so neither build_runs nor a merge holds a permutation or a gather.
On a TPU v5e a gather is priced by the index, ~7 ns each whatever the array,
and a sort of the same rows at a seventh of ONE one-column gather: until
PR 45 three argsorts and the 18 gathers of their permutations were 96.8 % of
every merge (383.0 of 395.5 ms in 42 executions) and 97.5 % of build_runs
(call `a44`'s kept profile; PERF.md section 5, "Per operation").

Entries carry the transfer id (not its table slot) so hash-table growth
rehashes never invalidate the index; query results are resolved to rows with
one batched id lookup.  Sentinel entries (account id 2^128-1, an id that can
never exist: id_must_not_be_int_max) pad partial runs and sort after every
real entry.

Where a run's keys come from: a level-0 run is sorted from the key columns
(sm.INDEX_KEY_COLS: both sides' account ids and the stored timestamp) of the
rows a commit program just wrote, which the program returns beside its codes
(sm.create_transfers_fast_probed, machine._group_fast_dispatch,
tf.create_transfers_full), so an append reads no table; the fast programs
return the written-lanes mask too, and build_runs picks a grouped dispatch's
row itself, so such an append is one dispatch.  A route whose kernel
returns none (the sequential path, the unprobed create_transfers_fast) reads
them back by id through probe_keys, the one place this module walks the
transfers table per batch.

The index is DERIVED state: it is not checkpointed; restarts and state sync
rebuild it from the transfers table in one shot (rebuild()).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import hash_table as ht
from . import state_machine as sm

U64M = (1 << 64) - 1

COLS = ("acct_lo", "acct_hi", "ts", "tid_lo", "tid_hi")


def _sentinel_level(capacity: int) -> Dict[str, jax.Array]:
    lvl = {name: jnp.full((capacity,), U64M, jnp.uint64) for name in COLS}
    return lvl


# The three stable passes of _sort_level, least significant key first.
_PASSES = ("ts", "acct_lo", "acct_hi")


def _sort_level(lvl: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Order by (acct_hi, acct_lo, ts) — jnp.lexsort's order, as three
    stable single-key passes that MOVE THE ROWS THEMSELVES: a pass sorts its
    key column once beside each of the other four columns, one stable
    two-operand ``lax.sort`` a column (a stable sort's permutation goes by
    the keys alone, so the four agree), and hands all five on, already
    moved.  No argsort, no permutation, no gather.

    Why not a permutation: on a v5e a one-column gather costs 7-10 ns an
    index whatever the array and a sort of the same rows a fraction of ONE
    such gather: the 18 gathers that three argsorts needed were 97 % of every
    merge (call `a44`'s kept profile), and a level of 16,384 random rows
    took 2.157 ms that way against 0.221 this way, one of 2,097,152 rows
    373.4 against 68.0 (PR 45's probe, tools/index_sort_probe.py, call `a45`;
    PERF.md section 5 "Per operation").  Why a column at a time and not one
    sort with four payload columns, which is twice as fast again on the
    device (0.112 and 36.2 ms): COMPILE time, which a client pays inside its
    request the first time a level fills and a cold start pays for every
    level.  The v5e compiler's time for a sort grows faster than the sort's
    operand count, and it compiles a program's identical sorts once: these
    twelve (u64, u64) sorts compile as the three argsorts did (12.1 s at
    16,384 rows and 53.7 at 2,097,152 against 12.4 and 54.6), three sorts of
    five u64 operands in 23.6 and 126.9 s, one three-key sort in 39.7 s at
    16,384 (same probe, the chip's host)."""
    for key in _PASSES:
        moved = {}
        for name in COLS:
            if name != key:
                moved[key], moved[name] = jax.lax.sort(
                    (lvl[key], lvl[name]), num_keys=1, is_stable=True)
        lvl = moved
    return {name: lvl[name] for name in COLS}


@jax.jit
def probe_keys(
    ledger: sm.Ledger, id_lo: jax.Array, id_hi: jax.Array, ok: jax.Array
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """(keys, use) for build_runs, read back from the transfers table by id:
    for a route whose commit kernel does not return the keys it stored."""
    with jax.named_scope("tb/index_probe"):
        look = ht.lookup(ledger.transfers, id_lo, id_hi, sm.MAX_PROBE)
        use = ok & look.found
        rows = ht.gather_cols(ledger.transfers, look.slot, use)
    return {name: rows[name] for name in sm.INDEX_KEY_COLS}, use


@jax.jit
def build_runs(
    keys: Dict[str, jax.Array], id_lo: jax.Array, id_hi: jax.Array,
    ok: jax.Array, row=None,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Sorted level-0 runs (debit side, credit side) for a just-committed
    batch: ``keys`` are the sm.INDEX_KEY_COLS of the rows the commit wrote
    (from its kernel, or from probe_keys), ``ok`` the lanes it wrote; each
    side is keyed by its account and sorted.  With ``row`` the arguments are
    a grouped dispatch's stacked outputs and the batch is that row of each:
    selected here, inside the one program, because on a TPU every slice the
    host takes is a dispatch of its own that the device waits for."""
    if row is not None:
        keys, id_lo, id_hi, ok = jax.tree_util.tree_map(
            lambda col: jax.lax.dynamic_index_in_dim(
                col, row, keepdims=False),
            (keys, id_lo, id_hi, ok),
        )

    def side(acct_field):
        lvl = {
            "acct_lo": jnp.where(ok, keys[acct_field + "_lo"], jnp.uint64(U64M)),
            "acct_hi": jnp.where(ok, keys[acct_field + "_hi"], jnp.uint64(U64M)),
            "ts": jnp.where(ok, keys["timestamp"], jnp.uint64(U64M)),
            "tid_lo": jnp.where(ok, id_lo, jnp.uint64(U64M)),
            "tid_hi": jnp.where(ok, id_hi, jnp.uint64(U64M)),
        }
        with jax.named_scope("tb/index_sort"):
            return _sort_level(lvl)

    return side("debit_account_id"), side("credit_account_id")


def _merge(levels: List[Dict[str, jax.Array]]) -> Dict[str, jax.Array]:
    with jax.named_scope("tb/index_merge"):
        cat = {
            name: jnp.concatenate([lvl[name] for lvl in levels])
            for name in COLS
        }
        return _sort_level(cat)


# A carry into a level above this one sorts no further than it: the levels
# above are merged in, two sorted levels at a time (_merge2).
_SORT_LEVELS = 9


def _merge2(a: Dict[str, jax.Array], b: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Two levels of n rows each, both in _sort_level's order, as one of 2n:
    a bitonic merge.  ``a`` then ``b`` reversed is a bitonic sequence, and
    log2(2n) compare-exchange passes at strides n, n/2, ..., 1 order it; a
    pass is elementwise over the five columns rolled by its stride, and the
    passes are the trips of ONE loop, so the program is a pass long.

    Why the top levels do not sort: a sort's executable and its compile time
    grow with its rows on a v5e (the level-10 merge as a sort: 27.8 MB in
    the compile cache and about a minute inside the request that first
    fills the level, after 25.7 MB and as long for level 9), and a level
    above the ninth is filled once in 1,024 requests.  This program, for
    level 10: 0.30 MB, a second to compile, 94 ms for its 2^23 rows (the
    passes unrolled at static strides: 10.8 MB, 8 s, 100 ms; my chip run,
    PR 48, call `p48`).  Equal keys are equal rows (a timestamp is unique a
    side; sentinel rows are all ones), so the passes, which are not stable,
    give what the stable sort gives."""
    def less(p, q):
        return (p["acct_hi"] < q["acct_hi"]) | ((p["acct_hi"] == q["acct_hi"]) & (
            (p["acct_lo"] < q["acct_lo"]) | (
                (p["acct_lo"] == q["acct_lo"]) & (p["ts"] < q["ts"]))))

    with jax.named_scope("tb/index_merge"):
        rows = {name: jnp.concatenate([a[name], b[name][::-1]]) for name in COLS}
        n = rows["ts"].shape[0]
        lane = jnp.arange(n, dtype=jnp.uint32)

        def exchange(i, rows):
            # Lane j is exchanged with lane j ^ stride: the pair's first
            # keeps the lesser row, its second the greater.
            stride = (n // 2) >> i
            second = (lane & stride.astype(jnp.uint32)) != 0
            other = {
                name: jnp.where(
                    second, jnp.roll(col, stride), jnp.roll(col, -stride))
                for name, col in rows.items()
            }
            take = less(other, rows) ^ second
            return {
                name: jnp.where(take, other[name], col)
                for name, col in rows.items()
            }

        return jax.lax.fori_loop(0, n.bit_length() - 1, exchange, rows)


_merge_jit = jax.jit(_merge)
_merge2_jit = jax.jit(_merge2)
_sort_level_jit = jax.jit(_sort_level)


def _carry(run: Dict[str, jax.Array], levels: List[Dict[str, jax.Array]]):
    """``run`` and the occupied ``levels`` below level k = len(levels), as
    level k's rows."""
    low = min(len(levels), _SORT_LEVELS)
    out = _merge_jit([run] + levels[:low])
    for lvl in levels[low:]:
        out = _merge2_jit(out, lvl)
    return out


@functools.partial(jax.jit, static_argnames=("acct_field", "capacity"))
def _full_build_side(ledger: sm.Ledger, acct_field: str, capacity: int):
    """One sorted run over every live transfer (restart/state-sync rebuild)."""
    t = ledger.transfers
    live = ((t.key_lo != 0) | (t.key_hi != 0)) & ~t.tombstone
    n = t.capacity
    assert capacity >= n
    pad = capacity - n

    def col(vals):
        v = jnp.where(live, vals, jnp.uint64(U64M))
        return jnp.concatenate([v, jnp.full((pad,), U64M, jnp.uint64)])

    lvl = {
        "acct_lo": col(t.cols[acct_field + "_lo"]),
        "acct_hi": col(t.cols[acct_field + "_hi"]),
        "ts": col(t.cols["timestamp"]),
        "tid_lo": col(t.key_lo),
        "tid_hi": col(t.key_hi),
    }
    return _sort_level(lvl)


def _search3(lvl, q_hi, q_lo, q_ts):
    """First index with (acct_hi, acct_lo, ts) >= (q_hi, q_lo, q_ts)."""
    n = lvl["ts"].shape[0]
    lo = jnp.int64(0)
    hi = jnp.int64(n)
    for _ in range(int(n).bit_length()):
        mid = jnp.minimum((lo + hi) // 2, n - 1)
        m_hi = lvl["acct_hi"][mid]
        m_lo = lvl["acct_lo"][mid]
        m_ts = lvl["ts"][mid]
        less = (
            (m_hi < q_hi)
            | ((m_hi == q_hi) & (m_lo < q_lo))
            | ((m_hi == q_hi) & (m_lo == q_lo) & (m_ts < q_ts))
        )
        active = lo < hi
        lo = jnp.where(active & less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
    return lo


def _query_side(levels, acct_lo, acct_hi, ts_min, ts_max, k, descending):
    """Up to k (ts, tid) candidates for one side across all levels."""
    cand_ts, cand_lo, cand_hi = [], [], []
    for lvl in levels:
        n = lvl["ts"].shape[0]
        if descending:
            # Window ENDING at the first entry beyond (acct, ts_max).
            upper = _search3(lvl, acct_hi, acct_lo, ts_max + jnp.uint64(1))
            pos = upper - 1 - jnp.arange(k, dtype=jnp.int64)
        else:
            lower = _search3(lvl, acct_hi, acct_lo, ts_min)
            pos = lower + jnp.arange(k, dtype=jnp.int64)
        in_range = (pos >= 0) & (pos < n)
        safe = jnp.clip(pos, 0, n - 1)
        e_hi = lvl["acct_hi"][safe]
        e_lo = lvl["acct_lo"][safe]
        e_ts = lvl["ts"][safe]
        valid = (
            in_range
            & (e_hi == acct_hi) & (e_lo == acct_lo)
            & (e_ts >= ts_min) & (e_ts <= ts_max)
        )
        cand_ts.append(jnp.where(valid, e_ts, jnp.uint64(U64M)))
        cand_lo.append(jnp.where(valid, lvl["tid_lo"][safe], 0))
        cand_hi.append(jnp.where(valid, lvl["tid_hi"][safe], 0))
    return (
        jnp.concatenate(cand_ts),
        jnp.concatenate(cand_lo),
        jnp.concatenate(cand_hi),
    )


@functools.partial(jax.jit, static_argnames=("k", "descending"))
def query_transfers(
    dr_levels: Tuple[Dict[str, jax.Array], ...],
    cr_levels: Tuple[Dict[str, jax.Array], ...],
    acct_lo: jax.Array,
    acct_hi: jax.Array,
    ts_min: jax.Array,
    ts_max: jax.Array,
    want_debits: jax.Array,
    want_credits: jax.Array,
    k: int,
    descending: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(valid[k], tid_lo[k], tid_hi[k]) in result order: the union-merge of
    the debit/credit index scans (scan_merge.zig union), timestamp-ordered."""
    big = jnp.uint64(U64M)
    all_ts, all_lo, all_hi = [], [], []
    for levels, want in ((dr_levels, want_debits), (cr_levels, want_credits)):
        if not levels:
            continue
        ts, lo, hi = _query_side(
            levels, acct_lo, acct_hi, ts_min, ts_max, k, descending
        )
        all_ts.append(jnp.where(want, ts, big))
        all_lo.append(lo)
        all_hi.append(hi)
    if not all_ts:
        z = jnp.zeros((k,), jnp.uint64)
        return jnp.zeros((k,), jnp.bool_), z, z
    ts = jnp.concatenate(all_ts)
    lo = jnp.concatenate(all_lo)
    hi = jnp.concatenate(all_hi)
    # A transfer with both sides on the filtered account cannot exist
    # (accounts_must_be_different), so the union has no duplicates.
    sort_key = jnp.where(ts == big, big, jnp.where(descending, ~ts, ts))
    order = jnp.argsort(sort_key)[:k]
    valid = ts[order] != big
    return valid, lo[order], hi[order]


class TransferIndex:
    """Host driver: owns the device level arrays and the (host-side) level
    occupancy that decides the Bentley–Saxe carry chain per append.

    NOTE: ops/scan_builder.py FieldIndex is this pyramid's single-side
    generic twin — a fix to either's level logic almost certainly applies
    to both."""

    def __init__(self, base: int) -> None:
        assert base & (base - 1) == 0
        self.base = base
        self.dr_levels: List[Dict[str, jax.Array]] = []
        self.cr_levels: List[Dict[str, jax.Array]] = []
        self.occupied: List[bool] = []
        # A fresh machine's empty index matches its empty table; staleness
        # comes only from restore/state-sync (reset()), and is cured by a
        # wholesale rebuild on next use.
        self.stale = False
        # Source of extra host rows to index at rebuild (the machine wires
        # its cold-tier runs here): the stale-rebuild fallback must cover
        # them too, or evicted transfers silently vanish from queries.
        self.extra_rows_provider = None
        # Monotonic count of NEW level allocations: each new level is a
        # fresh power-of-two shape class whose first merge/fill jit-
        # compiles (bounded: log(rows) levels).  The machine's TB_SANITIZE
        # recompile tripwire diffs this to forgive exactly those compiles.
        self.shape_class_events = 0

    # -- maintenance --------------------------------------------------------

    def reset(self) -> None:
        self.dr_levels, self.cr_levels, self.occupied = [], [], []
        self.stale = True

    def _ensure_level(self, k: int) -> None:
        while len(self.occupied) <= k:
            cap = self.base << len(self.occupied)
            self.dr_levels.append(_sentinel_level(cap))
            self.cr_levels.append(_sentinel_level(cap))
            self.occupied.append(False)
            self.shape_class_events += 1  # new size class: first-use jits

    def append_batch(
        self, keys: Dict[str, jax.Array], id_lo: jax.Array, id_hi: jax.Array,
        ok: jax.Array, row=None,
    ) -> None:
        if self.stale:
            return  # rebuilt wholesale on next query
        dr_run, cr_run = build_runs(keys, id_lo, id_hi, ok, row)
        k = 0
        while k < len(self.occupied) and self.occupied[k]:
            k += 1
        self._ensure_level(k)
        if k == 0:
            self.dr_levels[0] = dr_run
            self.cr_levels[0] = cr_run
        else:
            self.dr_levels[k] = _carry(dr_run, self.dr_levels[:k])
            self.cr_levels[k] = _carry(cr_run, self.cr_levels[:k])
            for j in range(k):
                cap = self.base << j
                self.dr_levels[j] = _sentinel_level(cap)
                self.cr_levels[j] = _sentinel_level(cap)
                self.occupied[j] = False
        self.occupied[k] = True

    def rebuild(self, ledger: sm.Ledger, extra_rows=None) -> None:
        """Full rebuild from the live table (restart / state sync / explicit
        invalidation). One argsort of the table per side.

        ``extra_rows``: host TRANSFER_DTYPE arrays to index as well — the
        cold-tier runs, whose rows left the hot table but must stay
        queryable (get_account_transfers resolves their ids from the
        spill).  Defaults to whatever ``extra_rows_provider`` supplies, so
        EVERY rebuild path (including the stale fallback in query()) covers
        the cold tier."""
        if extra_rows is None:
            extra_rows = (
                self.extra_rows_provider() if self.extra_rows_provider else ()
            )
        cap = max(self.base, ledger.transfers.capacity)
        k = (cap // self.base - 1).bit_length()
        self.dr_levels, self.cr_levels, self.occupied = [], [], []
        self._ensure_level(k)
        self.dr_levels[k] = _full_build_side(
            ledger, "debit_account_id", self.base << k
        )
        self.cr_levels[k] = _full_build_side(
            ledger, "credit_account_id", self.base << k
        )
        self.occupied[k] = True
        for rows in extra_rows:
            self._add_host_rows(rows)
        self.stale = False

    def _add_host_rows(self, rows) -> None:
        """Occupy a free level with host rows (cold-tier runs at rebuild)."""
        import numpy as np

        rows = np.asarray(rows)
        n = len(rows)
        if n == 0:
            return
        j = max(0, ((n + self.base - 1) // self.base - 1).bit_length())
        self._ensure_level(j)
        while self.occupied[j]:
            j += 1
            self._ensure_level(j)

        def level(acct_field):
            cap = self.base << j

            def col(vals):
                out = np.full((cap,), U64M, np.uint64)
                out[:n] = vals
                return jnp.asarray(out)

            return _sort_level_jit({
                "acct_lo": col(rows[acct_field + "_lo"]),
                "acct_hi": col(rows[acct_field + "_hi"]),
                "ts": col(rows["timestamp"]),
                "tid_lo": col(rows["id_lo"]),
                "tid_hi": col(rows["id_hi"]),
            })

        self.dr_levels[j] = level("debit_account_id")
        self.cr_levels[j] = level("credit_account_id")
        self.occupied[j] = True

    # -- queries ------------------------------------------------------------

    def query(
        self, ledger: sm.Ledger, acct_lo, acct_hi, ts_min, ts_max,
        want_debits, want_credits, k: int, descending: bool,
    ):
        if self.stale:
            self.rebuild(ledger)
        return query_transfers(
            tuple(self.dr_levels), tuple(self.cr_levels),
            acct_lo, acct_hi, ts_min, ts_max, want_debits, want_credits,
            k, descending,
        )
