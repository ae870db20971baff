"""The secondary index orders a level INSIDE its sorts (ops/index.py:
`_sort_level` moves the five columns through three stable passes; no
permutation, no gather).  The benchmark's `correct` reads no index, so this
file is the guard on the order: `_sort_level`, `build_runs` and `_merge`
against a numpy oracle (`np.lexsort((ts, acct_lo, acct_hi))` applied to all
five columns: the form the tree held until PR 45, kept here as the oracle and
nowhere as a path), bit for bit; `TransferIndex` and `FieldIndex` beside a
pyramid kept in numpy by the same oracle, level for level and answer for
answer; and the lowered programs hold no gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_pipeline import LANES, N_ACCOUNTS, batch, make_machine
from tigerbeetle_tpu import types
from tigerbeetle_tpu.ops import index
from tigerbeetle_tpu.ops import scan_builder as sb
from tigerbeetle_tpu.ops import state_machine as sm

U64M = np.uint64(index.U64M)
N = 256     # rows of a level-0 run in the cases below


def oracle(lvl):
    """A level ordered by (acct_hi, acct_lo, ts), ties in input order."""
    lvl = {name: np.asarray(lvl[name]) for name in index.COLS}
    order = np.lexsort((lvl["ts"], lvl["acct_lo"], lvl["acct_hi"]))
    return {name: lvl[name][order] for name in index.COLS}


def assert_levels_equal(got, want, ctx=""):
    for name in index.COLS:
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name])), (
            f"{ctx}{name}")


def _level(rng, n, accounts, *, acct_hi=1, ts_shift=0, live=None):
    """`n` rows, the first `live` real: accounts drawn from `accounts`
    (duplicates), distinct timestamps shifted left by `ts_shift`, ids that
    tell the rows apart; the rest sentinels."""
    live = n if live is None else live
    cols = {
        "acct_lo": rng.choice(accounts, n).astype(np.uint64),
        "acct_hi": rng.integers(0, acct_hi, n).astype(np.uint64),
        "ts": (rng.permutation(n).astype(np.uint64) + np.uint64(1))
        << np.uint64(ts_shift),
        "tid_lo": rng.integers(1, 1 << 62, n).astype(np.uint64),
        "tid_hi": rng.integers(0, 1 << 62, n).astype(np.uint64),
    }
    for col in cols.values():
        col[live:] = U64M
    return cols


def _duplicate_accounts(rng, n):
    return _level(rng, n, np.arange(1, 8))


def _acct_hi_set(rng, n):
    return _level(rng, n, np.arange(1, 8), acct_hi=4)


def _high_halves_only(rng, n):
    """Every key column differs in the high `u32` half of its `uint64`
    alone: a comparison that looked at the low half would see ties."""
    lvl = _level(rng, n, np.arange(1, 8, dtype=np.uint64) << np.uint64(32),
                 acct_hi=4, ts_shift=32)
    lvl["acct_hi"] <<= np.uint64(32)
    return lvl


def _partial_run(rng, n):
    return _level(rng, n, np.arange(1, 8), acct_hi=2, live=n // 3)


def _ties(rng, n):
    """Whole keys repeated (the ledger's timestamps are unique; the order
    of equal keys must still be the input's: the passes are stable)."""
    lvl = _level(rng, n, np.arange(1, 4), acct_hi=2)
    lvl["ts"] = rng.integers(1, 5, n).astype(np.uint64)
    return lvl


def _all_sentinels(rng, n):
    return _level(rng, n, np.arange(1, 8), live=0)


CASES = {
    "duplicate_accounts": _duplicate_accounts,
    "acct_hi_set": _acct_hi_set,
    "high_halves_only": _high_halves_only,
    "partial_run": _partial_run,
    "ties": _ties,
    "all_sentinels": _all_sentinels,
}


def _device(lvl):
    return {name: jnp.asarray(lvl[name]) for name in index.COLS}


@pytest.mark.parametrize("case", CASES)
def test_sort_level_orders_as_lexsort(case):
    lvl = CASES[case](np.random.default_rng(45), N)
    got = index._sort_level_jit(_device(lvl))
    assert_levels_equal(got, oracle(lvl))
    # Sentinels sort after every real entry.
    live = int((lvl["tid_lo"] != U64M).sum())
    assert (np.asarray(got["tid_lo"])[live:] == U64M).all()
    assert (np.asarray(got["tid_lo"])[:live] != U64M).all()


def _batch_keys(rng, n, make):
    """A batch as a commit program hands it over: both sides' accounts from
    `make` (the debit side's level, the credit side's), one timestamp and
    id a lane, `ok` false on the lanes that wrote nothing."""
    dr, cr = make(rng, n), make(rng, n)
    ok = dr["tid_lo"] != U64M
    ok[rng.integers(0, n, n // 8)] = False      # rejected lanes in between
    keys = {
        "debit_account_id_lo": dr["acct_lo"], "debit_account_id_hi": dr["acct_hi"],
        "credit_account_id_lo": cr["acct_lo"], "credit_account_id_hi": cr["acct_hi"],
        "timestamp": dr["ts"],
    }
    assert set(keys) == set(sm.INDEX_KEY_COLS)
    return keys, dr["tid_lo"], dr["tid_hi"], ok


def _side_level(accounts, ts, id_lo, id_hi, ok, side):
    """One side's level-0 run before it is ordered: the lanes that wrote
    nothing are sentinels."""
    def col(vals):
        return np.where(ok, vals, U64M)

    return {
        "acct_lo": col(accounts[side + "_lo"]),
        "acct_hi": col(accounts[side + "_hi"]),
        "ts": col(ts), "tid_lo": col(id_lo), "tid_hi": col(id_hi),
    }


def _run_oracle(keys, id_lo, id_hi, ok, side):
    return oracle(_side_level(keys, keys["timestamp"], id_lo, id_hi, ok, side))


@pytest.mark.parametrize("case", [c for c in CASES if c != "all_sentinels"])
def test_build_runs_orders_both_sides(case):
    keys, id_lo, id_hi, ok = _batch_keys(
        np.random.default_rng(46), N, CASES[case])
    dr, cr = index.build_runs(
        {name: jnp.asarray(col) for name, col in keys.items()},
        jnp.asarray(id_lo), jnp.asarray(id_hi), jnp.asarray(ok))
    assert_levels_equal(
        dr, _run_oracle(keys, id_lo, id_hi, ok, "debit_account_id"), "dr.")
    assert_levels_equal(
        cr, _run_oracle(keys, id_lo, id_hi, ok, "credit_account_id"), "cr.")


def test_build_runs_picks_a_grouped_dispatchs_row():
    rng = np.random.default_rng(47)
    rows = [_batch_keys(rng, N, _acct_hi_set) for _ in range(3)]
    stacked = jax.tree_util.tree_map(lambda *cols: jnp.stack(cols), *rows)
    for row, (keys, id_lo, id_hi, ok) in enumerate(rows):
        dr, cr = index.build_runs(*stacked, jnp.int32(row))
        assert_levels_equal(
            dr, _run_oracle(keys, id_lo, id_hi, ok, "debit_account_id"))
        assert_levels_equal(
            cr, _run_oracle(keys, id_lo, id_hi, ok, "credit_account_id"))


def _carry(rng, empty_level):
    """A run and levels 0..2 as `append_batch` hands them to `_merge`, each
    ordered already, level `empty_level` all sentinels."""
    makes = [_partial_run, _duplicate_accounts, _acct_hi_set, _high_halves_only]
    levels = []
    for k, make in enumerate(makes):
        n = N << max(0, k - 1)      # the run and level 0: N; then 2N, 4N
        make = _all_sentinels if k - 1 == empty_level else make
        levels.append(oracle(make(rng, n)))
    return levels


@pytest.mark.parametrize("empty_level", [None, 0, 1, 2])
def test_merge_carries_a_run_and_three_levels(empty_level):
    levels = _carry(np.random.default_rng(48), empty_level)
    got = index._merge_jit([_device(lvl) for lvl in levels])
    cat = {name: np.concatenate([lvl[name] for lvl in levels])
           for name in index.COLS}
    assert len(got["ts"]) == 8 * N
    assert_levels_equal(got, oracle(cat))


# -- above level 9: merged by passes, not sorted (index._merge2, index._carry) --


def _cut(whole, lo, hi, rows=None):
    rows = np.arange(len(whole["ts"])) if rows is None else rows
    return oracle({name: col[rows[lo:hi]] for name, col in whole.items()})


@pytest.mark.parametrize("case", [c for c in CASES if c != "ties"])
def test_merge_by_passes_is_the_sort(case):
    """Two ordered levels whose 2n keys differ, as the ledger's do (a
    timestamp is unique; `ties` repeats whole keys with rows that differ,
    which only a stable sort orders): one level of 2n rows dealt out row by
    row."""
    rng = np.random.default_rng(50)
    whole = CASES[case](rng, 2 * N)
    deal = rng.permutation(2 * N)
    a, b = _cut(whole, 0, N, deal), _cut(whole, N, 2 * N, deal)
    got = index._merge2_jit(_device(a), _device(b))
    assert_levels_equal(got, oracle(whole))


@pytest.mark.parametrize("sorted_levels", [1, 2, 3])
def test_a_carry_above_the_sorted_levels_is_the_sort(monkeypatch,
                                                     sorted_levels):
    """A run and levels 0..2, of which the sort takes the run and the
    first `sorted_levels` and the passes merge the rest in: 3 is every
    level, the accepted cells' program alone."""
    monkeypatch.setattr(index, "_SORT_LEVELS", sorted_levels)
    whole = _acct_hi_set(np.random.default_rng(51), 8 * N)
    parts = [_cut(whole, lo, hi) for lo, hi in
             ((0, N), (N, 2 * N), (2 * N, 4 * N), (4 * N, 8 * N))]
    got = index._carry(_device(parts[0]), [_device(p) for p in parts[1:]])
    assert_levels_equal(got, oracle(whole))


def test_the_merge_by_passes_lowers_to_no_sort_and_no_gather():
    level = {name: jax.ShapeDtypeStruct((N,), jnp.uint64)
             for name in index.COLS}
    text = index._merge2_jit.lower(level, level).as_text()
    assert "stablehlo.sort" not in text and "gather" not in text


# -- the pyramids, beside one kept in numpy by the oracle ---------------------


class NumpyPyramid:
    """Bentley-Saxe over the oracle: what a side of `TransferIndex` (or a
    `FieldIndex`) must hold after the same appends."""

    def __init__(self, base):
        self.base, self.levels, self.occupied = base, [], []

    def append(self, run):
        k = 0
        while k < len(self.occupied) and self.occupied[k]:
            k += 1
        while len(self.occupied) <= k:
            n = self.base << len(self.occupied)
            self.levels.append(
                {name: np.full(n, U64M) for name in index.COLS})
            self.occupied.append(False)
        if k == 0:
            self.levels[0] = oracle(run)
        else:
            below = [oracle(run)] + self.levels[:k]
            self.levels[k] = oracle({
                name: np.concatenate([lvl[name] for lvl in below])
                for name in index.COLS})
            for j in range(k):
                self.levels[j] = {
                    name: np.full(self.base << j, U64M) for name in index.COLS}
                self.occupied[j] = False
        self.occupied[k] = True


def _padded(b, res):
    """(rows padded to LANES, the lanes that were written)."""
    ok = np.zeros(LANES, dtype=bool)
    ok[: len(b)] = True
    ok[[i for i, _ in res]] = False
    rows = np.zeros(LANES, dtype=types.TRANSFER_DTYPE)
    rows[: len(b)] = b
    return rows, ok


@pytest.fixture(scope="module")
def seeded():
    """A machine after seven committed batches (carries through level 2),
    one of them with a rejected lane, beside both sides' numpy pyramids and
    a debit-account `FieldIndex` with its own."""
    m = make_machine()
    pyramids = {side: NumpyPyramid(LANES)
                for side in ("debit_account_id", "credit_account_id")}
    field = sb.FieldIndex(
        LANES, "transfers", "debit_account_id_lo", "debit_account_id_hi")
    field.rebuild(m.ledger)     # an empty table: one level of sentinels
    field_pyramid = NumpyPyramid(LANES)
    field_pyramid.levels = [
        {name: np.asarray(lvl[name]) for name in index.COLS}
        for lvl in field.levels]
    field_pyramid.occupied = list(field.occupied)
    rng = np.random.default_rng(49)
    for j in range(7):
        b = batch(1000 * (j + 1), int(rng.integers(9, LANES + 1)))
        if j == 3:
            b["debit_account_id_lo"][4] = 999   # no such account: rejected
        res = m.create_transfers(b, wall_clock_ns=0)
        assert len(res) == (1 if j == 3 else 0)
        rows, ok = _padded(b, res)
        ts = np.zeros(LANES, np.uint64)
        ts[ok] = m.lookup_transfers(
            [int(i) for i in rows["id_lo"][ok]])["timestamp"]

        def run(side):
            return _side_level(rows, ts, rows["id_lo"], rows["id_hi"], ok, side)

        for side, pyramid in pyramids.items():
            pyramid.append(run(side))
        field.append_batch(m.ledger, jnp.asarray(rows["id_lo"]),
                           jnp.asarray(rows["id_hi"]), jnp.asarray(ok))
        field_pyramid.append(run("debit_account_id"))
    return m, pyramids, field, field_pyramid


def test_transfer_index_levels_are_the_oracles(seeded):
    m, pyramids, _field, _fp = seeded
    assert not m.index.stale
    assert m.index.occupied == [True, True, True]
    for side, levels in (("debit_account_id", m.index.dr_levels),
                         ("credit_account_id", m.index.cr_levels)):
        assert m.index.occupied == pyramids[side].occupied
        for k, (got, want) in enumerate(zip(levels, pyramids[side].levels)):
            assert_levels_equal(got, want, f"{side}[{k}].")


def test_transfer_index_above_the_sorted_levels_holds_the_oracles_levels(
        monkeypatch):
    """Eight appends carry into level 3; with the sort stopped at level 1
    the two levels above are merged in by passes."""
    monkeypatch.setattr(index, "_SORT_LEVELS", 1)
    rng = np.random.default_rng(52)
    held = index.TransferIndex(N)
    pyramids = {side: NumpyPyramid(N)
                for side in ("debit_account_id", "credit_account_id")}
    for j in range(8):
        keys, id_lo, id_hi, ok = _batch_keys(rng, N, _acct_hi_set)
        keys["timestamp"] = keys["timestamp"] + np.uint64(j * N)
        held.append_batch(
            {name: jnp.asarray(col) for name, col in keys.items()},
            jnp.asarray(id_lo), jnp.asarray(id_hi), jnp.asarray(ok))
        for side, pyramid in pyramids.items():
            pyramid.append(_side_level(
                keys, keys["timestamp"], id_lo, id_hi, ok, side))
    assert held.occupied == [False, False, False, True]
    for side, levels in (("debit_account_id", held.dr_levels),
                         ("credit_account_id", held.cr_levels)):
        for k, (got, want) in enumerate(zip(levels, pyramids[side].levels)):
            assert_levels_equal(got, want, f"{side}[{k}].")


@pytest.mark.parametrize("descending", [False, True])
@pytest.mark.parametrize("account", [1, 4, N_ACCOUNTS])
def test_transfer_index_answers_as_the_oracles_levels(
        seeded, account, descending):
    """`query_transfers` over the machine's levels and over the numpy
    pyramid's: the same ids in the same order; and they are the account's
    transfers by timestamp."""
    m, pyramids, _field, _fp = seeded
    args = (jnp.uint64(account), jnp.uint64(0), jnp.uint64(1),
            jnp.uint64(index.U64M - 1), jnp.bool_(True), jnp.bool_(True))
    got = m.index.query(m.ledger, *args, k=LANES, descending=descending)
    want = index.query_transfers(
        tuple(_device(lvl) for lvl in pyramids["debit_account_id"].levels),
        tuple(_device(lvl) for lvl in pyramids["credit_account_id"].levels),
        *args, k=LANES, descending=descending)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    valid, tid_lo, _tid_hi = (np.asarray(x) for x in got)
    ts, ids = (
        np.concatenate([
            lvl[name][(lvl["acct_lo"] == account) & (lvl["acct_hi"] == 0)]
            for pyramid in pyramids.values() for lvl in pyramid.levels])
        for name in ("ts", "tid_lo"))
    order = np.argsort(ts, kind="stable")
    order = order[::-1] if descending else order
    assert len(ids) > 0
    assert list(tid_lo[valid]) == list(ids[order][:LANES])


def test_field_index_levels_are_the_oracles(seeded):
    _m, _pyramids, field, field_pyramid = seeded
    assert field.occupied == field_pyramid.occupied
    for k, (got, want) in enumerate(zip(field.levels, field_pyramid.levels)):
        assert_levels_equal(got, want, f"field[{k}].")


@pytest.mark.parametrize("descending", [False, True])
def test_field_index_answers_as_the_oracles_levels(seeded, descending):
    _m, _pyramids, field, field_pyramid = seeded
    args = (jnp.uint64(2), jnp.uint64(0), jnp.uint64(1),
            jnp.uint64(index.U64M - 1))
    got = sb._leaf_window(tuple(field.levels), *args, k=16,
                          descending=descending)
    want = sb._leaf_window(
        tuple(_device(lvl) for lvl in field_pyramid.levels), *args, k=16,
        descending=descending)
    assert (np.asarray(got[0]) != U64M).any()
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# -- the lowering: no permutation gather may come back unseen -----------------


def _shapes(n, lead=()):
    return jax.ShapeDtypeStruct(lead + (n,), jnp.uint64)


def _lowered(program):
    ids = _shapes(LANES)
    level = {name: ids for name in index.COLS}
    if program == "build_runs":
        keys = {name: ids for name in sm.INDEX_KEY_COLS}
        ok = jax.ShapeDtypeStruct((LANES,), jnp.bool_)
        return index.build_runs.lower(keys, ids, ids, ok)
    if program == "merge":          # a run and levels 0 and 1
        double = {name: _shapes(2 * LANES) for name in index.COLS}
        return index._merge_jit.lower([level, level, double])
    assert program == "sort_level"
    return index._sort_level_jit.lower(level)


@pytest.mark.parametrize("program", ["build_runs", "merge", "sort_level"])
def test_the_lowered_program_holds_sorts_and_no_gather(program):
    text = _lowered(program).as_text()
    assert "stablehlo.sort" in text
    assert "gather" not in text, (
        f"{program}: a gather is back in the index's sort (on a v5e one "
        "costs seven times a whole five-operand sort of its rows)")
