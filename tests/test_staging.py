"""One staging for every commit route (`ops/staging.py`; PERF.md PR 46).

A request reaches its commit program as three packed buffers in ONE
`device_put` (`stage_batch`), a grouped run as one stack of them with a
leading dimension that goes by the run's length (`stage_group`).  Held
here, on the one-chip routes (tests/test_sharded_staging.py holds the
mesh's): what the packed operands un-stage to; one put a staging on every
route and the bytes a group uploads (`stage.puts`, `stage.bytes`); no
compile after `warmup()` whatever the run's length; a grouped run's results
against the same batches one by one; and that the general program hands the
index what the host used to build for it."""

import jax
import numpy as np
import pytest

from tigerbeetle_tpu import jaxenv, machine, types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import txtrace
from tigerbeetle_tpu.ops import staging
from tigerbeetle_tpu.ops import state_machine as sm
from tigerbeetle_tpu.ops import transfer_full as tf

FULL_LANES = 8192          # the serving width: the staging alone runs at it
LANES = 64                 # the machines of this file
N_ACCOUNTS = 16
GROUP_K = TpuStateMachine.GROUP_K
SHORT = TpuStateMachine.GROUP_ROWS_SHORT
PENDING = types.TransferFlags.PENDING
# A staged row: 14 uint64 and 5 uint32 columns; its (count, timestamp).
ROW_BYTES = (14 * 8 + 5 * 4) * LANES
META_BYTES = 2 * 8


def _random_batch(dtype, n, seed):
    rng = np.random.default_rng(seed)
    batch = np.zeros(n, dtype=dtype)
    for name in dtype.names:   # every field non-zero in every lane
        info = np.iinfo(dtype.fields[name][0])
        batch[name] = rng.integers(1, info.max, n, dtype=info.dtype)
    return batch


def _assert_unstages_to_soa(columns, batch, lanes):
    """``columns`` are exactly `types.to_soa` of the zero-padded batch."""
    padded = np.zeros(lanes, dtype=batch.dtype)
    padded[:len(batch)] = batch
    want = types.to_soa(padded)
    assert set(columns) == set(want) and len(columns) == 19
    for name, column in columns.items():
        got = np.asarray(column)
        assert got.dtype == want[name].dtype and got.shape == (lanes,), name
        assert np.array_equal(got, want[name]), name


@pytest.mark.parametrize("n", [1, 8189, 8190])
@pytest.mark.parametrize("dtype", [types.ACCOUNT_DTYPE, types.TRANSFER_DTYPE],
                         ids=["accounts", "transfers"])
def test_a_staged_batch_unstages_to_the_padded_soa(dtype, n):
    batch = _random_batch(dtype, n, seed=n)
    timestamp = 7_000_000_000_000 + n
    staged = staging.stage_batch(batch, FULL_LANES, timestamp)
    cols64, cols32, meta = staged
    assert cols64.shape == (14, FULL_LANES) and cols64.dtype == np.uint64
    assert cols32.shape == (5, FULL_LANES) and cols32.dtype == np.uint32
    assert meta.shape == (2,) and meta.dtype == np.uint64
    columns, count, stamp = staging.unstage(dtype, *staged)
    assert (int(count), int(stamp)) == (n, timestamp)
    _assert_unstages_to_soa(columns, batch, FULL_LANES)


@pytest.mark.parametrize("n", [1, 8189, 8190])
def test_a_staged_group_unstages_row_by_row(n):
    dtype = types.TRANSFER_DTYPE
    batches = [_random_batch(dtype, c, seed=c + j)
               for j, c in enumerate((n, 1, n))]
    stamps = [10_000, 20_000, 30_000]
    cols64, cols32, meta = staging.stage_group(
        batches, FULL_LANES, stamps, SHORT)
    assert cols64.shape == (SHORT, 14, FULL_LANES)
    assert cols32.shape == (SHORT, 5, FULL_LANES)
    assert meta.shape == (2, SHORT) and meta.dtype == np.uint64
    meta = np.asarray(meta)
    # The loop stops at the first zero count; the timestamps past the run
    # repeat the last one (the rows' keys are computed and never read).
    assert meta[0].tolist() == [n, 1, n] + [0] * (SHORT - 3)
    assert meta[1].tolist() == stamps + [30_000] * (SHORT - 3)
    for j, batch in enumerate(batches):
        columns, count, stamp = staging.unstage(
            dtype, cols64[j], cols32[j], meta[:, j])
        assert (int(count), int(stamp)) == (len(batch), stamps[j])
        _assert_unstages_to_soa(columns, batch, FULL_LANES)
    assert not np.asarray(cols64[3:]).any()
    assert not np.asarray(cols32[3:]).any()


# -- the routes of a one-chip machine -----------------------------------------

def _machine(**kwargs):
    m = TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=9, transfers_capacity_log2=12,
                     posted_capacity_log2=8, **kwargs),
        batch_lanes=LANES)
    assert m.pipeline_depth == 2 and not m.shards
    return m


def _accounts():
    return types.accounts_array([
        types.account(id=i + 1, ledger=1, code=10) for i in range(N_ACCOUNTS)
    ])


def _transfers(first_id, n, flags=0):
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 3) % N_ACCOUNTS, amount=3 + i % 5,
            ledger=1, code=10, flags=flags,
        )
        for i in range(n)
    ])


def _run(first_id, k):
    """A run of ``k`` batches whose codes are not all OK: batch j's lane 2
    debits an account that does not exist, its lane 4 repeats lane 3's id
    with another amount."""
    batches = []
    for j in range(k):
        batch = _transfers(first_id + 100 * j, 9 + j % 7)
        batch["debit_account_id_lo"][2] = 999
        batch["id_lo"][4] = batch["id_lo"][3]
        batches.append(batch)
    return batches


def _group(m, first_id, k):
    batches = [_transfers(first_id + 100 * j, 7 + j % 5) for j in range(k)]
    stamps = [m.prepare("create_transfers", len(b), 0) for b in batches]
    assert m.commit_group_fast(batches, stamps) == [[]] * k


def _lone(m, first_id):
    batch = _transfers(first_id, 10)
    handle = m.commit_fast_deferred(
        batch, m.prepare("create_transfers", len(batch), 0))
    assert handle.resolve() == [[]]


def _sequential(m, first_id):
    m.force_sequential = True
    try:
        assert m.create_transfers(_transfers(first_id, 10)) == []
    finally:
        m.force_sequential = False


def _blocking(m, first_id, flags=0):
    """One blocking request: the fast kernel's, or with PENDING the general
    one's."""
    assert m.create_transfers(_transfers(first_id, 10, flags)) == []


ROUTES = {
    "lone": (_lone, 1),
    "general": (lambda m, i: _blocking(m, i, PENDING), 1),
    "blocking_fast": (_blocking, 1),
    "sequential": (_sequential, 1),
    "accounts": (lambda m, i: m.create_accounts(types.accounts_array([
        types.account(id=i + j, ledger=1, code=10) for j in range(5)
    ])), 1),
    **{f"grouped_{k}": (lambda m, i, k=k: _group(m, i, k), rows)
       for k, rows in ((2, SHORT), (7, SHORT), (8, SHORT), (9, GROUP_K),
                       (GROUP_K, GROUP_K))},
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_one_put_a_staging_and_the_rows_it_holds(route):
    """Every route makes ONE `device_put` a dispatch, inside a `stage_h2d`
    span; a request uploads one row, a grouped run 8 rows up to a length
    of 8 and GROUP_K rows beyond."""
    serve, rows = ROUTES[route]
    m = _machine()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    with registry.enabled_scope(), txtrace.attribution_scope():
        serve(m, 10_000)
        counters = registry.snapshot()["counters"]
        totals = txtrace.stage_totals()
    assert counters["stage.puts"] == 1
    assert counters["stage.bytes"] == rows * (ROW_BYTES + META_BYTES)
    assert totals["stage_h2d"]["count"] == 1
    assert counters.get("sharding.staged", 0) == 0


def test_a_group_of_7_uploads_8_rows_and_a_group_of_9_group_k():
    m = _machine()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    uploaded = {}
    for k in (7, 9):
        with registry.enabled_scope():
            _group(m, 10_000 * k, k)
            uploaded[k] = registry.snapshot()["counters"]["stage.bytes"]
    assert uploaded[7] == 8 * (ROW_BYTES + META_BYTES)
    assert uploaded[9] == GROUP_K * (ROW_BYTES + META_BYTES)
    assert GROUP_K == 32 and SHORT == 8


def test_no_run_length_compiles_after_warmup():
    """Two executables of the loop program, both warmed: a run of 2..9 (and
    GROUP_K) finds its own compiled, as do the lone and the general
    request.  (The index is lazy here: a level's first merge compiles by
    design, once a level.)"""
    assert jaxenv.instrument_compiles()
    m = _machine(lazy_index=True)
    m.warmup()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    programs = {
        "group": machine._group_fast_dispatch,
        "lone": sm.create_transfers_fast_probed.jitted,
        "general": tf.create_transfers_full,
        "accounts": sm.create_accounts.jitted,
    }
    warmed = {name: p._cache_size() for name, p in programs.items()}
    with registry.enabled_scope():
        before = registry.snapshot()["counters"].get("jit.compiles", 0)
        for k in list(range(2, 10)) + [GROUP_K]:
            _group(m, 1_000 * k, k)
        _lone(m, 50_000)
        _blocking(m, 60_000, PENDING)
        assert m.create_accounts(types.accounts_array(
            [types.account(id=900, ledger=1, code=10)])) == []
        after = registry.snapshot()["counters"].get("jit.compiles", 0)
    assert {n: p._cache_size() for n, p in programs.items()} == warmed
    assert after - before == 0


@pytest.mark.parametrize("k", [7, 9])
def test_a_grouped_run_equals_its_batches_one_by_one(k):
    grouped, serial = _machine(), _machine()
    for m in (grouped, serial):
        assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    batches = _run(10_000, k)
    stamps = [grouped.prepare("create_transfers", len(b), 0) for b in batches]
    got = grouped.commit_group_fast(batches, stamps)
    want = []
    for b in batches:
        ts = serial.prepare("create_transfers", len(b), 0)
        want.append(serial.commit_batch("create_transfers", b, ts))
    assert got == want and all(len(r) == 2 for r in got)
    assert grouped.digest() == serial.digest()
    assert grouped.commit_timestamp == serial.commit_timestamp


@pytest.mark.parametrize("use_waves", [False, True], ids=["plain", "waves"])
def test_the_general_program_hands_the_index_its_ids_and_written_lanes(
    use_waves,
):
    """What `_index_append` built on the host until PR 46: the id columns
    of the padded batch and the mask (code 0, inside the batch)."""
    m = _machine()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    batch = _transfers(10_000, 20, PENDING)
    batch["debit_account_id_lo"][2] = 999           # refused: no such account
    batch["id_lo"][4] = batch["id_lo"][3]           # refused: exists, differs
    staged = staging.stage_batch(batch, LANES, 5_000)
    r = tf.create_transfers_full(
        m.ledger, *staged, None, None, max_passes=8, has_postvoid=False,
        has_history=False, use_waves=use_waves)
    m.ledger = r[0]
    assert len(r) == (8 if use_waves else 7) and int(r[2]) == 0
    codes = np.asarray(r[1])
    id_lo, id_hi, keys, written = r[-4:]
    padded = np.zeros(LANES, dtype=batch.dtype)
    padded[:len(batch)] = batch
    assert np.array_equal(np.asarray(id_lo), padded["id_lo"])
    assert np.array_equal(np.asarray(id_hi), padded["id_hi"])
    ok = np.zeros(LANES, dtype=bool)
    ok[:len(batch)] = codes[:len(batch)] == 0
    assert np.array_equal(np.asarray(written), ok)
    assert ok.sum() == len(batch) - 2 and not ok[2] and not ok[4]
    assert set(keys) == set(sm.INDEX_KEY_COLS)
    assert all(isinstance(x, jax.Array) for x in (id_lo, id_hi, written))
