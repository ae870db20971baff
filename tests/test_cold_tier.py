"""Tiered transfers store (round-2 VERDICT #6, BASELINE config 4): hot
device window + cold host spill, exact semantics across the boundary."""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.ops import cold as cold_mod
from tigerbeetle_tpu.testing import model as M

CFG = LedgerConfig(
    accounts_capacity_log2=8, transfers_capacity_log2=8,
    posted_capacity_log2=8,
)


def make_pair(tmp_path, hot_max=256):
    dev = TpuStateMachine(
        CFG, batch_lanes=64, spill_dir=str(tmp_path / "cold"),
        hot_transfers_capacity_max=hot_max,
    )
    ref = M.ReferenceStateMachine()
    accounts = types.accounts_array(
        [types.account(id=i + 1, ledger=1, code=10) for i in range(8)]
    )
    assert dev.create_accounts(accounts, 1) == ref.create_accounts(
        [M.account_from_row(r) for r in accounts], 1
    )
    return dev, ref


def run_batch(dev, ref, specs):
    batch = types.transfers_array([types.transfer(**s) for s in specs])
    got = dev.create_transfers(batch)
    want = ref.create_transfers([M.transfer_from_row(r) for r in batch])
    assert got == want, f"codes diverge: {got[:6]} vs {want[:6]}"
    assert dev.balances_snapshot() == ref.balances_snapshot()
    return got


class TestBloomParity:
    def test_host_add_device_check_no_false_negatives(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        ids_lo = rng.integers(1, 1 << 63, size=500, dtype=np.uint64)
        ids_hi = rng.integers(0, 1 << 63, size=500, dtype=np.uint64)
        bloom = np.zeros(((1 << 16) // 32,), np.uint32)
        cold_mod.bloom_add_host(bloom, ids_lo, ids_hi)
        hits = np.asarray(cold_mod.bloom_check(
            jnp.asarray(bloom), jnp.asarray(ids_lo), jnp.asarray(ids_hi)
        ))
        assert hits.all(), "false negative: host add / device check diverge"
        # And absent ids mostly miss (FP rate sanity).
        other_lo = rng.integers(1 << 63, None, size=2000, dtype=np.uint64)
        other_hi = np.zeros(2000, np.uint64)
        fp = np.asarray(cold_mod.bloom_check(
            jnp.asarray(bloom), jnp.asarray(other_lo), jnp.asarray(other_hi)
        )).mean()
        assert fp < 0.05, f"implausible FP rate {fp}"


class TestDeterministicReservation:
    """Two replicas executing the identical committed history must
    materialize IDENTICAL cold-tier layouts: same run filenames (sequence
    numbers), same manifests (row counts + AEGIS checksums), byte-identical
    file contents.  This is the TPU design's FreeSet analogue
    (lsm/free_set.zig deterministic block reservation): derived storage
    placement is a pure function of the replicated op stream, never of
    local timing."""

    def _drive(self, tmp_path, name):
        dev = TpuStateMachine(
            CFG, batch_lanes=64, spill_dir=str(tmp_path / name),
            hot_transfers_capacity_max=256,
        )
        accounts = types.accounts_array(
            [types.account(id=i + 1, ledger=1, code=10) for i in range(8)]
        )
        assert dev.create_accounts(accounts, 1) == []
        tid = 1000
        while tid < 1500:
            batch = types.transfers_array([
                types.transfer(
                    id=tid + i, debit_account_id=1 + (tid + i) % 8,
                    credit_account_id=1 + (tid + i + 3) % 8,
                    amount=1 + i % 9, ledger=1, code=10,
                )
                for i in range(50)
            ])
            assert dev.create_transfers(batch) == []
            tid += 50
        return dev

    def test_identical_history_identical_spill(self, tmp_path):
        a = self._drive(tmp_path, "a")
        b = self._drive(tmp_path, "b")
        assert a.cold.count > 0, "eviction never fired; test is vacuous"
        ma, mb = a.cold.manifest(), b.cold.manifest()
        assert ma == mb, f"manifests diverge: {ma} vs {mb}"
        for ent in ma:
            fa = tmp_path / "a" / ent["path"]
            fb = tmp_path / "b" / ent["path"]
            assert fa.read_bytes() == fb.read_bytes(), ent["path"]


class TestEvictionExactness:
    def _fill(self, dev, ref, n, start_id):
        tid = start_id
        while tid < start_id + n:
            m = min(50, start_id + n - tid)
            run_batch(dev, ref, [
                dict(id=tid + i, debit_account_id=1 + (tid + i) % 8,
                     credit_account_id=1 + (tid + i + 3) % 8,
                     amount=1 + i, ledger=1, code=10)
                for i in range(m)
            ])
            tid += m
        return tid

    def test_spill_and_cold_duplicates(self, tmp_path):
        dev, ref = make_pair(tmp_path)
        # Fill well past the hot ceiling: forces evictions along the way.
        self._fill(dev, ref, 400, 1000)
        assert dev.cold.count > 0, "nothing was evicted"
        # A duplicate of a COLD id must hit the exact exists precedence.
        cold_ids = [
            (int(r["id_lo"]), int(r["id_hi"]))
            for r in np.asarray(dev.cold.runs[0][:3])
        ]
        for lo, hi in cold_ids:
            orig = ref.transfers[lo | (hi << 64)]
            run_batch(dev, ref, [dict(
                id=lo | (hi << 64),
                debit_account_id=orig.debit_account_id,
                credit_account_id=orig.credit_account_id,
                amount=orig.amount, ledger=1, code=10,
            )])  # -> exists (46)
            run_batch(dev, ref, [dict(
                id=lo | (hi << 64),
                debit_account_id=orig.debit_account_id,
                credit_account_id=orig.credit_account_id,
                amount=orig.amount + 1, ledger=1, code=10,
            )])  # -> exists_with_different_amount (39)

    def test_cold_pending_post(self, tmp_path):
        dev, ref = make_pair(tmp_path)
        # A pending created early, then enough plain volume to evict it.
        run_batch(dev, ref, [dict(
            id=500, debit_account_id=1, credit_account_id=2, amount=77,
            ledger=1, code=10, flags=types.TransferFlags.PENDING,
        )])
        self._fill(dev, ref, 400, 10_000)
        assert dev.cold.lookup(500, 0) is not None, "pending not evicted"
        # Posting the now-cold pending must rehydrate and succeed exactly.
        run_batch(dev, ref, [dict(
            id=501, pending_id=500, ledger=1, code=10,
            flags=types.TransferFlags.POST_PENDING_TRANSFER,
        )])

    def test_cold_lookup_and_query(self, tmp_path):
        dev, ref = make_pair(tmp_path)
        end = self._fill(dev, ref, 400, 20_000)
        assert dev.cold.count > 0
        # lookup_transfers across hot+cold.
        sample = [20_000, 20_001, end - 1, 999_999]
        got = dev.lookup_transfers(sample)
        want = ref.lookup_transfers(sample)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert int(g["id_lo"]) == w.id and int(g["amount_lo"]) == w.amount
        # get_account_transfers spanning the eviction boundary.
        f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
        f["account_id_lo"] = 1
        f["limit"] = 8000
        f["flags"] = 3
        got_rows = dev.get_account_transfers(f)
        want_rows = ref.get_account_transfers(1, 0, 0, 8000, 3)
        assert [int(r["id_lo"]) for r in got_rows] == [t.id for t in want_rows]

    def test_restart_reload(self, tmp_path):
        dev, ref = make_pair(tmp_path)
        self._fill(dev, ref, 400, 30_000)
        assert dev.cold.count > 0
        state = dev.host_state()
        ledger = dev.ledger

        dev2 = TpuStateMachine(
            CFG, batch_lanes=64, spill_dir=str(tmp_path / "cold"),
            hot_transfers_capacity_max=256,
        )
        dev2.ledger = ledger
        dev2.restore_host_state(state)
        assert dev2.cold.count == dev.cold.count
        # Cold duplicate still detected exactly after reload.
        lo, hi = int(np.asarray(dev.cold.runs[0][0])["id_lo"]), 0
        orig = ref.transfers[lo]
        batch = types.transfers_array([types.transfer(
            id=lo, debit_account_id=orig.debit_account_id,
            credit_account_id=orig.credit_account_id, amount=orig.amount,
            ledger=1, code=10,
        )])
        got = dev2.create_transfers(batch)
        want = ref.create_transfers([M.transfer_from_row(r) for r in batch])
        assert got == want
        assert got == [(0, int(types.CreateTransferResult.exists))]

    @pytest.mark.slow  # tier-1 budget: runs whole in the ci integration tier
    def test_restart_query_includes_cold(self, tmp_path):
        """After a restart the rebuilt index must cover the cold tier too:
        get_account_transfers would otherwise silently drop every evicted
        transfer (the rebuild scans only the hot table)."""
        dev, ref = make_pair(tmp_path)
        self._fill(dev, ref, 400, 40_000)
        assert dev.cold.count > 0
        dev2 = TpuStateMachine(
            CFG, batch_lanes=64, spill_dir=str(tmp_path / "cold"),
            hot_transfers_capacity_max=256,
        )
        dev2.ledger = dev.ledger
        dev2.restore_host_state(dev.host_state())
        f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
        f["account_id_lo"] = 1
        f["limit"] = 8000
        f["flags"] = 3
        got_rows = dev2.get_account_transfers(f)
        want_rows = ref.get_account_transfers(1, 0, 0, 8000, 3)
        assert [int(r["id_lo"]) for r in got_rows] == [t.id for t in want_rows]

    def test_restart_without_cap_reads_cold_manifest(self, tmp_path):
        """A restart that omits the hot-cap flag must still reload a
        checkpoint whose cold_manifest references the spill directory."""
        dev, ref = make_pair(tmp_path)
        self._fill(dev, ref, 400, 50_000)
        assert dev.cold.count > 0
        dev2 = TpuStateMachine(
            CFG, batch_lanes=64, spill_dir=str(tmp_path / "cold"),
        )
        dev2.ledger = dev.ledger
        dev2.restore_host_state(dev.host_state())
        assert dev2.cold.count == dev.cold.count
        sample = [50_000, 50_001]
        got = dev2.lookup_transfers(sample)
        want = ref.lookup_transfers(sample)
        assert len(got) == len(want) == 2

    def test_run_names_never_reused(self, tmp_path):
        """Run file sequence numbers are monotonic across merges and
        reloads — a reused name would overwrite bytes an older checkpoint
        still references."""
        store = cold_mod.ColdStore(str(tmp_path / "c"))
        rows = types.transfers_array([
            types.transfer(id=i + 1, debit_account_id=1, credit_account_id=2,
                           amount=1, ledger=1, code=10)
            for i in range(4)
        ])
        seen = set()
        for k in range(store.MAX_RUNS * 3):
            rows["id_lo"] = np.arange(4, dtype=np.uint64) + 1 + 10 * k
            store.append_run(rows.copy())
            seen.update(store.run_paths)
            seen.update(store.garbage)
        # next_seq counts every file ever written (appends + merges); a
        # reused name would collapse two writes onto one path and make
        # the distinct-path count fall short.
        assert len(seen) == store.next_seq
        assert not (set(store.run_paths) & set(store.garbage))
        # A fresh store over the same directory continues the sequence.
        store2 = cold_mod.ColdStore(str(tmp_path / "c"))
        assert store2.next_seq == store.next_seq


@pytest.mark.parametrize("write_lanes", [64, 1 << 19])
def test_the_rehash_lays_the_table_out_as_one_claim_over_the_slots(
        monkeypatch, write_lanes):
    """drop_evicted (the kept rows compacted, one sort-free claim, written a
    chunk at a time) against the form it replaced: ONE claim_slots over all
    the slots' lanes and one write.  Slot for slot, column for column, with
    the write in several trips and in one."""
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.ops import hash_table as ht

    monkeypatch.setattr(cold_mod, "_WRITE_LANES", write_lanes)
    rng = np.random.default_rng(48)
    capacity, rows = 1 << 10, 500
    dtypes = {"timestamp": jnp.uint64, "amount_lo": jnp.uint64,
              "code": jnp.uint32}
    table = ht.make_table(capacity, dtypes)
    for start in range(0, rows, 100):     # five batches: real probe chains
        lo = jnp.asarray(rng.integers(1, 1 << 62, 100).astype(np.uint64))
        hi = jnp.asarray(rng.integers(0, 3, 100).astype(np.uint64))
        at = jnp.arange(start, start + 100, dtype=jnp.uint64)
        table, _ = ht.insert(
            table, lo, hi, jnp.ones(100, jnp.bool_),
            {"timestamp": at + jnp.uint64(1), "amount_lo": lo ^ hi,
             "code": at.astype(jnp.uint32)}, capacity)
    threshold = jnp.uint64(rows // 2)
    keep = cold_mod._live(table) & (table.cols["timestamp"] > threshold)
    fresh = ht.make_table(capacity, dtypes)
    claimed, _ = ht.claim_slots(
        fresh, table.key_lo, table.key_hi, keep, capacity)
    want = ht.write_rows(
        fresh, table.key_lo, table.key_hi, claimed, keep, table.cols)
    got = jax.jit(
        cold_mod.drop_evicted.__wrapped__, static_argnames=("k",)
    )(table, threshold, k=256)
    assert int(got.count) == int(want.count) == rows - rows // 2
    for name in ("key_lo", "key_hi", "tombstone"):
        assert np.array_equal(np.asarray(getattr(got, name)),
                              np.asarray(getattr(want, name))), name
    for name in dtypes:
        assert np.array_equal(np.asarray(got.cols[name]),
                              np.asarray(want.cols[name])), name
