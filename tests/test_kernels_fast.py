"""Differential tests: vectorized device kernels vs the scalar oracle."""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.testing import model as M
from tigerbeetle_tpu.testing.workload import WorkloadGen
from tigerbeetle_tpu.types import AccountFlags, TransferFlags as F


def make_pair(batch_lanes=256):
    cfg = LedgerConfig(
        accounts_capacity_log2=12,
        transfers_capacity_log2=13,
        posted_capacity_log2=10,
        max_probe=1 << 10,
    )
    return TpuStateMachine(cfg, batch_lanes=batch_lanes), M.ReferenceStateMachine()


def run_accounts(dev, ref, batch, wall=0):
    got = dev.create_accounts(batch, wall_clock_ns=wall)
    want = ref.execute(
        "create_accounts",
        ref.prepare("create_accounts", len(batch), wall),
        [M.account_from_row(r) for r in batch],
    )
    assert got == want, f"accounts results differ: {got} vs {want}"


def run_transfers(dev, ref, batch, wall=0):
    got = dev.create_transfers(batch, wall_clock_ns=wall)
    want = ref.execute(
        "create_transfers",
        ref.prepare("create_transfers", len(batch), wall),
        [M.transfer_from_row(r) for r in batch],
    )
    assert got == want, f"transfer results differ: {got} vs {want}"


def check_parity(dev, ref):
    assert dev.balances_snapshot() == ref.balances_snapshot()


def seed_accounts(dev, ref, n=8, ledger=1):
    batch = types.accounts_array(
        [types.account(id=i + 1, ledger=ledger, code=10) for i in range(n)]
    )
    run_accounts(dev, ref, batch, wall=1000)
    return list(range(1, n + 1))


class TestCreateAccountsKernel:
    def test_basic_and_validation(self):
        dev, ref = make_pair()
        rows = [
            types.account(id=1, ledger=1, code=1),
            types.account(id=0, ledger=1, code=1),
            types.account(id=(1 << 128) - 1, ledger=1, code=1),
            types.account(id=2, ledger=0, code=1),
            types.account(id=3, ledger=1, code=0),
            types.account(id=4, ledger=1, code=1, debits_posted=5),
            types.account(id=5, ledger=1, code=1, reserved=9),
            types.account(id=6, ledger=1, code=1, flags=0x8000),
            types.account(id=7, ledger=1, code=1, timestamp=4),
            types.account(id=8, ledger=1, code=1),
        ]
        run_accounts(dev, ref, types.accounts_array(rows), wall=500)
        check_parity(dev, ref)

    def test_exists_ladder_across_batches(self):
        dev, ref = make_pair()
        run_accounts(
            dev, ref,
            types.accounts_array([types.account(id=1, ledger=1, code=1, user_data_32=9)]),
            wall=100,
        )
        rows = [
            types.account(id=1, ledger=1, code=1, user_data_32=9),  # exists
            types.account(id=1, ledger=2, code=1, user_data_32=9),
            types.account(id=1, ledger=1, code=3, user_data_32=9),
            types.account(id=1, ledger=1, code=1, user_data_32=8),
            types.account(id=1, ledger=1, code=1, user_data_32=9, user_data_64=5),
            types.account(id=1, ledger=1, code=1, user_data_32=9, user_data_128=5),
            types.account(id=1, ledger=1, code=1, user_data_32=9, flags=AccountFlags.HISTORY),
        ]
        run_accounts(dev, ref, types.accounts_array(rows))
        check_parity(dev, ref)

    def test_intra_batch_duplicates(self):
        dev, ref = make_pair()
        rows = [
            types.account(id=5, ledger=0, code=1),  # invalid: not the winner
            types.account(id=5, ledger=1, code=1),  # winner
            types.account(id=5, ledger=1, code=1),  # exists
            types.account(id=5, ledger=1, code=2),  # exists_with_different_code
        ]
        run_accounts(dev, ref, types.accounts_array(rows), wall=50)
        check_parity(dev, ref)

    def test_linked_chains(self):
        dev, ref = make_pair()
        L = int(AccountFlags.LINKED)
        rows = [
            types.account(id=1, ledger=1, code=1, flags=L),
            types.account(id=2, ledger=0, code=1, flags=L),  # breaks chain
            types.account(id=3, ledger=1, code=1),
            types.account(id=4, ledger=1, code=1, flags=L),
            types.account(id=5, ledger=1, code=1),  # chain 2 commits
            types.account(id=6, ledger=1, code=1, flags=L),  # chain open at end
        ]
        run_accounts(dev, ref, types.accounts_array(rows), wall=60)
        check_parity(dev, ref)

    def test_random_differential(self):
        dev, ref = make_pair()
        gen = WorkloadGen(seed=42)
        for i in range(4):
            batch = gen.accounts_batch(40)
            # Inject duplicates/invalids by mutating some rows.
            rng = np.random.default_rng(100 + i)
            for j in rng.integers(0, 40, size=6):
                k = rng.integers(0, 3)
                if k == 0:
                    batch[j]["id_lo"] = batch[(j + 1) % 40]["id_lo"]
                    batch[j]["id_hi"] = batch[(j + 1) % 40]["id_hi"]
                elif k == 1:
                    batch[j]["ledger"] = 0
                else:
                    batch[j]["code"] = 0
            run_accounts(dev, ref, batch, wall=1000 * (i + 1))
        check_parity(dev, ref)


class TestCreateTransfersKernel:
    def test_basic_and_validation(self):
        dev, ref = make_pair()
        seed_accounts(dev, ref)
        rows = [
            types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=100,
                           ledger=1, code=10),
            types.transfer(id=0, debit_account_id=1, credit_account_id=2, amount=1,
                           ledger=1, code=10),
            types.transfer(id=2, debit_account_id=1, credit_account_id=1, amount=1,
                           ledger=1, code=10),
            types.transfer(id=3, debit_account_id=99, credit_account_id=2, amount=1,
                           ledger=1, code=10),
            types.transfer(id=4, debit_account_id=1, credit_account_id=99, amount=1,
                           ledger=1, code=10),
            types.transfer(id=5, debit_account_id=1, credit_account_id=2, amount=0,
                           ledger=1, code=10),
            types.transfer(id=6, debit_account_id=1, credit_account_id=2, amount=1,
                           ledger=9, code=10),
            types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=1,
                           ledger=1, code=0),
            types.transfer(id=8, debit_account_id=1, credit_account_id=2, amount=1,
                           ledger=1, code=10, timeout=5),
            types.transfer(id=9, debit_account_id=1, credit_account_id=2, amount=1,
                           ledger=1, code=10, pending_id=3),
            types.transfer(id=10, debit_account_id=3, credit_account_id=4,
                           amount=(1 << 64) - 1, ledger=1, code=10),
        ]
        run_transfers(dev, ref, types.transfers_array(rows))
        check_parity(dev, ref)

    def test_pending_and_exists(self):
        dev, ref = make_pair()
        seed_accounts(dev, ref)
        t1 = types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=50,
                            ledger=1, code=10, flags=F.PENDING, timeout=100)
        run_transfers(dev, ref, types.transfers_array([t1]))
        # Same id again: exists; modified: exists_with_different_*.
        rows = [
            t1,
            types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=50,
                           ledger=1, code=10, flags=F.PENDING, timeout=101),
            types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=51,
                           ledger=1, code=10, flags=F.PENDING, timeout=100),
            types.transfer(id=1, debit_account_id=1, credit_account_id=3, amount=50,
                           ledger=1, code=10, flags=F.PENDING, timeout=100),
            types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=50,
                           ledger=1, code=10, timeout=0),
        ]
        run_transfers(dev, ref, types.transfers_array(rows))
        check_parity(dev, ref)

    def test_intra_batch_duplicates(self):
        dev, ref = make_pair()
        seed_accounts(dev, ref)
        rows = [
            types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=0,
                           ledger=1, code=10),  # amount_must_not_be_zero
            types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=5,
                           ledger=1, code=10),  # winner
            types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=5,
                           ledger=1, code=10),  # exists
            types.transfer(id=7, debit_account_id=2, credit_account_id=1, amount=5,
                           ledger=1, code=10),  # exists_with_different_debit_account_id
            types.transfer(id=7, debit_account_id=1, credit_account_id=2, amount=6,
                           ledger=0, code=10),  # own failure: ledger_must_not_be_zero
        ]
        run_transfers(dev, ref, types.transfers_array(rows))
        check_parity(dev, ref)

    def test_linked_chains_rollback(self):
        dev, ref = make_pair()
        seed_accounts(dev, ref)
        L = int(F.LINKED)
        rows = [
            types.transfer(id=1, debit_account_id=1, credit_account_id=2, amount=10,
                           ledger=1, code=10, flags=L),
            types.transfer(id=2, debit_account_id=3, credit_account_id=4, amount=10,
                           ledger=1, code=10, flags=L),
            types.transfer(id=3, debit_account_id=1, credit_account_id=99, amount=10,
                           ledger=1, code=10),  # breaks: chain 1-3 rolls back
            types.transfer(id=4, debit_account_id=1, credit_account_id=2, amount=7,
                           ledger=1, code=10, flags=L),
            types.transfer(id=5, debit_account_id=2, credit_account_id=3, amount=7,
                           ledger=1, code=10),  # chain 4-5 commits
            types.transfer(id=6, debit_account_id=1, credit_account_id=2, amount=1,
                           ledger=1, code=10, flags=L),  # chain open
        ]
        run_transfers(dev, ref, types.transfers_array(rows))
        check_parity(dev, ref)

    def test_balances_same_account_many_times(self):
        dev, ref = make_pair()
        seed_accounts(dev, ref, n=3)
        rows = [
            types.transfer(id=10 + i, debit_account_id=1 + (i % 2),
                           credit_account_id=3, amount=1 << i, ledger=1, code=10)
            for i in range(20)
        ]
        run_transfers(dev, ref, types.transfers_array(rows))
        check_parity(dev, ref)

    def test_random_differential_multi_batch(self):
        dev, ref = make_pair()
        gen = WorkloadGen(seed=7)
        run_accounts(dev, ref, gen.accounts_batch(16), wall=1000)
        for i in range(6):
            batch = gen.transfers_batch(
                60, invalid_rate=0.25, dup_rate=0.15, pending_rate=0.25
            )
            run_transfers(dev, ref, batch, wall=2000 * (i + 1))
            assert dev.balances_snapshot() == ref.balances_snapshot(), f"batch {i}"
        # Cross-check lookups too.
        ids = gen.transfer_ids[:50]
        got = dev.lookup_transfers(ids)
        want = ref.lookup_transfers(ids)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert M.transfer_from_row(g) == w

    def test_random_differential_linked(self):
        dev, ref = make_pair()
        gen = WorkloadGen(seed=13)
        run_accounts(dev, ref, gen.accounts_batch(10), wall=500)
        for i in range(4):
            batch = gen.transfers_batch(
                40, invalid_rate=0.25, dup_rate=0.0, pending_rate=0.2,
                linked_rate=0.3,
            )
            run_transfers(dev, ref, batch, wall=7000 * (i + 1))
            assert dev.balances_snapshot() == ref.balances_snapshot(), f"batch {i}"


@pytest.mark.parametrize("lanes,amounts", [
    (64, "max"), (64, "random"), (8192, "max"), (8192, "edges"),
])
def test_balance_plan_sums_16_bit_limbs_exactly(lanes, amounts):
    """balance_plan's per-account u128 deltas against Python integers: every
    leg on a handful of accounts, amounts that fill all four 16-bit limbs
    (2^64-1 on every lane: 16,384 terms a limb), masked lanes."""
    import jax.numpy as jnp

    from tigerbeetle_tpu.ops import state_machine as sm

    rng = np.random.default_rng(lanes)
    if amounts == "max":
        amt = np.full(lanes, (1 << 64) - 1, np.uint64)
    elif amounts == "edges":
        amt = rng.choice(np.array(
            [0xFFFF, 0x10000, (1 << 32) - 1, 1 << 32, 1 << 48, 1 << 63,
             (1 << 64) - 1], np.uint64), size=lanes)
    else:
        amt = rng.integers(0, 1 << 64, size=lanes, dtype=np.uint64)
    accounts = 3 if amounts == "max" else 40
    dr = rng.integers(0, accounts, size=lanes).astype(np.uint64)
    cr = ((dr + 1 + rng.integers(0, accounts - 1, size=lanes).astype(
        np.uint64)) % np.uint64(accounts)).astype(np.uint64)
    ok = rng.random(lanes) < 0.9
    pending = rng.random(lanes) < 0.3
    sentinel = 1 << 12

    plan = sm.balance_plan(
        jnp.asarray(dr), jnp.asarray(cr), jnp.asarray(ok), jnp.asarray(amt),
        jnp.asarray(pending), sentinel)

    want = {}
    for i in range(lanes):
        if not ok[i]:
            continue
        kind = "pending" if pending[i] else "posted"
        for slot, side in ((int(dr[i]), "debits_"), (int(cr[i]), "credits_")):
            key = (slot, side + kind)
            want[key] = want.get(key, 0) + int(amt[i])

    s_slot, head = np.asarray(plan.s_slot), np.asarray(plan.head)
    got = {}
    for field, (d_lo, d_hi) in plan.deltas.items():
        d_lo, d_hi = np.asarray(d_lo), np.asarray(d_hi)
        assert d_lo.dtype == d_hi.dtype == np.uint64
        for at in np.flatnonzero(head):
            total = int(d_lo[at]) | (int(d_hi[at]) << 64)
            if total:
                got[(int(s_slot[at]), field)] = total
    assert got == want
