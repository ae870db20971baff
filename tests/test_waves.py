"""Wave-scheduler differential suite (TB_WAVES; docs/waves.md).

The conflict-index wave scheduler must be BIT-IDENTICAL to the serial
path: same codes, same balances, same routing — it only changes how many
Jacobi passes the general kernel runs before committing.  Covered here:

- machine-level differentials vs testing/model.py with waves ON across
  plain / two-phase (in-batch and table) / Zipfian-hot / limit-account
  mixes, at pipeline depths 1/2/4 (the deferred fast path rides along);
- waves-on vs waves-off digest identity on the same seeded workloads;
- forced-conflict batches (balancing x linked chains) that must still
  collapse to the sequential chain path under waves;
- kernel-level wave-bound certification: a conflict-free batch commits
  with a proved bound of 1 (one evaluation pass + the balance-update
  pass), hazard chains either bound tightly or fall back to stability;
- a pinned VOPR seed re-validated under TB_WAVES=1 (slow tier).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.ops import staging
from tigerbeetle_tpu.ops import state_machine as sm
from tigerbeetle_tpu.ops import transfer_full as tf
from tigerbeetle_tpu.testing import model as M

CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=11,
)


def make_pair(n_accounts=16, lanes=256, limits=(), waves=True, depth=1):
    dev = TpuStateMachine(CFG, batch_lanes=lanes)
    dev.waves_enabled = waves
    dev.pipeline_depth = depth
    ref = M.ReferenceStateMachine()
    rows = []
    for i in range(n_accounts):
        flags = 0
        if i in limits:
            flags |= types.AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
        rows.append(types.account(id=i + 1, ledger=1, code=10, flags=flags))
    accounts = types.accounts_array(rows)
    got = dev.create_accounts(accounts, wall_clock_ns=1)
    want = ref.create_accounts(M.accounts_from_batch(accounts), 1)
    assert got == want
    return dev, ref


def run_batch(dev, ref, batch):
    got = dev.create_transfers(batch)
    want = ref.create_transfers(M.transfers_from_batch(batch))
    assert got == want, f"codes diverge: {got[:8]} vs {want[:8]}"
    assert dev.balances_snapshot() == ref.balances_snapshot()


def zipf_mix_batches(seed, n_accounts, n_batches=6, batch=96):
    """Seeded Zipfian-hot mix: plain transfers + pendings + posts/voids of
    EARLIER (table) pendings, hot accounts concentrating the touches."""
    rng = np.random.default_rng(seed)
    batches = []
    pending_pool = []  # (id, amount) of pendings created in earlier batches
    next_id = 1000
    for _ in range(n_batches):
        specs = []
        for _ in range(batch):
            # Zipf-ish: squaring a uniform concentrates on low ids.
            dr = 1 + int(n_accounts * rng.random() ** 3) % n_accounts
            cr = 1 + (dr + 1 + int(4 * rng.random())) % n_accounts
            kind = rng.random()
            if kind < 0.55:
                specs.append(dict(
                    id=next_id, debit_account_id=dr, credit_account_id=cr,
                    amount=1 + int(rng.random() * 100), ledger=1, code=1,
                ))
            elif kind < 0.75 or not pending_pool:
                specs.append(dict(
                    id=next_id, debit_account_id=dr, credit_account_id=cr,
                    amount=1 + int(rng.random() * 100), ledger=1, code=1,
                    flags=types.TransferFlags.PENDING,
                ))
                pending_pool.append((next_id, None))
            else:
                pid, _ = pending_pool[int(rng.random() * len(pending_pool))]
                flag = (
                    types.TransferFlags.POST_PENDING_TRANSFER
                    if rng.random() < 0.7
                    else types.TransferFlags.VOID_PENDING_TRANSFER
                )
                specs.append(dict(
                    id=next_id, pending_id=pid, ledger=1, code=1, flags=flag,
                ))
            next_id += 1
        batches.append(types.transfers_array(
            [types.transfer(**s) for s in specs]
        ))
    return batches


class TestWavesDifferential:
    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_zipf_mix_vs_model(self, depth):
        dev, ref = make_pair(n_accounts=24, waves=True, depth=depth)
        for b in zipf_mix_batches(7, 24):
            run_batch(dev, ref, b)

    @pytest.mark.slow  # tier-1 budget: runs whole in the ci integration tier
    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_zipf_mix_with_limits_vs_model(self, depth):
        """Hot accounts limit-flagged: deep hazard chains — the scheduler
        must fall back to the stability exit without code drift."""
        dev, ref = make_pair(
            n_accounts=24, limits=(0, 1, 2), waves=True, depth=depth
        )
        # Fund the limit accounts so some transfers are accepted and some
        # reject with exceeds_credits — both directions checked.
        fund = types.transfers_array([
            types.transfer(id=900 + i, debit_account_id=10 + i,
                           credit_account_id=1 + i, amount=500, ledger=1,
                           code=1)
            for i in range(3)
        ])
        run_batch(dev, ref, fund)
        for b in zipf_mix_batches(11, 24, n_batches=4):
            run_batch(dev, ref, b)

    def test_in_batch_two_phase_vs_model(self):
        dev, ref = make_pair(waves=True)
        specs = [
            dict(id=300 + i, debit_account_id=1 + i % 8,
                 credit_account_id=9 + i % 8, amount=50, ledger=1, code=1,
                 flags=types.TransferFlags.PENDING)
            for i in range(16)
        ] + [
            dict(id=400 + i, pending_id=300 + i, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER)
            for i in range(16)
        ]
        run_batch(dev, ref, types.transfers_array(
            [types.transfer(**s) for s in specs]
        ))

    def test_table_pending_fulfillment_race_vs_model(self):
        """Double post / post-void races on TABLE pendings are scheduled
        (non-hazard) under waves — the riskiest single-pass case."""
        dev, ref = make_pair(waves=True)
        run_batch(dev, ref, types.transfers_array([
            types.transfer(id=500 + i, debit_account_id=1 + i,
                           credit_account_id=5 + i, amount=100, ledger=1,
                           code=1, flags=types.TransferFlags.PENDING)
            for i in range(4)
        ]))
        run_batch(dev, ref, types.transfers_array([
            types.transfer(id=520, pending_id=500, ledger=1, code=1,
                           flags=types.TransferFlags.POST_PENDING_TRANSFER),
            types.transfer(id=521, pending_id=500, ledger=1, code=1,
                           flags=types.TransferFlags.POST_PENDING_TRANSFER),
            types.transfer(id=522, pending_id=501, amount=40, ledger=1,
                           code=1,
                           flags=types.TransferFlags.POST_PENDING_TRANSFER),
            types.transfer(id=523, pending_id=501, ledger=1, code=1,
                           flags=types.TransferFlags.VOID_PENDING_TRANSFER),
            types.transfer(id=524, pending_id=502, amount=200, ledger=1,
                           code=1,
                           flags=types.TransferFlags.POST_PENDING_TRANSFER),
            types.transfer(id=525, pending_id=503, ledger=1, code=1,
                           flags=types.TransferFlags.VOID_PENDING_TRANSFER),
        ]))

    @pytest.mark.slow  # tier-1 budget: runs whole in the ci integration tier
    def test_forced_conflict_collapses_to_chain_path(self):
        """Balancing x linked chains: the kernel must still route FLAG_SEQ
        (the sequential chain path) with waves on — and match the model."""
        dev, ref = make_pair(waves=True)
        seq0 = dev._sequential
        calls = []

        def counting_sequential(op, batch, ts):
            calls.append(len(batch))
            return seq0(op, batch, ts)

        dev._sequential = counting_sequential
        fund = types.transfers_array([
            types.transfer(id=700, debit_account_id=3, credit_account_id=1,
                           amount=1000, ledger=1, code=1),
        ])
        run_batch(dev, ref, fund)
        # A linked chain whose middle member is a balancing transfer that
        # clamps to the full available balance, followed by a chain member
        # that must then fail — the classic failed-chain balance hazard.
        chain = types.transfers_array([
            types.transfer(id=701, debit_account_id=1, credit_account_id=2,
                           amount=100, ledger=1, code=1,
                           flags=types.TransferFlags.LINKED),
            types.transfer(id=702, debit_account_id=1, credit_account_id=2,
                           amount=0, ledger=1, code=1,
                           flags=types.TransferFlags.LINKED
                           | types.TransferFlags.BALANCING_DEBIT),
            types.transfer(id=703, debit_account_id=1, credit_account_id=99,
                           amount=1, ledger=1, code=1),
        ])
        run_batch(dev, ref, chain)
        assert calls, "forced-conflict batch did not take the chain path"

    @pytest.mark.slow  # ~22s; runs whole in the ci integration tier
    def test_waves_on_off_digest_identity(self):
        """Same seeded workload, waves on vs off: identical digests,
        results, and balances (bit-identity, not just code equality)."""
        results = {}
        for waves in (False, True):
            dev = TpuStateMachine(CFG, batch_lanes=256)
            dev.waves_enabled = waves
            accounts = types.accounts_array([
                types.account(id=i + 1, ledger=1, code=10)
                for i in range(24)
            ])
            dev.create_accounts(accounts, wall_clock_ns=1)
            out = []
            for b in zipf_mix_batches(23, 24):
                out.append(dev.create_transfers(b))
            results[waves] = (out, dev.digest(), dev.balances_snapshot())
        assert results[False] == results[True]


class TestWaveBound:
    def _setup(self, limits=()):
        led = sm.make_ledger(1 << 8, 1 << 10, 1 << 8)
        acc = np.zeros(64, dtype=types.ACCOUNT_DTYPE)
        n = 16
        acc["id_lo"][:n] = 1 + np.arange(n, dtype=np.uint64)
        acc["ledger"][:n] = 1
        acc["code"][:n] = 10
        for i in limits:
            acc["flags"][i] = types.AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
        led, _ = sm.create_accounts(
            led, *staging.stage_batch(acc[:n], len(acc), n)
        )
        return led, n

    def _plan(self, led, batch, count, ts):
        p = np.zeros(64, dtype=types.TRANSFER_DTYPE)
        p[:count] = batch[:count]
        soa = {k: jnp.asarray(v) for k, v in types.to_soa(p).items()}
        lane = jnp.arange(64, dtype=jnp.int32)
        valid = lane < count
        pv = (
            ((soa["flags"] & tf.TF_POST) != 0)
            | ((soa["flags"] & tf.TF_VOID) != 0)
        ) & valid
        ctx = tf.build_gather_ctx(led, soa, valid, pv)
        return tf._kernel_core(
            ctx, soa, jnp.uint64(count), jnp.uint64(ts), use_waves=True,
        )

    @pytest.mark.slow  # ~25s; runs whole in the ci integration tier
    def test_conflict_free_batch_certifies_bound_one(self):
        led, n = self._setup()
        b = np.zeros(64, dtype=types.TRANSFER_DTYPE)
        b["id_lo"][:8] = 100 + np.arange(8, dtype=np.uint64)
        b["debit_account_id_lo"][:8] = 1 + np.arange(8) % 8
        b["credit_account_id_lo"][:8] = 9 + np.arange(8) % 8
        b["amount_lo"][:8] = 5
        b["ledger"][:8] = 1
        b["code"][:8] = 10
        plan = self._plan(led, b, 8, n + 8)
        assert int(plan.wave_bound) == 1
        assert int(plan.passes) == 1
        hist = np.asarray(plan.wave_hist)
        assert int(hist[0]) == 8 and int(hist[1:].sum()) == 0
        assert int(plan.route) == 0

    def test_limit_chain_bounds_or_falls_back(self):
        """Lanes sharing a limit-flagged account: hazard chain — either a
        proved bound > 1 or (deep chains) fall back to stability."""
        led, n = self._setup(limits=(0,))
        b = np.zeros(64, dtype=types.TRANSFER_DTYPE)
        b["id_lo"][:4] = 200 + np.arange(4, dtype=np.uint64)
        b["debit_account_id_lo"][:4] = 1  # all touch limit account 1
        b["credit_account_id_lo"][:4] = 2 + np.arange(4)
        b["amount_lo"][:4] = 5
        b["ledger"][:4] = 1
        b["code"][:4] = 10
        plan = self._plan(led, b, 4, n + 8)
        bound = int(plan.wave_bound)
        hist = np.asarray(plan.wave_hist)
        # 4 hazard lanes chained through account 1: depths 1..4.
        assert bound == 5
        assert hist[1:5].tolist() == [1, 1, 1, 1]
        # All 4 reject (unfunded limit account): stability lands first,
        # at pass 2, and no pass runs after it.
        assert int(plan.passes) == 2
        assert np.asarray(plan.codes)[:4].tolist() == [54] * 4

    def test_linked_batch_is_unscheduled(self):
        led, n = self._setup()
        b = np.zeros(64, dtype=types.TRANSFER_DTYPE)
        b["id_lo"][:2] = 300 + np.arange(2, dtype=np.uint64)
        b["debit_account_id_lo"][:2] = 1
        b["credit_account_id_lo"][:2] = 2
        b["amount_lo"][:2] = 5
        b["ledger"][:2] = 1
        b["code"][:2] = 10
        b["flags"][0] = types.TransferFlags.LINKED
        plan = self._plan(led, b, 2, n + 8)
        assert int(plan.wave_bound) == 0  # unschedulable: stability exit
        assert int(plan.passes) == 2 and int(plan.route) == 0


@pytest.mark.slow
class TestVoprWaves:
    def test_pinned_seed_green_under_waves(self, tmp_path, monkeypatch):
        """The pinned VOPR seed replays green with TB_WAVES=1 (machines
        created inside the sim read the env lazily)."""
        monkeypatch.setenv("TB_WAVES", "1")
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

        result = run_seed(42, workdir=str(tmp_path), ticks=3_000)
        assert result.exit_code == EXIT_PASSED, result.summary
