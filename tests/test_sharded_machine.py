"""Sharded LIVE commit path (TB_SHARDS; docs/sharding.md) — machine-level
parity and differentials.

tests/test_sharded.py proves the mesh KERNELS byte-equal to the single-chip
kernels (the dryrun); this file proves the MACHINE mode built on them: the
serving-path dispatch, the cross-shard two-phase split, the sequential
fallback (unshard -> exact scan path -> reshard), growth under sharding,
queries/checkpoints through the canonical view, and the pinned VOPR seed.

Runs on the virtual 8-device CPU mesh (conftest).  The heavy parametrized
differentials and the VOPR seed are @slow and ride the ci integration tier
(tier-1 budget discipline, ROADMAP standing constraint)."""

import random

import jax
import numpy as np
import pytest

from tigerbeetle_tpu import jaxenv, types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.ops.scrub import mix64_np
from tigerbeetle_tpu.testing import model as M

LANES = 128


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(
            f"needs {n} devices, have {len(jax.devices())} "
            f"(jaxenv degraded: {jaxenv.DEGRADED_DEVICE_COUNT})"
        )


def small_cfg():
    return LedgerConfig(
        accounts_capacity_log2=10, transfers_capacity_log2=12,
        posted_capacity_log2=10,
    )


def owner_of(account_id: int, shards: int) -> int:
    return int(
        mix64_np(np.array([account_id], np.uint64), np.zeros(1, np.uint64))[0]
    ) & (shards - 1)


def accounts_by_owner(shards: int, per_owner: int, flags=0):
    """Account ids bucketed by shard owner (owner = low hash bits)."""
    buckets = {s: [] for s in range(shards)}
    aid = 1
    while any(len(b) < per_owner for b in buckets.values()):
        s = owner_of(aid, shards)
        if len(buckets[s]) < per_owner:
            buckets[s].append(aid)
        aid += 1
    rows = [
        types.account(id=a, ledger=1, code=10, flags=flags)
        for b in buckets.values() for a in b
    ]
    return buckets, types.accounts_array(sorted(rows, key=lambda r: int(r["id_lo"])))


def make_pair(shards, cfg=None, **kw):
    cfg = cfg or small_cfg()
    single = TpuStateMachine(cfg, batch_lanes=LANES, **kw)
    sharded = TpuStateMachine(cfg, batch_lanes=LANES, shards=shards, **kw)
    assert sharded.shards == shards
    return single, sharded


def commit_both(single, sharded, batch):
    w = single.create_transfers(batch)
    g = sharded.create_transfers(batch)
    assert w == g, (w[:5], g[:5])
    return w


def test_shards_off_is_plain_single_device(monkeypatch):
    monkeypatch.delenv("TB_SHARDS", raising=False)
    m = TpuStateMachine(small_cfg(), batch_lanes=LANES)
    assert m.shards == 0 and m._shard_mesh is None
    assert not m._ledger_is_sharded
    # count stays a scalar — the pre-sharding ledger layout exactly.
    assert np.ndim(m.ledger.accounts.count) == 0


def test_env_twin_engages(monkeypatch):
    _need_devices(2)
    monkeypatch.setenv("TB_SHARDS", "2")
    m = TpuStateMachine(small_cfg(), batch_lanes=LANES)
    assert m.shards == 2 and m._ledger_is_sharded
    assert np.asarray(m.ledger.accounts.count).shape == (2,)


def test_sharded_machine_parity_mixed():
    """Compact parity pass: plain cross-shard + two-phase + history
    seq-fallback through the live machine at 2 shards, results, digest,
    and balances equal the single-device machine; cross-shard and
    fallback accounting fires, in the machine's counters and in the
    ``sharding.*`` series."""
    from tigerbeetle_tpu.obs.metrics import registry

    _need_devices(2)
    with registry.enabled_scope():
        sharded = _parity_mixed()
        snap = registry.snapshot()
    counters = snap["counters"]
    assert counters["sharding.batches"] == 4  # the history batch fell back
    assert counters["sharding.lanes"] == sharded.shard_lanes_total > 0
    assert counters["sharding.cross_shard_lanes"] == sharded.shard_lanes_cross
    assert counters["sharding.seq_fallbacks"] == 1
    assert snap["histograms"]["sharding.cross_shard_pct"]["max"] == 100
    assert snap["gauges"]["sharding.shards"] == 2


def _parity_mixed():
    single, sharded = make_pair(2)
    buckets, accounts = accounts_by_owner(2, 6)
    # One HISTORY account, touched only by the final batch.
    hist_rows = types.accounts_array(
        [types.account(id=5000, ledger=1, code=10,
                       flags=types.AccountFlags.HISTORY)]
    )
    assert single.create_accounts(accounts, wall_clock_ns=1) == (
        sharded.create_accounts(accounts, wall_clock_ns=1)
    )
    assert single.create_accounts(hist_rows) == sharded.create_accounts(hist_rows)

    same = buckets[0]
    other = buckets[1]
    # 100% cross-shard plain batch, then a same-shard one.
    cross = types.transfers_array([
        types.transfer(id=100 + i, debit_account_id=same[i % 6],
                       credit_account_id=other[(i + 1) % 6],
                       amount=3 + i, ledger=1, code=1)
        for i in range(10)
    ])
    commit_both(single, sharded, cross)
    assert sharded.shard_lanes_cross == 10
    local = types.transfers_array([
        types.transfer(id=200 + i, debit_account_id=same[i % 6],
                       credit_account_id=same[(i + 1) % 6],
                       amount=2, ledger=1, code=1)
        for i in range(6)
    ])
    commit_both(single, sharded, local)
    assert sharded.shard_lanes_cross == 10  # unchanged: same-owner pairs
    # Cross-shard two-phase: pending on shard pair, then table post/void.
    pend = types.transfers_array([
        types.transfer(id=300 + i, debit_account_id=same[i % 6],
                       credit_account_id=other[i % 6], amount=20,
                       ledger=1, code=1, flags=types.TransferFlags.PENDING)
        for i in range(6)
    ])
    commit_both(single, sharded, pend)
    post = types.transfers_array([
        types.transfer(id=400 + i, pending_id=300 + i, ledger=1, code=1,
                       flags=(types.TransferFlags.POST_PENDING_TRANSFER
                              if i % 2 == 0
                              else types.TransferFlags.VOID_PENDING_TRANSFER))
        for i in range(6)
    ])
    commit_both(single, sharded, post)
    assert sharded.shard_seq_fallbacks == 0
    # History batch: the sequential-fallback exit.
    hist = types.transfers_array([
        types.transfer(id=500, debit_account_id=5000,
                       credit_account_id=same[0], amount=7, ledger=1, code=1)
    ])
    commit_both(single, sharded, hist)
    assert sharded.shard_seq_fallbacks == 1
    assert single.digest() == sharded.digest()
    assert single.balances_snapshot() == sharded.balances_snapshot()
    # Lookups and the account-transfers query go through the canonical view.
    ids = [same[0], other[0], 5000, 999_999]
    assert (single.lookup_accounts(ids) == sharded.lookup_accounts(ids)).all()
    tids = [100, 300, 400, 777_777]
    assert (
        single.lookup_transfers(tids) == sharded.lookup_transfers(tids)
    ).all()
    filt = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0].copy()
    filt["account_id_lo"] = same[0]
    filt["limit"] = 64
    filt["flags"] = (
        types.AccountFilterFlags.DEBITS | types.AccountFilterFlags.CREDITS
    )
    q1, q2 = single.get_account_transfers(filt), sharded.get_account_transfers(filt)
    assert len(q1) == len(q2) and (q1 == q2).all()
    return sharded


def zipf_mix(rng, accounts, pendings, n=48, two_phase=True):
    """Zipfian-hot mixed batch builder (waves-smoke discipline: posts draw
    only from earlier batches' pendings so batches stay schedulable)."""
    specs = []
    avail = list(pendings)
    nid = rng.randrange(1 << 20, 1 << 21)
    n_acc = len(accounts)
    for _ in range(n):
        dr = accounts[int(n_acc * rng.random() ** 3) % n_acc]
        cr = accounts[(accounts.index(dr) + 1 + int(3 * rng.random())) % n_acc]
        kind = rng.random()
        if not two_phase or kind < 0.6:
            specs.append(types.transfer(
                id=nid, debit_account_id=dr, credit_account_id=cr,
                amount=1 + int(rng.random() * 50), ledger=1, code=1,
            ))
        elif kind < 0.85 or not avail:
            specs.append(types.transfer(
                id=nid, debit_account_id=dr, credit_account_id=cr,
                amount=20, ledger=1, code=1,
                flags=types.TransferFlags.PENDING,
            ))
            pendings.append(nid)
        else:
            pid = avail.pop(int(rng.random() * len(avail)))
            if pid in pendings:
                pendings.remove(pid)
            specs.append(types.transfer(
                id=nid, pending_id=pid, ledger=1, code=1,
                flags=types.TransferFlags.POST_PENDING_TRANSFER,
            ))
        nid += 1
    return types.transfers_array(specs)


class TestShardedDifferential:
    """Machine-level differentials vs the scalar oracle across cross-shard
    fraction x pipeline depth x workload mix (the satellite matrix)."""

    @pytest.mark.slow  # rides the ci integration tier
    @pytest.mark.parametrize("cross_pct", [0, 50, 100])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_cross_fraction_vs_model(self, cross_pct, depth):
        _need_devices(2)
        m = TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=2)
        m.pipeline_depth = depth
        ref = M.ReferenceStateMachine()
        buckets, accounts = accounts_by_owner(2, 8)
        got = m.create_accounts(accounts, wall_clock_ns=1)
        want = ref.create_accounts(
            [M.account_from_row(r) for r in accounts], 1
        )
        assert got == want
        same, other = buckets[0], buckets[1]
        rng = random.Random(1234 + cross_pct + depth)
        for _b in range(3):
            specs = []
            for i in range(40):
                dr = same[rng.randrange(8)]
                if rng.randrange(100) < cross_pct:
                    cr = other[rng.randrange(8)]
                else:
                    cr = same[(same.index(dr) + 1) % 8]
                specs.append(types.transfer(
                    id=(1 << 16) + cross_pct * 1000 + depth * 300
                    + _b * 100 + i,
                    debit_account_id=dr, credit_account_id=cr,
                    amount=1 + rng.randrange(40), ledger=1, code=1,
                ))
            batch = types.transfers_array(specs)
            got = m.create_transfers(batch)
            want = ref.create_transfers(
                [M.transfer_from_row(r) for r in batch]
            )
            assert got == want
        assert m.balances_snapshot() == ref.balances_snapshot()
        if cross_pct == 100:
            assert m.shard_lanes_cross == m.shard_lanes_total
        if cross_pct == 0:
            assert m.shard_lanes_cross == 0

    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("mix", ["zipf", "two_phase"])
    def test_zipf_and_two_phase_vs_model(self, depth, mix):
        _need_devices(2)
        m = TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=2)
        m.pipeline_depth = depth
        ref = M.ReferenceStateMachine()
        _buckets, accounts = accounts_by_owner(2, 8)
        acct_ids = sorted(int(r["id_lo"]) for r in accounts)
        assert m.create_accounts(accounts, wall_clock_ns=1) == (
            ref.create_accounts([M.account_from_row(r) for r in accounts], 1)
        )
        rng = random.Random(77 + depth)
        pendings = []
        for _b in range(4):
            batch = zipf_mix(
                rng, acct_ids, pendings, two_phase=(mix == "two_phase")
            )
            got = m.create_transfers(batch)
            want = ref.create_transfers(
                [M.transfer_from_row(r) for r in batch]
            )
            assert got == want
        assert m.balances_snapshot() == ref.balances_snapshot()


@pytest.mark.slow
class TestShardedStructural:
    """Growth, conversions, checkpoint arrays, waves, scrub — the
    structural surfaces of the mode.  @slow: growth compiles new kernel
    shape variants; rides the ci integration tier."""

    def test_growth_parity(self):
        _need_devices(2)
        cfg = LedgerConfig(
            accounts_capacity_log2=10, transfers_capacity_log2=10,
            posted_capacity_log2=10,
        )
        single, sharded = make_pair(2, cfg=cfg)
        _buckets, accounts = accounts_by_owner(2, 8)
        single.create_accounts(accounts, wall_clock_ns=1)
        sharded.create_accounts(accounts, wall_clock_ns=1)
        acct_ids = sorted(int(r["id_lo"]) for r in accounts)
        # 3 * 512 transfers through a 1024-slot table: forced growth.
        for b in range(12):
            batch = types.transfers_array([
                types.transfer(
                    id=(1 << 18) + b * 128 + i,
                    debit_account_id=acct_ids[i % 16],
                    credit_account_id=acct_ids[(i + 1) % 16],
                    amount=1, ledger=1, code=1,
                )
                for i in range(128)
            ])
            commit_both(single, sharded, batch)
        assert single.ledger.transfers.capacity == (
            sharded.ledger.transfers.capacity
        )
        assert single.digest() == sharded.digest()
        assert single.balances_snapshot() == sharded.balances_snapshot()

    def test_checkpoint_roundtrip_and_restore(self):
        _need_devices(2)
        from tigerbeetle_tpu.vsr import checkpoint as ck

        single, sharded = make_pair(2)
        _buckets, accounts = accounts_by_owner(2, 6)
        single.create_accounts(accounts, wall_clock_ns=1)
        sharded.create_accounts(accounts, wall_clock_ns=1)
        acct_ids = sorted(int(r["id_lo"]) for r in accounts)
        batch = types.transfers_array([
            types.transfer(id=900 + i, debit_account_id=acct_ids[i % 12],
                           credit_account_id=acct_ids[(i + 5) % 12],
                           amount=9, ledger=1, code=1)
            for i in range(20)
        ])
        commit_both(single, sharded, batch)
        # Canonical arrays must be identical to the single-device machine's
        # serialization — the cross-shard-config restore contract.
        a1 = ck.ledger_to_arrays(single.checkpoint_ledger())
        a2 = ck.ledger_to_arrays(sharded.checkpoint_ledger())
        assert sorted(a1) == sorted(a2)
        for key in a1:
            assert (a1[key] == a2[key]).all(), key
        # Restore the canonical snapshot into a FRESH sharded machine.
        m3 = TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=2)
        m3.ledger = ck.arrays_to_ledger(a2)
        m3.restore_host_state(sharded.host_state())
        assert m3._ledger_is_sharded
        assert m3.digest() == sharded.digest()
        nxt = types.transfers_array([
            types.transfer(id=7777, debit_account_id=acct_ids[0],
                           credit_account_id=acct_ids[1], amount=1,
                           ledger=1, code=1)
        ])
        r_a = sharded.create_transfers(nxt)
        r_b = m3.commit_batch(
            "create_transfers", nxt, sharded.prepare_timestamp
        )
        assert r_a == r_b and m3.digest() == sharded.digest()

    def test_waves_on_off_identity_under_shards(self, monkeypatch):
        """Satellite: use_waves inside the sharded per-shard kernel — the
        TB_SHARDS>0 x TB_WAVES on/off matrix stays digest-identical."""
        _need_devices(2)
        digs = {}
        for waves in (False, True):
            m = TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=2)
            m.waves_enabled = waves
            _buckets, accounts = accounts_by_owner(2, 8)
            m.create_accounts(accounts, wall_clock_ns=1)
            acct_ids = sorted(int(r["id_lo"]) for r in accounts)
            rng = random.Random(5)
            pendings = []
            results = []
            for _b in range(3):
                batch = zipf_mix(rng, acct_ids, pendings, n=40)
                results.append(m.create_transfers(batch))
            digs[waves] = (m.digest(), results, m.balances_snapshot())
        assert digs[False] == digs[True]

    def test_scrub_lanes_detect_and_recover(self):
        _need_devices(2)
        m = TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=2)
        m.scrub_interval = 1
        _buckets, accounts = accounts_by_owner(2, 4)
        m.create_accounts(accounts, wall_clock_ns=1)
        m.scrub_arm()
        acct_ids = sorted(int(r["id_lo"]) for r in accounts)
        batch = types.transfers_array([
            types.transfer(id=600 + i, debit_account_id=acct_ids[i % 8],
                           credit_account_id=acct_ids[(i + 1) % 8],
                           amount=4, ledger=1, code=1)
            for i in range(8)
        ])
        m.create_transfers(batch)
        m.create_transfers(types.transfers_array([
            types.transfer(id=700, debit_account_id=acct_ids[0],
                           credit_account_id=acct_ids[1], amount=1,
                           ledger=1, code=1)
        ]))
        assert m.scrub_checks >= 1 and m.scrub_mismatches == 0
        digest_before = m.digest()
        assert m.inject_sdc_bitflip(random.Random(11))
        assert m.digest() != digest_before  # the flip is visible
        assert not m.scrub_check()  # detected + recovered
        assert m.device_recoveries == 1 and m._ledger_is_sharded
        assert m.digest() == digest_before  # content restored
        assert m.scrub_check()  # clean again

    def test_unshard_shard_roundtrip_deterministic(self):
        _need_devices(2)
        from jax.sharding import Mesh

        from tigerbeetle_tpu.parallel import sharded as shard_mod

        m = TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=2)
        _buckets, accounts = accounts_by_owner(2, 6)
        m.create_accounts(accounts, wall_clock_ns=1)
        acct_ids = sorted(int(r["id_lo"]) for r in accounts)
        m.create_transfers(types.transfers_array([
            types.transfer(id=800 + i, debit_account_id=acct_ids[i % 12],
                           credit_account_id=acct_ids[(i + 1) % 12],
                           amount=2, ledger=1, code=1)
            for i in range(24)
        ]))
        mesh = Mesh(np.array(jax.devices()[:2]), (shard_mod.AXIS,))
        canon1 = shard_mod.unshard_ledger(m.ledger, mesh)
        back = shard_mod.shard_ledger(canon1, mesh)
        canon2 = shard_mod.unshard_ledger(back, mesh)
        from tigerbeetle_tpu.vsr import checkpoint as ck

        a1, a2 = ck.ledger_to_arrays(canon1), ck.ledger_to_arrays(canon2)
        for key in a1:
            assert (a1[key] == a2[key]).all(), key
        # Re-sharding reproduced the machine's own layout byte for byte.
        b1 = ck.ledger_to_arrays(m.ledger)
        b2 = ck.ledger_to_arrays(back)
        for key in b1:
            assert (np.asarray(b1[key]) == np.asarray(b2[key])).all(), key


@pytest.mark.slow
class TestVoprSharded:
    def test_pinned_seed_green_under_shards(self, tmp_path, monkeypatch):
        """The pinned VOPR seed replays green with TB_SHARDS=2: every
        replica's machine commits through the mesh path, checkpoints
        serialize canonically, and all oracles (auditor, conservation,
        convergence, per-op digests) hold.  Tiered schedules run untiered
        under shards (stream-stable override in sim/vopr.py)."""
        monkeypatch.setenv("TB_SHARDS", "2")
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

        result = run_seed(42, workdir=str(tmp_path), ticks=3_000)
        assert result.exit_code == EXIT_PASSED


def test_more_shards_than_devices_is_an_error():
    """Asked-for shards that cannot be had must not silently serve
    single-device (`start --shards N` exits non-zero on the same check)."""
    shards = 1 << len(jax.devices()).bit_length()  # > device count
    with pytest.raises(RuntimeError, match=r"device\(s\) visible"):
        TpuStateMachine(small_cfg(), batch_lanes=LANES, shards=shards)
