"""Device fault domain (ops/scrub.py, docs/fault_domains.md): differential
proofs for SDC scrubbing, dispatch retry/quarantine, and device-state
recovery.

Layers under test:
- machine: digest folds match the mirror's numpy twins byte-for-byte on
  clean streams (no spurious quarantines — false-positive safety across
  pipeline depths and grouped/ungrouped commits), a seeded bit flip is
  detected at the next scrub point and recovered to a state identical to
  an unfaulted twin, forced dispatch exceptions are retried (and degrade
  to the host engine after N consecutive failures).
- replica: a forced dispatch exception mid-group under the pipelined
  engine (TB_PIPELINE=2) completes with reply/ledger state identical to
  the fault-free run; checkpoint+WAL replay rebuilds device state in
  process (recover_device_state).
- VOPR: a pinned seed injecting device-SDC passes with scrubbing armed
  (detection + recovery + auditor green) and demonstrably FAILS with
  scrubbing off — the scrub is load-bearing, not decorative.
"""

import concurrent.futures
import os
import random

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import TEST_MIN, LedgerConfig
from tigerbeetle_tpu.host_engine import engine_available
from tigerbeetle_tpu.machine import (
    DeviceCommitHandle, DeviceStateUnrecoverable, TpuStateMachine,
)
from tigerbeetle_tpu.ops import scrub as scrub_ops
from tigerbeetle_tpu.testing import model as M

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)
N_ACCOUNTS = 16


def accounts_batch():
    return types.accounts_array([
        types.account(id=i + 1, ledger=1, code=10)
        for i in range(N_ACCOUNTS)
    ])


def batch(first_id, n, flags=0):
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 3) % N_ACCOUNTS,
            amount=3 + i % 5, ledger=1, code=10, flags=flags,
        )
        for i in range(n)
    ])


def pending_post_batch(first_id, n):
    """Half pending creates + half posts: drives the posted table so the
    posted fold carries weight."""
    rows = []
    for i in range(n // 2):
        rows.append(types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 5) % N_ACCOUNTS, amount=2,
            ledger=1, code=10, flags=int(types.TransferFlags.PENDING),
        ))
    for i in range(n // 2):
        rows.append(types.transfer(
            id=first_id + 1000 + i, pending_id=first_id + i,
            ledger=1, code=10,
            flags=int(types.TransferFlags.POST_PENDING_TRANSFER),
        ))
    return types.transfers_array(rows)


def make_machine(scrub_interval=0, **kw):
    m = TpuStateMachine(CFG, batch_lanes=LANES, **kw)
    m.retry_tick_s = 0
    m.scrub_interval = scrub_interval
    assert m.create_accounts(accounts_batch(), wall_clock_ns=1000) == []
    if scrub_interval:
        assert m.scrub_arm()
    return m


class TestScrubDigest:
    def test_mirror_digests_match_device_on_clean_stream(self):
        """The numpy twins must equal the device folds value-for-value —
        including two-phase flows (transfers + posted pads)."""
        m = make_machine(scrub_interval=8)
        assert m.create_transfers(batch(1000, 20)) == []
        assert m.create_transfers(pending_post_batch(5000, 12)) == []
        got = np.asarray(scrub_ops.scrub_digest(m.ledger))
        want = scrub_ops.mirror_digests(m._scrub_mirror)
        assert (int(got[0]), int(got[1]), int(got[2])) == want
        # The accounts fold doubles as the checkpoint digest.
        assert int(got[0]) == m.digest()
        assert m.scrub_check() is True
        assert m.scrub_mismatches == 0

    @pytest.mark.slow
    def test_no_false_positives_across_depths_and_grouping(self, tmp_path):
        """Satellite: scrub digest invariance across pipeline depths 1/2/4
        and grouped vs ungrouped commits — the overlap machinery must
        never cause a spurious quarantine.  (@slow: six replica builds;
        runs in the CI integration tier.)"""
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        digests = set()
        for depth in (1, 2, 4):
            for group in (False, True):
                path = str(tmp_path / f"d{depth}g{int(group)}.tb")
                Replica.format(path, cluster=5, cluster_config=TEST_MIN)
                r = Replica(
                    path, cluster_config=TEST_MIN, ledger_config=CFG,
                    batch_lanes=LANES, time_ns=lambda: 0, scrub_interval=1,
                )
                r.open()
                r.machine.retry_tick_s = 0
                r.pipeline_depth = depth
                sessions = {}

                def req(client, n, op, body):
                    h = wire.new_header(
                        wire.Command.request, cluster=5, client=client,
                        request=n, session=sessions.get(client, 0),
                        operation=int(op),
                    )
                    h["size"] = wire.HEADER_SIZE + len(body)
                    return wire.set_checksums(h, body), body

                clients = [0x500 + i for i in range(3)]
                for c in clients:
                    replies, fs = r.on_request_group_pipelined(
                        [req(c, 0, wire.Operation.register, b"")]
                    )
                    if fs is not None:
                        fs.result()
                    rh, _ = wire.decode_header(replies[0][0][:256])
                    sessions[c] = int(rh["commit"])
                replies, fs = r.on_request_group_pipelined([req(
                    clients[0], 1, wire.Operation.create_accounts,
                    accounts_batch().tobytes(),
                )])
                if fs is not None:
                    fs.result()
                for g in range(3):
                    reqs = [
                        req(c, g + 2, wire.Operation.create_transfers,
                            batch((g * 3 + k + 1) * 10_000, 8 + k).tobytes())
                        for k, c in enumerate(clients)
                    ]
                    # Ungrouped: one request a commit group.
                    for chunk in [reqs] if group else [[q] for q in reqs]:
                        replies, fs = r.on_request_group_pipelined(chunk)
                        if fs is not None:
                            fs.result()
                r.pipeline_flush()
                assert r.machine.scrub_check() is True
                assert r.machine.scrub_mismatches == 0, (depth, group)
                assert r.machine.device_recoveries == 0, (depth, group)
                got = np.asarray(scrub_ops.scrub_digest(r.machine.ledger))
                digests.add((int(got[0]), int(got[1]), int(got[2])))
                r.close()
        assert len(digests) == 1, (
            f"scrub digests diverge across depth/grouping: {digests}"
        )


class TestSdcRecovery:
    def test_bitflip_detected_and_recovered_identical(self):
        clean = make_machine()
        faulted = make_machine(scrub_interval=1)
        streams = [batch(1000, 20), batch(2000, 12), batch(3000, 9)]
        for k, b in enumerate(streams):
            if k == 1:
                assert faulted.inject_sdc_bitflip(random.Random(7))
            assert clean.create_transfers(b) == []
            assert faulted.create_transfers(b) == []
        assert faulted.scrub_mismatches == 1
        assert faulted.device_recoveries == 1
        assert faulted.scrub_check() is True
        assert faulted.digest() == clean.digest()
        assert faulted.balances_snapshot() == clean.balances_snapshot()

    def test_unscrubbed_bitflip_diverges(self):
        """The negative control: without the scrub the flip persists into
        the final state (this is what the VOPR's conservation/convergence
        oracles catch cluster-wide)."""
        clean = make_machine()
        faulted = make_machine()  # fault domain OFF
        for k, b in enumerate([batch(1000, 20), batch(2000, 12)]):
            if k == 1:
                assert faulted.inject_sdc_bitflip(random.Random(7))
            clean.create_transfers(b)
            faulted.create_transfers(b)
        assert faulted.digest() != clean.digest()

    def test_recovery_matches_scalar_oracle(self):
        """Post-recovery results must still be model-exact (the mirror IS
        the model: recovery must not fork them)."""
        ref = M.ReferenceStateMachine()
        assert ref.create_accounts(
            [M.account_from_row(r) for r in accounts_batch()], 1000
        ) == []
        m = make_machine(scrub_interval=1)
        for k, b in enumerate(
            [batch(1000, 20), pending_post_batch(4000, 10), batch(6000, 7)]
        ):
            if k == 2:
                assert m.inject_sdc_bitflip(random.Random(3))
            ts = m.prepare("create_transfers", len(b), 0)
            got = m.commit_batch("create_transfers", b, ts)
            want = ref.create_transfers([M.transfer_from_row(r) for r in b])
            assert got == want, k
        assert m.device_recoveries == 1
        assert m.balances_snapshot() == ref.balances_snapshot()


class TestDispatchRetry:
    def test_blocking_fault_retried_identical(self):
        clean = make_machine()
        faulted = make_machine(scrub_interval=8)
        for k, b in enumerate([batch(1000, 20), batch(2000, 12)]):
            if k == 1:
                faulted.inject_device_faults(1)
            assert clean.create_transfers(b) == []
            assert faulted.create_transfers(b) == []
        assert faulted.device_recoveries == 1
        assert faulted.digest() == clean.digest()

    def test_deferred_group_fault_recovered_across_handles(self):
        """A failed dispatch with TWO runs in flight: both must resolve
        with results identical to the blocking twin's (FIFO recovery)."""
        m = make_machine(scrub_interval=8)
        twin = make_machine()
        batches = [batch(2000, 8), batch(3000, 8)]
        tss = [m.prepare("create_transfers", 8, 0) for _ in batches]
        m.inject_device_faults(1)
        h1 = m.commit_group_fast(batches, tss, deferred=True)
        assert isinstance(h1, DeviceCommitHandle)
        b4 = batch(4000, 5)
        ts4 = m.prepare("create_transfers", 5, 0)
        h2 = m.commit_fast_deferred(b4, ts4)
        r1, r2 = h1.resolve(), h2.resolve()
        tss_t = [twin.prepare("create_transfers", 8, 0) for _ in batches]
        assert tss_t == tss
        rt = twin.commit_group_fast(batches, tss_t)
        rt4 = twin.commit_batch(
            "create_transfers", b4, twin.prepare("create_transfers", 5, 0)
        )
        assert r1 == rt and r2 == [rt4]
        assert m.device_recoveries >= 1
        assert m.digest() == twin.digest()
        assert m.scrub_check() is True

    @pytest.mark.skipif(
        not engine_available(), reason="native host engine not built"
    )
    def test_consecutive_faults_degrade_to_host_engine(self):
        clean = make_machine()
        m = make_machine(scrub_interval=8)
        m.inject_device_faults(50)  # every re-dispatch fails too
        with pytest.warns(RuntimeWarning, match="degraded to the native"):
            assert m.create_transfers(batch(1000, 20)) == []
        assert m.degraded_to_host_engine
        assert m._engine is not None
        assert not m.scrub_armed  # the host ledger is the authority now
        clean.create_transfers(batch(1000, 20))
        # Serving continues on the engine, value-identical.
        assert m.create_transfers(batch(2000, 6)) == []
        clean.create_transfers(batch(2000, 6))
        assert m.balances_snapshot() == clean.balances_snapshot()
        assert m.digest() == clean.digest()

    def test_unrecoverable_without_mirror_reraises(self):
        """Fault domain off: a dispatch failure propagates untouched
        (pre-fault-domain behavior, bit for bit)."""
        m = make_machine()  # no scrub -> no mirror
        m.inject_device_faults(1)
        with pytest.raises(scrub_ops.SimulatedDeviceFault):
            m.create_transfers(batch(1000, 8))


class TestReplicaFaultDomain:
    def _harness(self, tmp, name, scrub):
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        path = os.path.join(tmp, f"{name}.tb")
        Replica.format(path, cluster=5, cluster_config=TEST_MIN)
        r = Replica(path, cluster_config=TEST_MIN, ledger_config=CFG,
                    batch_lanes=LANES, time_ns=lambda: 0,
                    scrub_interval=scrub)
        r.open()
        r.machine.retry_tick_s = 0
        r.pipeline_depth = 2
        return r, wire

    def _run_stream(self, r, wire, fault_at_group=None):
        sessions = {}

        def req(client, n, op, body):
            h = wire.new_header(
                wire.Command.request, cluster=5, client=client,
                request=n, session=sessions.get(client, 0),
                operation=int(op),
            )
            h["size"] = wire.HEADER_SIZE + len(body)
            return wire.set_checksums(h, body), body

        clients = [0x700 + i for i in range(3)]
        for c in clients:
            replies, fs = r.on_request_group_pipelined(
                [req(c, 0, wire.Operation.register, b"")]
            )
            if fs is not None:
                fs.result()
            rh, _ = wire.decode_header(replies[0][0][:256])
            sessions[c] = int(rh["commit"])
        replies, fs = r.on_request_group_pipelined([req(
            clients[0], 1, wire.Operation.create_accounts,
            accounts_batch().tobytes(),
        )])
        if fs is not None:
            fs.result()
        bodies = []
        for g in range(4):
            if fault_at_group is not None and g == fault_at_group:
                r.machine.inject_device_faults(1)
            reqs = [
                req(c, g + 2, wire.Operation.create_transfers,
                    batch((g * 3 + k + 1) * 10_000, 8 + k).tobytes())
                for k, c in enumerate(clients)
            ]
            replies, fs = r.on_request_group_pipelined(
                reqs, deferred_replies=True
            )
            if isinstance(replies, concurrent.futures.Future):
                r.pipeline_flush()
                replies = replies.result(timeout=30)
            if fs is not None:
                fs.result()
            for rl in replies:
                assert rl, "request dropped"
                bodies.append(rl[0][256:])
        r.pipeline_flush()
        return bodies

    def test_forced_fault_mid_group_pipelined_identical(self, tmp_path):
        """Acceptance: a forced dispatch exception mid-group under
        TB_PIPELINE=2 is retried and completes with reply/ledger digests
        identical to the fault-free run."""
        tmp = str(tmp_path)
        base_r, wire = self._harness(tmp, "base", scrub=0)
        base = (self._run_stream(base_r, wire), base_r.machine.digest(),
                base_r.machine.balances_snapshot())
        base_r.close()
        faulted_r, wire = self._harness(tmp, "faulted", scrub=4)
        bodies = self._run_stream(faulted_r, wire, fault_at_group=2)
        assert faulted_r.machine.device_recoveries >= 1
        assert bodies == base[0]
        assert faulted_r.machine.digest() == base[1]
        assert faulted_r.machine.balances_snapshot() == base[2]
        faulted_r.close()

    def test_resolve_escalation_routes_to_wal_replay(self, tmp_path):
        """A device fault at deferred-resolve when the mirror cannot
        re-materialize (suspect) must escalate to the durable-state
        rebuild — aborting the in-flight group (clients retry) — instead
        of crashing the serving path with a raw device error."""
        r, wire = self._harness(str(tmp_path), "esc", scrub=4)
        sessions = {}

        def req(client, n, op, body):
            h = wire.new_header(
                wire.Command.request, cluster=5, client=client, request=n,
                session=sessions.get(client, 0), operation=int(op),
            )
            h["size"] = wire.HEADER_SIZE + len(body)
            return wire.set_checksums(h, body), body

        c = 0x900
        replies, fs = r.on_request_group_pipelined(
            [req(c, 0, wire.Operation.register, b"")]
        )
        if fs is not None:
            fs.result()
        rh, _ = wire.decode_header(replies[0][0][:256])
        sessions[c] = int(rh["commit"])
        replies, fs = r.on_request_group_pipelined([req(
            c, 1, wire.Operation.create_accounts, accounts_batch().tobytes()
        )])
        if fs is not None:
            fs.result()
        replies, fs = r.on_request_group_pipelined(
            [req(c, 2, wire.Operation.create_transfers,
                 batch(10_000, 8).tobytes())]
        )
        if fs is not None:
            fs.result()
        digest_committed = r.machine.digest()
        # Mirror suspect + a dispatch fault on the next deferred run.
        r.machine._scrub_suspect = True
        r.machine.inject_device_faults(1)
        promise, fs = r.on_request_group_pipelined(
            [req(c, 3, wire.Operation.create_transfers,
                 batch(20_000, 6).tobytes())],
            deferred_replies=True,
        )
        r.pipeline_flush()  # resolve fails -> abort + WAL-replay recovery
        if isinstance(promise, concurrent.futures.Future):
            with pytest.raises(RuntimeError):
                promise.result(timeout=30)  # the aborted group's promise
        if fs is not None:
            fs.result()
        assert r.machine.device_recoveries >= 1
        assert r.machine.digest() == digest_committed  # committed prefix
        assert r.machine.scrub_armed  # re-armed from the verified rebuild
        # Serving continues (the dropped client would simply retry).
        replies, fs = r.on_request_group_pipelined(
            [req(c, 3, wire.Operation.create_transfers,
                 batch(30_000, 5).tobytes())]
        )
        if fs is not None:
            fs.result()
        assert replies[0] and replies[0][0][256:] == b""
        r.close()

    def test_recover_device_state_checkpoint_wal_replay(self, tmp_path):
        """The fallback path: rebuild from checkpoint + WAL replay in
        process, byte-identical, scrub re-armed, serving continues."""
        from tigerbeetle_tpu.config import ClusterConfig
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        config = ClusterConfig(message_size_max=8192, journal_slot_count=64)
        path = str(tmp_path / "wal.tb")
        Replica.format(path, cluster=1, cluster_config=config)
        r = Replica(path, cluster_config=config, ledger_config=CFG,
                    batch_lanes=LANES, scrub_interval=4)
        r.open()
        r.machine.retry_tick_s = 0

        def req(client, n, op, body, session=0):
            h = wire.new_header(
                wire.Command.request, cluster=1, client=client, request=n,
                session=session, operation=int(op),
            )
            h = wire.set_checksums(h, body)
            out = r.on_request(h, body)
            assert out
            return wire.decode(out[0])

        rh, _, _ = req(0xAA, 0, wire.Operation.register, b"")
        session = int(rh["op"])
        req(0xAA, 1, wire.Operation.create_accounts,
            accounts_batch().tobytes(), session)
        n = 2
        for i in range(config.vsr_checkpoint_interval + 4):
            req(0xAA, n, wire.Operation.create_transfers,
                batch(10_000 + i * 100, 2).tobytes(), session)
            n += 1
        assert r.op_checkpoint > 0
        digest = r.machine.digest()
        balances = r.machine.balances_snapshot()
        recoveries0 = r.machine.device_recoveries
        r.recover_device_state()
        assert r.machine.digest() == digest
        assert r.machine.balances_snapshot() == balances
        assert r.machine.device_recoveries == recoveries0 + 1
        assert r.machine.scrub_armed and r.machine.scrub_check() is True
        # An unrecoverable machine state routes _execute through the same
        # rebuild: poison the mirror and force a scrub escalation.
        r.machine._scrub_suspect = True
        assert r.machine.inject_sdc_bitflip(random.Random(11))
        with pytest.raises(DeviceStateUnrecoverable):
            r.machine._rematerialize_from_mirror()
        r.recover_device_state()  # heals: rebuilt + re-armed
        assert r.machine.digest() == digest
        req(0xAA, n, wire.Operation.create_transfers,
            batch(90_000, 2).tobytes(), session)
        r.close()


class TestVoprDeviceFaults:
    def test_seed_42_sdc_scrub_on_passes_scrub_off_fails(self, tmp_path):
        """Acceptance: the pinned VOPR seed injects a device bit flip into
        a live ledger column; with scrubbing armed the run detects it,
        recovers, and finishes with the auditor green — the SAME seed with
        scrubbing disabled demonstrably fails the oracles."""
        from tigerbeetle_tpu.obs.metrics import registry
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

        registry.reset()
        registry.enable()
        try:
            on = run_seed(
                42, workdir=str(tmp_path / "on"), ticks=1200,
                settle_ticks=8000, scrub_interval=1, device_faults="sdc",
            )
            counters = registry.snapshot()["counters"]
        finally:
            registry.reset()
            registry.disable()
        assert on.exit_code == EXIT_PASSED, on
        assert counters.get("vopr.faults.device_sdc", 0) >= 1
        assert counters.get("scrub.mismatches", 0) >= 1, counters
        assert counters.get("device_recovery.recoveries", 0) >= 1

        (tmp_path / "off").mkdir()
        off = run_seed(
            42, workdir=str(tmp_path / "off"), ticks=1200,
            settle_ticks=4000, scrub_interval=0, device_faults="sdc",
        )
        assert off.exit_code != EXIT_PASSED, (
            "an unscrubbed device bit flip passed every oracle: the scrub "
            "is decorative for this seed"
        )

    def test_device_faults_off_is_bitwise_pre_fault_domain(self, tmp_path):
        """Feature-off identity: a run with the new knobs at their
        defaults must match a plain run exactly (seed stability)."""
        from tigerbeetle_tpu.sim.vopr import run_seed

        a = run_seed(77, workdir=str(tmp_path / "a"), ticks=900,
                     settle_ticks=20_000)
        (tmp_path / "b").mkdir()
        b = run_seed(77, workdir=str(tmp_path / "b"), ticks=900,
                     settle_ticks=20_000, scrub_interval=0,
                     device_faults=False)
        assert (a.exit_code, a.commits, a.ticks, a.faults, a.reason) == (
            b.exit_code, b.commits, b.ticks, b.faults, b.reason
        )


class TestVoprTpuScrub:
    def test_silent_sdc_scrubbed_model_stays_clean(self):
        from tigerbeetle_tpu.sim import vopr_tpu

        v = vopr_tpu.run(seed=3, n_clusters=96, n_steps=150, p_sdc=0.3)
        assert v.sum() == 0, f"{int(v.sum())} scrubbed-SDC violations"

    @pytest.mark.slow
    def test_scrub_off_bug_is_caught(self):
        """(@slow: test_vopr's BUGS parametrization already proves the
        catch in tier-1; this keeps a direct witness in the integration
        tier.)"""
        from tigerbeetle_tpu.sim import vopr_tpu

        v = vopr_tpu.run(
            seed=3, n_clusters=96, n_steps=150, bug="scrub_off", p_sdc=0.3
        )
        assert v.sum() > 0, "oracle missed undetected silent SDC"
