"""Merkle commitment tree (ops/merkle.py, docs/commitments.md):
differential proofs for the incremental on-device commitment forest.

Layers under test:
- ops: heap build / touched-path update / root verify against the numpy
  from-scratch oracle; proof encode/verify round trip + tamper rejection.
- machine: maintained roots == recompute-from-scratch across zipf /
  two-phase / linked mixes x TB_SHARDS {0,2} x pipeline depths {1,2};
  growth-rehash root stability; interval-0 and merkle-off identity; SDC
  detected by ROOT MISMATCH with the host mirror off (escalation to
  DeviceStateUnrecoverable), with the interval-1 paranoid mode keeping
  the mirror's in-process recovery.
- replica: checkpoint meta carries the canonical root; restores verify
  it without replay (a doctored root is rejected); wire Operation.get_proof
  round-trips through _execute.
- parallel: the vectorized canonical-view placement (_probe_place) is
  bit-identical to the scalar FCFS oracle (_probe_place_ref), including
  forced same-home and cross-group-displacement collisions.
- VOPR: the pinned seed's SDC flip is detected by root mismatch with the
  mirror off and recovered through checkpoint + WAL replay (slow tier).
"""

import random

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import TEST_MIN, LedgerConfig
from tigerbeetle_tpu.machine import DeviceStateUnrecoverable, TpuStateMachine
from tigerbeetle_tpu.ops import merkle as mk

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)
N_ACCOUNTS = 16


def accounts_batch(flags=0):
    return types.accounts_array([
        types.account(id=i + 1, ledger=1, code=10, flags=flags)
        for i in range(N_ACCOUNTS)
    ])


def plain_batch(first_id, n, zipf=False):
    rng = random.Random(first_id)
    return types.transfers_array([
        types.transfer(
            id=first_id + i,
            debit_account_id=(
                1 + min(int(rng.paretovariate(1.2)), N_ACCOUNTS - 1)
                if zipf else 1 + i % N_ACCOUNTS
            ),
            credit_account_id=1 + (i + 3) % N_ACCOUNTS,
            amount=3 + i % 5, ledger=1, code=10,
        )
        for i in range(n)
    ])


def two_phase_batches(first_id, n):
    pend = types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 5) % N_ACCOUNTS, amount=10,
            ledger=1, code=10, flags=types.TransferFlags.PENDING,
        )
        for i in range(n)
    ])
    post = types.transfers_array([
        types.transfer(
            id=first_id + 500 + i, pending_id=first_id + i, ledger=1,
            code=10,
            flags=(
                types.TransferFlags.POST_PENDING_TRANSFER if i % 2 == 0
                else types.TransferFlags.VOID_PENDING_TRANSFER
            ),
        )
        for i in range(n)
    ])
    return [pend, post]


def linked_batch(first_id, n):
    rows = []
    for i in range(n):
        rows.append(types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 2) % N_ACCOUNTS, amount=2,
            ledger=1, code=10,
            flags=types.TransferFlags.LINKED if i % 4 != 3 else 0,
        ))
    return types.transfers_array(rows)


def make_machine(merkle=True, interval=4, shards=0, paranoid=False):
    m = TpuStateMachine(CFG, batch_lanes=LANES, shards=shards)
    m.retry_tick_s = 0
    m.scrub_interval = interval
    if merkle:
        m.merkle_enabled = True
        m.scrub_paranoid = paranoid
        if interval:
            assert m.scrub_arm()
    return m


def drive_mixes(m):
    out = [m.create_accounts(accounts_batch(), wall_clock_ns=1000)]
    out.append(m.create_transfers(plain_batch(1000, 24)))
    out.append(m.create_transfers(plain_batch(2000, 20, zipf=True)))
    for b in two_phase_batches(3000, 8):
        out.append(m.create_transfers(b))
    out.append(m.create_transfers(linked_batch(5000, 12)))
    out.append(m.create_transfers(plain_batch(6000, 16)))
    return out


class TestMerkleOps:
    @pytest.mark.slow  # tier-1 budget: runs whole in the ci integration tier
    def test_build_matches_numpy_oracle(self):
        m = make_machine(merkle=False, interval=0)
        drive_mixes(m)
        forest = mk.build_forest(m.ledger)
        dev = tuple(int(r) for r in np.asarray(mk.forest_roots(forest)))
        assert dev == mk.np_ledger_roots(m.ledger)

    def test_touched_path_update_matches_rebuild(self):
        m = make_machine(merkle=False, interval=0)
        m.create_accounts(accounts_batch(), wall_clock_ns=1000)
        forest = mk.build_forest(m.ledger)
        b = plain_batch(1000, 24)
        m.create_transfers(b)
        import jax.numpy as jnp

        from tigerbeetle_tpu.ops import state_machine as sm

        def pad(a):
            buf = np.zeros(64, np.uint64)
            buf[:len(a)] = a.astype(np.uint64)
            return jnp.asarray(buf)

        forest = mk.update_transfers(
            forest, m.ledger, pad(b["id_lo"]), pad(b["id_hi"]),
            pad(np.concatenate([b["debit_account_id_lo"],
                                b["credit_account_id_lo"]])[:64]),
            pad(np.concatenate([b["debit_account_id_hi"],
                                b["credit_account_id_hi"]])[:64]),
            pad(np.zeros(0)), pad(np.zeros(0)),
            max_probe=sm.MAX_PROBE, has_postvoid=False,
        )
        lanes = np.asarray(mk.verify_roots(forest, m.ledger))
        assert (lanes[0] == lanes[1]).all(), lanes


class TestRootOracle:
    def test_root_vs_oracle_mixed_stream(self):
        """Maintained roots after plain/zipf/two-phase/linked mixes equal
        the from-scratch numpy oracle, and the results/digest are
        identical to a merkle-off machine (on-path identity); the armed
        run lands the ``merkle.*`` series."""
        from tigerbeetle_tpu.obs.metrics import registry

        off = make_machine(merkle=False, interval=0)
        res_off = drive_mixes(off)
        with registry.enabled_scope():
            on = make_machine()
            res_on = drive_mixes(on)
            assert on.scrub_check() is True
            assert on.get_proof(3)
            counters = registry.snapshot()["counters"]
        assert res_off == res_on
        assert off.digest() == on.digest()
        assert on.merkle_roots() == mk.np_ledger_roots(on.ledger)
        assert on._scrub_mirror is None  # the whole point: no mirror
        for name in ("updates", "rebuilds", "checks", "proofs"):
            assert counters.get(f"merkle.{name}", 0) >= 1, name

    def test_growth_rehash_root_stability(self):
        """Table growth rehashes every slot: the forest rebuilds and the
        roots still verify against the from-scratch oracle."""
        m = make_machine()
        cap0 = m.ledger.accounts.capacity
        for g in range(16):
            b = types.accounts_array([
                types.account(id=10_000 + 64 * g + i, ledger=1, code=10)
                for i in range(40)
            ])
            m.create_accounts(b, wall_clock_ns=1000)
        assert m.ledger.accounts.capacity > cap0, "growth did not trigger"
        assert m.scrub_check() is True
        assert m.merkle_roots() == mk.np_ledger_roots(m.ledger)
        assert m.merkle_rebuilds >= 2  # arm + post-growth

    def test_interval_zero_is_plain(self):
        """TB_SCRUB_INTERVAL=0 with merkle enabled arms nothing — results
        and digest are identical to a machine that never heard of it."""
        a = make_machine(merkle=False, interval=0)
        ra = drive_mixes(a)
        b = make_machine(merkle=True, interval=0)
        assert not b.scrub_armed and b.merkle_roots() is None
        rb = drive_mixes(b)
        assert ra == rb and a.digest() == b.digest()

    def test_deferred_and_grouped_paths(self):
        """The commitment update rides the dispatch-lane closures: deferred
        single-batch and grouped runs keep the maintained roots exact."""
        m = make_machine()
        m.create_accounts(accounts_batch(), wall_clock_ns=1000)
        handles = []
        for g in range(3):
            b = plain_batch(20_000 + g * 100, 24)
            h = m.commit_fast_deferred(
                b, m.prepare("create_transfers", len(b))
            )
            assert h is not None
            handles.append(h)
        for h in handles:
            h.resolve()
        batches = [plain_batch(30_000 + j * 100, 16) for j in range(3)]
        tss = [m.prepare("create_transfers", 16) for _ in range(3)]
        assert m.commit_group_fast(batches, tss) is not None
        assert m.scrub_check() is True
        assert m.merkle_roots() == mk.np_ledger_roots(m.ledger)


@pytest.mark.slow
class TestRootOracleMatrix:
    """The full acceptance matrix (slow: sharded compiles) — runs whole in
    the ci integration tier."""

    @pytest.mark.parametrize("shards", [0, 2])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_mixes_by_shards_and_depth(self, shards, depth):
        m = make_machine(shards=shards)
        m.pipeline_depth = depth
        res = drive_mixes(m)
        if depth > 1 and not shards:
            # Depth > 1 single-device: the tail of the stream rides the
            # deferred dispatch lane (sharded commits are blocking by
            # design — grouped/deferred stacking over the mesh is the
            # documented follow-up).
            for g in range(2):
                b = plain_batch(40_000 + g * 100, 16)
                h = m.commit_fast_deferred(
                    b, m.prepare("create_transfers", len(b))
                )
                assert h is not None
                res.append(h.resolve()[0])
        ref = make_machine(merkle=False, interval=0, shards=0)
        ref_res = drive_mixes(ref)
        if depth > 1 and not shards:
            for g in range(2):
                b = plain_batch(40_000 + g * 100, 16)
                ref_res.append(ref.create_transfers(b))
        assert ref_res == res
        assert m.digest() == ref.digest()
        assert m.scrub_check() is True
        if shards:
            assert m.merkle_canonical_roots() == mk.np_ledger_roots(
                m._query_ledger()
            )
        else:
            assert m.merkle_roots() == mk.np_ledger_roots(m.ledger)

    def test_sharded_sdc_detected(self):
        m = make_machine(shards=2, interval=1)
        drive_mixes(m)
        assert m.inject_sdc_bitflip(random.Random(11))
        with pytest.raises(DeviceStateUnrecoverable):
            m.scrub_check()
        assert m.merkle_mismatches == 1


class TestMerkleProofs:
    def test_round_trip_and_tamper(self):
        m = make_machine()
        drive_mixes(m)
        blob = m.get_proof(3)
        proof = mk.check_proof(blob)
        assert int(proof["account"]["id_lo"]) == 3
        assert proof["root"] == m.merkle_roots()[0]
        # every single-byte flip in the row or path must be rejected
        for off in (mk.PROOF_HEADER_DTYPE.itemsize + 2, len(blob) - 3):
            bad = bytearray(blob)
            bad[off] ^= 1
            with pytest.raises(mk.ProofError):
                mk.check_proof(bytes(bad))

    def test_absent_account_and_merkle_off(self):
        m = make_machine()
        m.create_accounts(accounts_batch(), wall_clock_ns=1000)
        assert m.get_proof(999_999) is None
        off = make_machine(merkle=False, interval=0)
        off.create_accounts(accounts_batch(), wall_clock_ns=1000)
        assert off.get_proof(1) is None

    def test_transfer_proof_roundtrip_and_tamper(self):
        m = make_machine()
        drive_mixes(m)
        blob = m.get_proof(1000, kind="transfers")
        proof = mk.check_proof(blob)
        assert proof["kind"] == "transfers"
        assert int(proof["row"]["id_lo"]) == 1000
        assert proof["root"] == m.merkle_roots()[1]
        # Flip bytes in hash-bound columns (id, amount), in a column the
        # leaf does NOT cover (debit_account_id — rides as canonical
        # zero, pinned by the verifier), and in the sibling path: every
        # single-byte tamper must be rejected.
        head = mk.PROOF_HEADER_DTYPE.itemsize
        dr_off = types.TRANSFER_DTYPE.fields["debit_account_id_lo"][1]
        for off in (head + 2, head + dr_off, len(blob) - 3):
            bad = bytearray(blob)
            bad[off] ^= 1
            with pytest.raises(mk.ProofError):
                mk.check_proof(bytes(bad))
        # The row's uncommitted columns are the canonical projection:
        # all zero in the blob (nothing forgeable rides along).
        assert int(proof["row"]["debit_account_id_lo"]) == 0
        assert int(proof["row"]["ledger"]) == 0
        # A kind swap in the header must not verify either (the leaf
        # hash domain differs per pad).
        bad = bytearray(blob)
        bad[20] ^= 1  # the kind field (header offset 20)
        with pytest.raises(mk.ProofError):
            mk.check_proof(bytes(bad))

    def test_posted_proof_binds_pending(self):
        """A posted-row proof anchors pending transfer 3000's fulfillment
        to the posted root; its pending_timestamp equals the timestamp in
        the transfer's OWN proof row — the client-side binding."""
        m = make_machine()
        drive_mixes(m)
        pb = m.get_proof(3000, kind="posted")  # posted (i % 2 == 0)
        pp = mk.check_proof(pb)
        assert pp["kind"] == "posted"
        assert int(pp["row"]["fulfillment"]) == 1  # posted, not voided
        assert pp["root"] == m.merkle_roots()[2]
        tp = mk.check_proof(m.get_proof(3000, kind="transfers"))
        assert int(tp["row"]["timestamp"]) == int(
            pp["row"]["pending_timestamp"]
        )
        vb = mk.check_proof(m.get_proof(3001, kind="posted"))
        assert int(vb["row"]["fulfillment"]) == 2  # voided
        # Tampers: the key, the fulfillment word, the RESERVED pad
        # (unhashed — pinned to canonical zero), and a sibling.
        head = mk.PROOF_HEADER_DTYPE.itemsize
        for off in (head + 1, head + 8, head + 12, len(pb) - 2):
            bad = bytearray(pb)
            bad[off] ^= 1
            with pytest.raises(mk.ProofError):
                mk.check_proof(bytes(bad))

    def test_proof_kind_misses(self):
        m = make_machine()
        drive_mixes(m)
        assert m.get_proof(999_999, kind="transfers") is None
        # 1000 is a plain transfer: no posted row exists for it.
        assert m.get_proof(1000, kind="posted") is None
        with pytest.raises(ValueError):
            m.get_proof(1, kind="history")

    @pytest.mark.slow
    def test_proof_kinds_sharded(self):
        """Transfer/posted proofs under TB_SHARDS anchor to the CANONICAL
        per-pad trees (same roots as the wrap-summed live subtrees after
        a clean settle) and verify client-side."""
        m = make_machine(shards=2)
        drive_mixes(m)
        tp = mk.check_proof(m.get_proof(2000, kind="transfers"))
        assert int(tp["row"]["id_lo"]) == 2000
        pp = mk.check_proof(m.get_proof(3002, kind="posted"))
        assert int(pp["row"]["fulfillment"]) == 1
        canon = mk.np_ledger_roots(m._query_ledger())
        assert tp["root"] == canon[1] and pp["root"] == canon[2]

    def test_wire_get_proof(self, tmp_path):
        """Operation.get_proof through the replica's execute path: a
        verifying proof for a live account, empty replies for absent ids."""
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        path = str(tmp_path / "proof.tb")
        Replica.format(path, cluster=5, cluster_config=TEST_MIN)
        r = Replica(
            path, cluster_config=TEST_MIN, ledger_config=CFG,
            batch_lanes=LANES, time_ns=lambda: 0, scrub_interval=4,
            merkle=True,
        )
        r.open()
        try:
            r.machine.scrub_paranoid = False
            assert r.machine.scrub_arm()
            r.machine.commit_batch(
                "create_accounts", accounts_batch(),
                r.machine.prepare("create_accounts", N_ACCOUNTS),
            )
            body = r._execute_inner(
                wire.Operation.get_proof,
                (3).to_bytes(16, "little"), 0,
            )
            proof = mk.check_proof(body)
            assert int(proof["account"]["id_lo"]) == 3
            empty = r._execute_inner(
                wire.Operation.get_proof,
                (424242).to_bytes(16, "little"), 0,
            )
            assert empty == b""
            # 24-byte body: id + u64 kind selector (1 = transfers).
            r.machine.commit_batch(
                "create_transfers", plain_batch(7000, 4),
                r.machine.prepare("create_transfers", 4),
            )
            tbody = r._execute_inner(
                wire.Operation.get_proof,
                (7000).to_bytes(16, "little") + (1).to_bytes(8, "little"),
                0,
            )
            tproof = mk.check_proof(tbody)
            assert tproof["kind"] == "transfers"
            assert int(tproof["row"]["id_lo"]) == 7000
            # An unknown kind must be rejected BEFORE journaling (every
            # journaled prepare must replay).
            from tigerbeetle_tpu.vsr.replica import InvalidRequest

            with pytest.raises(InvalidRequest):
                r._validate_request(
                    wire.Operation.get_proof,
                    (1).to_bytes(16, "little") + (9).to_bytes(8, "little"),
                )
            r._validate_request(
                wire.Operation.get_proof,
                (1).to_bytes(16, "little") + (2).to_bytes(8, "little"),
            )
        finally:
            r.close()


class TestMerkleSdc:
    def test_root_mismatch_with_mirror_off(self):
        """The acceptance bar: a device bit flip is detected by ROOT
        MISMATCH with no host mirror armed; recovery escalates to the
        replica's durable-state rebuild."""
        m = make_machine(interval=1)
        assert m._scrub_mirror is None
        drive_mixes(m)
        assert m.inject_sdc_bitflip(random.Random(7))
        with pytest.raises(DeviceStateUnrecoverable):
            m.scrub_check()
        assert m.merkle_mismatches == 1 and m.scrub_mismatches == 1

    def test_deferred_dispatch_fault_escalates_not_crashes(self):
        """Merkle-only mode has no mirror to re-dispatch from: a device
        fault surfacing at a deferred handle's resolve must escalate as
        DeviceStateUnrecoverable (the replica's settle path routes that
        into checkpoint + WAL replay) — never the raw device error."""
        m = make_machine(interval=4)
        m.create_accounts(accounts_batch(), wall_clock_ns=1000)
        b = plain_batch(70_000, 16)
        h = m.commit_fast_deferred(b, m.prepare("create_transfers", len(b)))
        assert h is not None
        m.inject_device_faults(1)  # fires at the deferred codes readback
        with pytest.raises(DeviceStateUnrecoverable):
            h.resolve()

    def test_paranoid_interval_keeps_mirror_and_recovers(self):
        """TB_SCRUB_INTERVAL=1 default: the mirror rides along and a flip
        recovers IN PROCESS (quarantine + re-materialize), after which
        the rebuilt forest verifies again."""
        m = make_machine(interval=1, paranoid=True)
        assert m._scrub_mirror is not None and m.merkle_armed
        drive_mixes(m)
        assert m.inject_sdc_bitflip(random.Random(7))
        assert m.scrub_check() is False  # detected + recovered
        assert m.device_recoveries == 1
        assert m.scrub_check() is True
        assert m.merkle_roots() == mk.np_ledger_roots(m.ledger)


class TestCheckpointRoot:
    def test_checkpoint_carries_and_verifies_root(self, tmp_path):
        """Checkpoints serialize the canonical root; a restore recomputes
        and verifies it WITHOUT replay, and a doctored root is rejected."""
        from tigerbeetle_tpu.vsr.replica import Replica

        path = str(tmp_path / "root.tb")
        Replica.format(path, cluster=5, cluster_config=TEST_MIN)
        r = Replica(
            path, cluster_config=TEST_MIN, ledger_config=CFG,
            batch_lanes=LANES, time_ns=lambda: 0, scrub_interval=4,
            merkle=True,
        )
        r.open()
        r.machine.scrub_paranoid = False
        assert r.machine.scrub_arm()
        r.machine.commit_batch(
            "create_accounts", accounts_batch(),
            r.machine.prepare("create_accounts", N_ACCOUNTS),
        )
        r.commit_min = r.op = 1
        r.checkpoint()
        arrays_roots = r.machine.merkle_canonical_roots()
        r.close()

        r2 = Replica(
            path, cluster_config=TEST_MIN, ledger_config=CFG,
            batch_lanes=LANES, time_ns=lambda: 0, scrub_interval=4,
            merkle=True,
        )
        r2.open()  # restore path verifies the root (no raise == verified)
        try:
            assert r2.machine.scrub_armed
            assert r2.machine.merkle_canonical_roots() == arrays_roots
            # Doctored meta: the install-time verifier must reject it.
            loaded = r2._load_checkpoint_state(r2._sb_state)
            assert loaded is not None
            ledger, meta = loaded
            meta = dict(meta)
            meta["merkle_root"] = dict(meta["merkle_root"])
            meta["merkle_root"]["accounts"] ^= 1
            with pytest.raises(RuntimeError, match="merkle root mismatch"):
                r2._install_checkpoint_ledger(ledger, meta, r2._sb_state)
        finally:
            r2.close()


class TestProbePlaceVectorized:
    """Satellite (ROADMAP item 1 follow-up): the canonical-view rebuild's
    vectorized FCFS placement is bit-identical to the scalar oracle."""

    def test_parity_random_and_adversarial(self):
        from tigerbeetle_tpu.parallel import sharded as sh

        rng = np.random.default_rng(7)
        for trial in range(60):
            cap = [64, 256][trial % 2]
            nregions = [1, 4][(trial // 2) % 2]
            local = cap // nregions
            # <= half-full PER REGION (the production load policy): an
            # overfull region has no free slot and both placements would
            # legitimately probe forever.
            n = int(rng.integers(1, cap // 2 + 1))
            homes = rng.integers(
                0, max(2, local // 8) if trial % 3 == 0 else local, n
            ).astype(np.uint64)
            base = (
                rng.integers(0, nregions, n) % nregions * local
            ).astype(np.int64)
            counts = np.bincount(base // local, minlength=nregions)
            if counts.max() > local // 2:
                continue  # skewed draw would exceed the region policy
            ref = sh._probe_place_ref(homes, base, local - 1, cap)
            vec = sh._probe_place(homes, base, local - 1, cap)
            assert (ref == vec).all(), trial

    def test_cross_group_displacement_case(self):
        """The FCFS-vs-batched-claim divergence case: a displaced earlier
        row steals the slot a later row homes at — sequential order must
        win (X(h5) r0 -> 5, A(h5) r1 -> 6, B(h6) r2 -> 7)."""
        from tigerbeetle_tpu.parallel import sharded as sh

        homes = np.array([5, 5, 6], np.uint64)
        base = np.zeros(3, np.int64)
        assert list(sh._probe_place(homes, base, 63, 64)) == [5, 6, 7]
        # wrap-around at the region edge
        homes = np.array([63, 63, 63, 0], np.uint64)
        ref = sh._probe_place_ref(homes, base[:1].repeat(4), 63, 64)
        vec = sh._probe_place(homes, np.zeros(4, np.int64), 63, 64)
        assert (ref == vec).all()

    def test_empty(self):
        from tigerbeetle_tpu.parallel import sharded as sh

        assert len(sh._probe_place(
            np.zeros(0, np.uint64), np.zeros(0, np.int64), 63, 64
        )) == 0


@pytest.mark.slow
class TestVoprMerkle:
    def test_seed_42_sdc_detected_by_root_mismatch_mirror_off(self, tmp_path):
        """Acceptance (ROADMAP 3): the pinned VOPR seed's device bit flip
        is detected by commitment-root mismatch with the host mirror OFF
        and recovered through checkpoint + WAL replay — auditor green."""
        from tigerbeetle_tpu.obs.metrics import registry
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

        registry.reset()
        registry.enable()
        try:
            on = run_seed(
                42, workdir=str(tmp_path / "on"), ticks=1200,
                settle_ticks=8000, scrub_interval=1, merkle=True,
                device_faults="sdc",
            )
            counters = registry.snapshot()["counters"]
        finally:
            registry.reset()
            registry.disable()
        assert on.exit_code == EXIT_PASSED, on
        assert counters.get("vopr.faults.device_sdc", 0) >= 1
        assert counters.get("merkle.mismatches", 0) >= 1, counters
        assert counters.get("device_recovery.wal_replays", 0) >= 1, counters
