"""The deferred commitment lane (TB_MERKLE_ASYNC; docs/commitments.md).

The knob is perf-only by contract and default-off: the Merkle path refresh
trails the dispatch closure in a commitment lane; every root observation
(scrub, checkpoint, get_proof, state-sync) settles first, so observed roots
are exactly the synchronous ones.

Covered here: the touch-record coalescer, machine-level
settle-before-observe, replica-level differentials vs testing/model.py and
the lane-off replica across conflicting / non-conflicting / zipf /
two-phase mixes at TB_PIPELINE {1,2} x TB_SHARDS {0,2}, and the pinned VOPR
seed under the knob (@slow).
"""

import os

import jax
import numpy as np
import pytest

from tigerbeetle_tpu import jaxenv, types
from tigerbeetle_tpu.config import TEST_MIN, LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.ops import merkle as merkle_ops
from tigerbeetle_tpu.testing import model as M

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)
N_ACCOUNTS = 16


def _need_devices(n):
    if n and len(jax.devices()) < n:
        pytest.skip(
            f"needs {n} devices, have {len(jax.devices())} "
            f"(jaxenv degraded: {jaxenv.DEGRADED_DEVICE_COUNT})"
        )


def accounts_batch():
    return types.accounts_array([
        types.account(id=i + 1, ledger=1, code=10)
        for i in range(N_ACCOUNTS)
    ])


def disjoint_batch(first_id, n, client, per=4):
    """Transfers confined to client's own account partition: no account
    is shared across clients."""
    lo = client * per
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + lo + i % per,
            credit_account_id=1 + lo + (i + 1) % per,
            amount=1 + i % 7, ledger=1, code=10,
        )
        for i in range(n)
    ])


def shared_batch(first_id, n):
    """Transfers over the SHARED pool: every client's batch touches
    every account."""
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 3) % N_ACCOUNTS,
            amount=2 + i % 5, ledger=1, code=10,
        )
        for i in range(n)
    ])


def two_phase_batch(first_id, n):
    """In-batch pending + post pairs: not fast-path eligible, so a run
    of them is refused whole and executes inline on the general kernel —
    and must still match the oracle."""
    half = n // 2
    return types.transfers_array(
        [
            types.transfer(
                id=first_id + i, debit_account_id=1 + i % 8,
                credit_account_id=9 + i % 8, amount=20, ledger=1, code=10,
                flags=types.TransferFlags.PENDING,
            )
            for i in range(half)
        ] + [
            types.transfer(
                id=first_id + half + i, pending_id=first_id + i, ledger=1,
                code=10, flags=types.TransferFlags.POST_PENDING_TRANSFER,
            )
            for i in range(half)
        ]
    )


def zipf_batch(first_id, n, seed):
    """Zipfian-hot plain transfers: heavy account overlap on the fast
    path."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        dr = 1 + int(N_ACCOUNTS * rng.random() ** 3) % N_ACCOUNTS
        cr = 1 + (dr + 1 + int(3 * rng.random())) % N_ACCOUNTS
        rows.append(types.transfer(
            id=first_id + i, debit_account_id=dr, credit_account_id=cr,
            amount=1 + int(rng.random() * 50), ledger=1, code=10,
        ))
    return types.transfers_array(rows)


# -- coalesce units ----------------------------------------------------------


class TestCoalesceTouchRecords:
    def test_consecutive_transfers_coalesce_ordered(self):
        ct = "create_transfers"
        recs = [
            (ct, np.arange(3)), (ct, np.arange(4)),
            ("create_accounts", np.arange(2)),
            (ct, np.arange(5)), (ct, np.arange(5)),
        ]
        out = [
            (op, [len(b) for b in bs])
            for op, bs in merkle_ops.coalesce_touch_records(recs, max_rows=8)
        ]
        assert out == [
            (ct, [3, 4]), ("create_accounts", [2]), (ct, [5]), (ct, [5]),
        ]

    def test_large_window_coalesces_across(self):
        ct = "create_transfers"
        recs = [(ct, np.arange(3)), (ct, np.arange(4)), (ct, np.arange(5))]
        out = list(merkle_ops.coalesce_touch_records(recs, max_rows=100))
        assert len(out) == 1 and [len(b) for b in out[0][1]] == [3, 4, 5]


# -- machine-level deferred lane -------------------------------------------


def make_machine(merkle=True, shards=0):
    m = TpuStateMachine(CFG, batch_lanes=LANES, shards=shards)
    assert m.create_accounts(accounts_batch(), wall_clock_ns=1000) == []
    if merkle:
        m.merkle_enabled = True
        m.scrub_interval = 1_000_000  # settle barriers drive the lane
        m.scrub_paranoid = False
        assert m.scrub_arm()
    return m


class TestDeferredLane:
    def test_settle_identity_and_coalescing(self):
        sync = make_machine()
        lane = make_machine()
        lane.merkle_async = True
        for first in (10_000, 20_000, 30_000):
            b = shared_batch(first, 12)
            ts = sync.prepare("create_transfers", 12, 0)
            sync.commit_batch("create_transfers", b, ts)
            tl = lane.prepare("create_transfers", 12, 0)
            lane.commit_batch("create_transfers", b, tl)
        updates_sync = sync.merkle_updates
        assert lane._merkle_pending and lane.merkle_updates < updates_sync
        lane.merkle_settle()
        assert not lane._merkle_pending
        # Coalesced: 3 batches of 12 fit one 36-row (padded) refresh.
        assert lane.merkle_updates < updates_sync
        assert lane.merkle_roots() == sync.merkle_roots()
        assert lane.digest() == sync.digest()
        assert lane._merkle_verify() and sync._merkle_verify()

    def test_commitment_root_sentinel_then_settled(self):
        sync = make_machine()
        lane = make_machine()
        lane.merkle_async = True
        b = shared_batch(40_000, 10)
        ts = sync.prepare("create_transfers", 10, 0)
        sync.commit_batch("create_transfers", b, ts)
        tl = lane.prepare("create_transfers", 10, 0)
        lane.commit_batch("create_transfers", b, tl)
        # Backlogged lane: the per-reply stamp is the skippable sentinel —
        # never a stale root, never a serving-thread settle.
        assert lane._merkle_pending
        assert lane.commitment_root() == 0
        assert lane._merkle_pending  # stamping did NOT settle
        lane.merkle_settle()
        assert lane.commitment_root() == sync.commitment_root() != 0

    def test_get_proof_settles_before_anchoring(self):
        sync = make_machine()
        lane = make_machine()
        lane.merkle_async = True
        b = shared_batch(50_000, 10)
        ts = sync.prepare("create_transfers", 10, 0)
        sync.commit_batch("create_transfers", b, ts)
        tl = lane.prepare("create_transfers", 10, 0)
        lane.commit_batch("create_transfers", b, tl)
        assert lane._merkle_pending
        got = lane.get_proof(1)
        assert not lane._merkle_pending  # proof observation settled
        assert got == sync.get_proof(1)
        parsed = merkle_ops.check_proof(got)  # raises unless it folds
        assert parsed["root"] in lane.merkle_roots()

    def test_scrub_observes_settled_roots_only(self):
        lane = make_machine()
        lane.merkle_async = True
        b = shared_batch(60_000, 10)
        tl = lane.prepare("create_transfers", 10, 0)
        lane.commit_batch("create_transfers", b, tl)
        assert lane._merkle_pending
        assert lane.scrub_check()  # green: verify settles first
        assert not lane._merkle_pending

    def test_rebuild_clears_pending(self):
        lane = make_machine()
        lane.merkle_async = True
        b = shared_batch(70_000, 10)
        tl = lane.prepare("create_transfers", 10, 0)
        lane.commit_batch("create_transfers", b, tl)
        assert lane._merkle_pending
        lane._merkle_dirty = True
        assert lane._merkle_rebuild_if_dirty()
        assert not lane._merkle_pending  # the rebuild subsumed the queue
        assert lane._merkle_verify()

    def test_knob_off_setter_drains(self):
        lane = make_machine()
        lane.merkle_async = True
        b = shared_batch(80_000, 10)
        tl = lane.prepare("create_transfers", 10, 0)
        lane.commit_batch("create_transfers", b, tl)
        assert lane._merkle_pending
        lane.merkle_async = False
        assert not lane._merkle_pending

    def test_lane_metrics(self):
        with registry.enabled_scope():
            lane = make_machine()
            lane.merkle_async = True
            for first in (90_000, 91_000):
                b = shared_batch(first, 8)
                tl = lane.prepare("create_transfers", 8, 0)
                lane.commit_batch("create_transfers", b, tl)
            lane.merkle_settle()
            snap = registry.snapshot()
            assert snap["counters"]["merkle.lane.deferred_updates"] == 2
            assert snap["counters"]["merkle.lane.settle_waits"] == 1
            lag = snap["histograms"]["merkle.lane.lag_batches"]
            assert lag["count"] == 1 and lag["max"] == 2


# -- replica-level differentials -------------------------------------------


class ReplicaHarness:
    """A solo replica served through on_request_group_pipelined, clock
    pinned so reply bytes compare across knob settings (the
    test_async_sharded harness, with the lane's knob on the machine)."""

    def __init__(self, tmp, name, depth, shards=0,
                 merkle_async=False, merkle=False):
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        self.wire = wire
        path = os.path.join(tmp, f"{name}.tb")
        Replica.format(path, cluster=5, cluster_config=TEST_MIN)
        self.r = Replica(
            path, cluster_config=TEST_MIN, ledger_config=CFG,
            batch_lanes=LANES, time_ns=lambda: 0,
            scrub_interval=1_000_000 if merkle else None,
            merkle=True if merkle else None,
        )
        if shards:
            self.r.machine = TpuStateMachine(
                CFG, batch_lanes=LANES, shards=shards,
                spill_dir=path + ".cold",
            )
            if merkle:
                self.r.machine.scrub_interval = 1_000_000
                self.r.machine.merkle_enabled = True
                self.r.machine.scrub_paranoid = False
        self.r.open()
        self.r.pipeline_depth = depth
        self.r.machine.merkle_async = merkle_async
        self.sessions = {}

    def request(self, client, request_n, op, body):
        wire = self.wire
        h = wire.new_header(
            wire.Command.request, cluster=5, client=client,
            request=request_n, session=self.sessions.get(client, 0),
            operation=int(op),
        )
        h["size"] = wire.HEADER_SIZE + len(body)
        return wire.set_checksums(h, body), body

    def register(self, client):
        wire = self.wire
        replies, fs = self.r.on_request_group_pipelined(
            [self.request(client, 0, wire.Operation.register, b"")]
        )
        if fs is not None:
            fs.result()
        rh, _ = wire.decode_header(replies[0][0][:wire.HEADER_SIZE])
        self.sessions[client] = int(rh["commit"])

    def setup_accounts(self, client):
        wire = self.wire
        replies, fs = self.r.on_request_group_pipelined([self.request(
            client, 1, wire.Operation.create_accounts,
            accounts_batch().tobytes(),
        )])
        if fs is not None:
            fs.result()
        assert replies[0][0][256:] == b"", "account setup failed"

    def serve_groups(self, groups):
        """Serve groups of per-client transfer batches; returns reply
        result bodies in request order."""
        wire = self.wire
        clients = [0x500 + i for i in range(max(len(g) for g in groups))]
        for c in clients:
            self.register(c)
        self.setup_accounts(clients[0])
        bodies = []
        for gi, group in enumerate(groups):
            reqs = [
                self.request(clients[k], gi + 2,
                             wire.Operation.create_transfers, b.tobytes())
                for k, b in enumerate(group)
            ]
            replies, fs = self.r.on_request_group_pipelined(reqs)
            if fs is not None:
                fs.result()
            for rl in replies:
                assert rl, "request dropped"
                bodies.append(rl[0][256:])
        return bodies

    def close(self):
        self.r.close()


def _mix_groups(mix):
    if mix == "disjoint":
        return [
            [disjoint_batch(10_000 * (c + 1) + g * 100, 10, client=c)
             for c in range(4)]
            for g in range(3)
        ]
    if mix == "conflicting":
        return [
            [shared_batch(10_000 * (c + 1) + g * 100, 10) for c in range(4)]
            for g in range(3)
        ]
    if mix == "two_phase":
        return [
            [two_phase_batch(10_000 * (c + 1) + g * 100, 8)
             for c in range(3)]
            for g in range(2)
        ]
    assert mix == "zipf"
    return [
        [zipf_batch(10_000 * (c + 1) + g * 100, 10, seed=7 * g + c)
         for c in range(4)]
        for g in range(3)
    ]


def _check_against_model(groups, bodies):
    ref = M.ReferenceStateMachine()
    assert ref.create_accounts(
        [M.account_from_row(r) for r in accounts_batch()], 0
    ) == []
    flat = [b for g in groups for b in g]
    assert len(flat) == len(bodies)
    for batch_arr, body in zip(flat, bodies):
        want = ref.create_transfers(
            [M.transfer_from_row(r) for r in batch_arr]
        )
        arr = np.frombuffer(body, dtype=types.EVENT_RESULT_DTYPE)
        got = [(int(e["index"]), int(e["result"])) for e in arr]
        assert got == want
    return ref


MIXES = ["disjoint", "conflicting", "two_phase", "zipf"]


class TestLaneDifferential:
    @pytest.mark.parametrize("mix", MIXES)
    @pytest.mark.parametrize("depth", [1, 2])
    def test_vs_model_and_off_path(self, tmp_path, depth, mix):
        """Serving with the lane on matches the scalar oracle AND the
        lane-off replica bit for bit (replies + digest + balances) at
        every depth x mix point — single device."""
        self._run_cell(str(tmp_path), depth, 0, mix)

    @pytest.mark.parametrize("mix", ["disjoint", "two_phase"])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_vs_model_and_off_path_sharded(self, tmp_path, depth, mix):
        _need_devices(2)
        self._run_cell(str(tmp_path), depth, 2, mix)

    @staticmethod
    def _run_cell(tmp, depth, shards, mix):
        groups = _mix_groups(mix)
        off = ReplicaHarness(tmp, f"off_{depth}_{shards}_{mix}", depth,
                             shards=shards)
        bodies_off = off.serve_groups(groups)
        digest_off = off.r.machine.digest()
        balances_off = off.r.machine.balances_snapshot()
        off.close()
        on = ReplicaHarness(tmp, f"on_{depth}_{shards}_{mix}", depth,
                            shards=shards, merkle_async=True, merkle=True)
        bodies_on = on.serve_groups(groups)
        assert bodies_on == bodies_off
        assert on.r.machine.digest() == digest_off
        assert on.r.machine.balances_snapshot() == balances_off
        # The deferred lane settles at close/checkpoint barriers; verify
        # the maintained forest agrees with the recomputed roots.
        assert on.r.machine._merkle_verify()
        on.close()
        _check_against_model(groups, bodies_off)

    def test_lane_alone_engages_and_matches_off(self, tmp_path):
        """The lane ON serves the off path's bytes, and engages: the mix
        must drive merkle.lane.deferred_updates — otherwise the
        differentials above prove nothing."""
        tmp, groups = str(tmp_path), _mix_groups("disjoint")
        off = ReplicaHarness(tmp, "off", 2)
        want = off.serve_groups(groups), off.r.machine.digest()
        off.close()
        with registry.enabled_scope():
            h = ReplicaHarness(tmp, "async", 2, merkle_async=True,
                               merkle=True)
            got = h.serve_groups(groups), h.r.machine.digest()
            h.close()
            snap = registry.snapshot()
        assert got == want
        assert snap["counters"]["merkle.lane.deferred_updates"] > 0


@pytest.mark.slow
class TestVoprDeferredLane:
    def test_pinned_seed_green_with_the_lane(self, tmp_path, monkeypatch):
        """The pinned VOPR seed replays green with TB_MERKLE_ASYNC=1:
        every scrub/checkpoint oracle observes settled roots only."""
        monkeypatch.setenv("TB_MERKLE_ASYNC", "1")
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

        result = run_seed(42, workdir=str(tmp_path), ticks=3_000)
        assert result.exit_code == EXIT_PASSED, result.summary
