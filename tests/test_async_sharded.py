"""Async sharded commit engine (ISSUE 11; docs/commit_pipeline.md +
docs/sharding.md composition sections): the TB_PIPELINE deferred-dispatch
lane composed with the TB_SHARDS mesh commit path.

The composition must be INVISIBLE in results: deferred/grouped sharded
commits (the dispatch-lane FIFO driving the cached sharded.machine_steps
fast_probed program, readbacks deferred through DeviceCommitHandle)
produce byte-identical replies, digests, and balances to the blocking
path at every (depth x shards x merkle) point, checked against each other
AND against the scalar oracle (testing/model.py).  The pinned VOPR seed
must stay green under the composed TB_PIPELINE=2 x TB_SHARDS=2 mode.

Heavy cells (sharded shard_map compiles) are @slow and listed in the ci
integration tier (tier-1 budget discipline); the fast cells cover the
engine mechanics that need no mesh.
"""

import concurrent.futures

import jax
import numpy as np
import pytest

from tigerbeetle_tpu import jaxenv, types
from tigerbeetle_tpu.config import TEST_MIN, LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine, _overflow_any
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


def batch(first_id, n, amount=3, flags=0):
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 3) % N_ACCOUNTS,
            amount=amount + i % 5, ledger=1, code=10, flags=flags,
        )
        for i in range(n)
    ])


def linked_batch(first_id, n):
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 2) % N_ACCOUNTS, amount=2,
            ledger=1, code=10,
            flags=types.TransferFlags.LINKED if i % 3 != 2 else 0,
        )
        for i in range(n)
    ])


def make_machine(shards=0, merkle=False):
    m = TpuStateMachine(CFG, batch_lanes=LANES, shards=shards)
    if shards:
        assert m.shards == shards
    assert m.create_accounts(accounts_batch(), wall_clock_ns=1000) == []
    if merkle:
        m.merkle_enabled = True
        m.scrub_interval = 4
        m.scrub_paranoid = False
        assert m.scrub_arm()
    return m


def make_model(wall_clock_ns=1000):
    ref = M.ReferenceStateMachine()
    assert ref.create_accounts(
        [M.account_from_row(r) for r in accounts_batch()], wall_clock_ns
    ) == []
    return ref


# -- fast cells: engine mechanics, no mesh ---------------------------------


def test_overflow_any_shapes():
    assert not _overflow_any(np.uint32(0))
    assert _overflow_any(np.uint32(1))
    assert not _overflow_any(np.zeros(4, np.uint32))
    assert _overflow_any(np.array([0, 0, 1, 0], np.uint32))
    assert not _overflow_any((np.uint32(0), np.zeros(2, np.uint32)))
    assert _overflow_any((np.zeros(2, np.uint32), np.uint32(1)))
    assert not _overflow_any(())


def test_deferred_inflight_occupancy():
    """The machine tracks commit-lane occupancy: deferred submits raise
    it, resolves (in FIFO order) drop it — the pipeline.shard.inflight
    substrate."""
    m = make_machine()
    assert m._deferred_inflight == 0
    handles = []
    for first in (10_000, 20_000):
        ts = m.prepare("create_transfers", 8, 0)
        h = m.commit_fast_deferred(batch(first, 8), ts)
        assert h is not None
        handles.append(h)
    assert m._deferred_inflight == 2
    assert handles[0].resolve() == [[]]
    assert m._deferred_inflight == 1
    assert handles[1].resolve() == [[]]
    assert m._deferred_inflight == 0


def test_discard_drops_occupancy():
    m = make_machine()
    ts = m.prepare("create_transfers", 4, 0)
    h = m.commit_fast_deferred(batch(30_000, 4), ts)
    assert h is not None and m._deferred_inflight == 1
    h.discard()
    assert m._deferred_inflight == 0


# -- slow cells: the composed matrix (sharded compiles) --------------------


@pytest.mark.slow
class TestMachineComposition:
    """Machine-level differentials: deferred (and grouped-deferred)
    commits through the sharded fast_probed lane vs the blocking path vs
    the scalar oracle."""

    @pytest.mark.parametrize("merkle", [False, True])
    @pytest.mark.parametrize("shards", [0, 2])
    def test_deferred_matches_blocking_and_model(self, shards, merkle):
        _need_devices(shards)
        blocking = make_machine(shards=shards, merkle=merkle)
        deferred = make_machine(shards=shards, merkle=merkle)
        ref = make_model()
        batches = [
            batch(10_000, 20), batch(20_000, 24, amount=5),
            batch(10_000, 20),  # duplicate ids: rejected lanes
            batch(30_000, 17),
        ]
        b_res = [blocking.create_transfers(b) for b in batches]
        handles = []
        for b in batches:
            ts = deferred.prepare("create_transfers", len(b), 0)
            h = deferred.commit_fast_deferred(b, ts)
            assert h is not None, "deferred dispatch refused"
            handles.append(h)
        d_res = [h.resolve()[0] for h in handles]
        assert d_res == b_res
        for b, got in zip(batches, b_res):
            want = ref.create_transfers(
                [M.transfer_from_row(r) for r in b]
            )
            assert got == want
        assert blocking.digest() == deferred.digest()
        assert (
            blocking.balances_snapshot()
            == deferred.balances_snapshot()
            == ref.balances_snapshot()
        )
        if merkle:
            assert blocking.merkle_roots() == deferred.merkle_roots()
            assert blocking.scrub_check()

    @pytest.mark.parametrize("shards", [0, 2])
    def test_group_deferred_matches_blocking(self, shards):
        _need_devices(shards)
        blocking = make_machine(shards=shards)
        grouped = make_machine(shards=shards)
        batches = [batch(10_000, 12), batch(20_000, 9), batch(30_000, 15)]
        b_res = [blocking.create_transfers(b) for b in batches]
        tss = [
            grouped.prepare("create_transfers", len(b), 0) for b in batches
        ]
        handle = grouped.commit_group_fast(batches, tss, deferred=True)
        assert handle is not None, "grouped sharded run refused"
        assert handle.resolve() == b_res
        assert blocking.digest() == grouped.digest()
        assert blocking.balances_snapshot() == grouped.balances_snapshot()

    def test_refused_batch_falls_back_identically(self):
        """A linked batch is not fast-path eligible: the deferred entry
        refuses (balance bound restored), the caller's blocking fallback
        commits it — same results as the all-blocking machine, sharded."""
        _need_devices(2)
        blocking = make_machine(shards=2)
        mixed = make_machine(shards=2)
        lb = linked_batch(40_000, 9)
        b1 = blocking.create_transfers(batch(10_000, 8))
        b2 = blocking.create_transfers(lb)
        ts = mixed.prepare("create_transfers", 8, 0)
        h = mixed.commit_fast_deferred(batch(10_000, 8), ts)
        assert h is not None
        assert h.resolve()[0] == b1
        bound0 = mixed._balance_bound
        ts = mixed.prepare("create_transfers", len(lb), 0)
        assert mixed.commit_fast_deferred(lb, ts) is None
        assert mixed._balance_bound == bound0  # refusal restored the bound
        assert mixed.commit_batch("create_transfers", lb, ts) == b2
        assert blocking.digest() == mixed.digest()
        assert blocking.balances_snapshot() == mixed.balances_snapshot()


def test_pipeline_shard_metrics_recorded():
    """The pipeline.shard.* occupancy series land in the registry for
    deferred sharded commits (docs/observability.md rows)."""
    _need_devices(2)
    from tigerbeetle_tpu.obs.metrics import registry

    registry.reset()
    registry.enable()
    try:
        m = make_machine(shards=2)
        handles = []
        for first in (10_000, 20_000):
            ts = m.prepare("create_transfers", 10, 0)
            h = m.commit_fast_deferred(batch(first, 10), ts)
            assert h is not None
            handles.append(h)
        for h in handles:
            h.resolve()
        snap = registry.snapshot()
        counters = snap["counters"]
        assert counters.get("pipeline.shard.dispatches", 0) == 2
        assert counters.get("pipeline.shard.resolves", 0) == 2
        assert counters.get("pipeline.shard.lanes", 0) == 20
        per_shard = {
            k: v for k, v in counters.items()
            if k.startswith("pipeline.shard.lanes.")
        }
        assert per_shard and sum(per_shard.values()) == 20
        hist = snap["histograms"]
        assert "pipeline.shard.inflight" in hist
        assert hist["pipeline.shard.inflight"]["max"] == 2
    finally:
        registry.reset()
        registry.disable()


# -- slow cells: replica-level composition matrix --------------------------


class ReplicaHarness:
    """A solo replica served through on_request_group_pipelined (the TCP
    bus's path), clock pinned so reply bytes compare across engines;
    ``shards`` rides the machine constructor via TB_SHARDS-equivalent
    plumbing (tests/test_sharded_machine.py::test_env_twin_engages covers the
    env twin)."""

    def __init__(self, tmp, name, depth, shards, merkle):
        import os

        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        self.wire = wire
        path = os.path.join(tmp, f"{name}.tb")
        Replica.format(path, cluster=5, cluster_config=TEST_MIN)
        self.r = Replica(
            path, cluster_config=TEST_MIN, ledger_config=CFG,
            batch_lanes=LANES, time_ns=lambda: 0,
            scrub_interval=4 if merkle else None,
            merkle=True if merkle else None,
        )
        if shards:
            # The replica's machine was constructed single-device (no
            # env set): rebuild it sharded BEFORE open() installs state.
            self.r.machine = TpuStateMachine(
                CFG, batch_lanes=LANES, shards=shards,
                spill_dir=path + ".cold",
            )
            if merkle:
                self.r.machine.scrub_interval = 4
                self.r.machine.merkle_enabled = True
                self.r.machine.scrub_paranoid = False
        self.r.open()
        self.r.pipeline_depth = depth
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

    def close(self):
        self.r.close()


def _mixed_stream(h: ReplicaHarness):
    """Three commit groups: deferrable plain runs, a lookup splitting a
    run (the op-order barrier), a linked (refused) batch mid-run, and a
    duplicate batch.  Returns reply result bodies in request order plus
    the transfer batches in op order (for the model)."""
    wire = h.wire
    clients = [0x300 + i for i in range(4)]
    for c in clients:
        h.register(c)
    h.setup_accounts(clients[0])
    bodies, op_batches, kinds = [], [], []
    groups = [
        [("t", batch(10_000, 10)), ("t", batch(20_000, 12)),
         ("lk", [10_001, 10_002, 77]), ("t", batch(30_000, 9))],
        [("t", batch(40_000, 8)), ("t", linked_batch(50_000, 6)),
         ("t", batch(40_000, 8))],
        [("t", batch(60_000, 14)), ("t", batch(70_000, 5))],
    ]
    for gi, group in enumerate(groups):
        reqs = []
        for k, (kind, payload) in enumerate(group):
            c = clients[k]
            kinds.append(kind)
            if kind == "t":
                body = payload.tobytes()
                op_batches.append(payload)
                op = wire.Operation.create_transfers
            else:
                body = b"".join(
                    int(i).to_bytes(16, "little") for i in payload
                )
                op = wire.Operation.lookup_transfers
            reqs.append(h.request(c, gi + 2, op, body))
        replies, fs = h.r.on_request_group_pipelined(reqs)
        if fs is not None:
            fs.result()
        for rl in replies:
            assert rl, "request dropped"
            bodies.append(rl[0][256:])
    return bodies, op_batches, kinds


class TestReplicaComposition:
    def test_matrix_bitwise_identical_and_match_model(self, tmp_path):
        """The full composition matrix — TB_PIPELINE {1,2,4} x TB_SHARDS
        {0,2} x TB_MERKLE on/off — serves one mixed request stream; every
        cell's reply bytes, ledger digest, and balances must be identical,
        and the transfer results must match the scalar oracle."""
        _need_devices(2)
        tmp = str(tmp_path)
        outs = {}
        for shards in (0, 2):
            for depth in (1, 2, 4):
                for merkle in (False, True):
                    key = (depth, shards, merkle)
                    h = ReplicaHarness(
                        tmp, f"d{depth}s{shards}m{int(merkle)}",
                        depth, shards, merkle,
                    )
                    bodies, op_batches, kinds = _mixed_stream(h)
                    outs[key] = (
                        bodies, h.r.machine.digest(),
                        h.r.machine.balances_snapshot(),
                    )
                    h.close()
        first = outs[(1, 0, False)]
        for key, got in outs.items():
            assert got == first, f"cell {key} diverged"

        # Clock pinned to 0 on both sides (the replica runs time_ns=0, so
        # prepare timestamps derive purely from event counts).
        ref = make_model(wall_clock_ns=0)
        transfer_bodies = [
            body for body, kind in zip(first[0], kinds) if kind == "t"
        ]
        assert len(transfer_bodies) == len(op_batches)
        for b, body in zip(op_batches, transfer_bodies):
            want = ref.create_transfers(
                [M.transfer_from_row(r) for r in b]
            )
            arr = np.frombuffer(body, dtype=types.EVENT_RESULT_DTYPE)
            got = [(int(e["index"]), int(e["result"])) for e in arr]
            assert got == want
        assert first[2] == ref.balances_snapshot()

    def test_deferred_replies_promise_under_shards(self, tmp_path):
        """deferred_replies under TB_SHARDS: group N's reply promise
        comes due with group N+1 (cross-group overlap over the mesh), the
        reply barrier unchanged."""
        _need_devices(2)
        h = ReplicaHarness(str(tmp_path), "promise_s2", 2, 2, False)
        wire = h.wire
        c1, c2 = 0x400, 0x401
        h.register(c1)
        h.register(c2)
        h.setup_accounts(c1)
        replies, fs = h.r.on_request_group_pipelined(
            [h.request(c1, 2, wire.Operation.create_transfers,
                       batch(80_000, 6).tobytes())],
            deferred_replies=True,
        )
        assert isinstance(replies, concurrent.futures.Future)
        assert h.r.pipeline_pending
        replies2, fs2 = h.r.on_request_group_pipelined(
            [h.request(c2, 2, wire.Operation.create_transfers,
                       batch(82_000, 4).tobytes())],
            deferred_replies=True,
        )
        out1 = replies.result(timeout=10)
        assert out1[0] and out1[0][0][256:] == b""
        h.r.pipeline_flush()
        out2 = (
            replies2.result(timeout=10)
            if isinstance(replies2, concurrent.futures.Future) else replies2
        )
        assert out2[0] and out2[0][0][256:] == b""
        for f in (fs, fs2):
            if f is not None:
                f.result()
        assert not h.r.pipeline_pending
        h.close()


@pytest.mark.slow
class TestVoprComposed:
    def test_pinned_seed_green_composed(self, tmp_path, monkeypatch):
        """The pinned VOPR seed replays green under the COMPOSED mode
        (TB_PIPELINE=2 x TB_SHARDS=2): consensus replicas commit per-op
        (the hash-log oracle outranks serving-path grouping), so the
        composition must not shift any schedule or oracle."""
        _need_devices(2)
        monkeypatch.setenv("TB_SHARDS", "2")
        monkeypatch.setenv("TB_PIPELINE", "2")
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

        result = run_seed(42, workdir=str(tmp_path), ticks=3_000)
        assert result.exit_code == EXIT_PASSED
