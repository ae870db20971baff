"""Pipelined commit engine (docs/commit_pipeline.md): differential proofs.

The three overlaps — staged H2D upload, deferred D2H readback on the
dispatch lane, and fsync/compute overlap — must be INVISIBLE in results:
pipelined (depth 2/4) and sequential (depth 1) commits produce byte-
identical ledgers and replies, checked against each other AND against the
scalar oracle (testing/model.py), including a mid-run fast-path refusal
(balance-bound restore) and a forced probe_overflow.  A VOPR run under
TB_PIPELINE=2 must stay seed-stable (the simulator commits per-op through
consensus, so the serving-path pipeline must never touch its schedules).
"""

import concurrent.futures
import os
import shutil
import tempfile

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import TEST_MIN, LedgerConfig
from tigerbeetle_tpu.machine import DeviceCommitHandle, TpuStateMachine
from tigerbeetle_tpu.obs.txtrace import txtrace
from tigerbeetle_tpu.testing import model as M

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)
N_ACCOUNTS = 16


def make_machine(**kwargs) -> TpuStateMachine:
    m = TpuStateMachine(CFG, batch_lanes=LANES, **kwargs)
    assert m.create_accounts(accounts_batch(), wall_clock_ns=1000) == []
    return m


def make_model(wall_clock_ns=1000) -> M.ReferenceStateMachine:
    ref = M.ReferenceStateMachine()
    assert ref.create_accounts(
        [M.account_from_row(r) for r in accounts_batch()], wall_clock_ns
    ) == []
    return ref


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
    """A linked chain (one lane breaks it): excluded from the fast path —
    the mid-run refusal case."""
    b = batch(first_id, n, flags=int(types.TransferFlags.LINKED))
    b["flags"][-1] = 0  # chain terminator
    b["debit_account_id_lo"][n // 2] = 999  # no such account: chain fails
    return b


class TestMachineDeferred:
    def test_single_deferred_matches_blocking_and_model(self):
        deferred = make_machine()
        blocking = make_machine()
        ref = make_model()
        for k, b in enumerate([batch(1000, 20), batch(2000, 31),
                               batch(1000, 20)]):  # 3rd: every lane exists
            ts_d = deferred.prepare("create_transfers", len(b), 0)
            handle = deferred.commit_fast_deferred(b, ts_d)
            assert isinstance(handle, DeviceCommitHandle)
            (res_d,) = handle.resolve()
            blocking.prepare("create_transfers", len(b), 0)
            res_b = blocking.commit_batch("create_transfers", b, ts_d)
            res_m = ref.create_transfers(
                [M.transfer_from_row(r) for r in b]
            )
            assert res_d == res_b == res_m, f"batch {k}"
        assert deferred.digest() == blocking.digest()
        assert deferred.balances_snapshot() == ref.balances_snapshot()

    def test_deferred_refuses_non_fast_batches_and_restores_bound(self):
        m = make_machine()
        bound0 = m._balance_bound
        b = batch(3000, 4, flags=int(types.TransferFlags.LINKED))
        b["flags"][-1] = 0  # terminated chain; LINKED excludes the fast path
        assert m.commit_fast_deferred(
            b, m.prepare("create_transfers", 4, 0)
        ) is None
        # The refusal must restore the balance bound: the blocking
        # fallback re-notes the batch itself (double-counting would
        # ratchet the monotonic bound and eventually cost the fast path).
        assert m._balance_bound == bound0

    def test_group_deferred_matches_blocking(self):
        deferred = make_machine()
        blocking = make_machine()
        batches = [batch(1000 * (k + 1), 20 + k) for k in range(4)]
        tss_d = [
            deferred.prepare("create_transfers", len(b), 0) for b in batches
        ]
        handle = deferred.commit_group_fast(batches, tss_d, deferred=True)
        assert isinstance(handle, DeviceCommitHandle)
        res_d = handle.resolve()
        tss_b = [
            blocking.prepare("create_transfers", len(b), 0) for b in batches
        ]
        assert tss_b == tss_d
        res_b = blocking.commit_group_fast(batches, tss_b)
        assert res_d == res_b
        assert deferred.digest() == blocking.digest()
        assert deferred.commit_timestamp == blocking.commit_timestamp

    def test_forced_probe_overflow_raises_at_resolve(self):
        """The overflow flag rides the deferred codes readback: a set flag
        must fail the resolve loudly (injected — load-factor management
        keeps real overflow unreachable)."""
        m = make_machine()
        b = batch(5000, 8)
        handle = m.commit_fast_deferred(
            b, m.prepare("create_transfers", 8, 0)
        )
        codes, _overflow = (
            handle._result.result()
            if hasattr(handle._result, "result") else handle._result
        )
        handle._result = (codes, np.uint32(1))  # inject the overflow flag
        with pytest.raises(RuntimeError, match="probe overflow"):
            handle.resolve()

    def test_forced_probe_overflow_group(self):
        m = make_machine()
        batches = [batch(6000, 4), batch(7000, 4)]
        tss = [m.prepare("create_transfers", 4, 0) for _ in batches]
        handle = m.commit_group_fast(batches, tss, deferred=True)
        codes, _overflow = (
            handle._result.result()
            if hasattr(handle._result, "result") else handle._result
        )
        handle._result = (codes, np.uint32(1))
        with pytest.raises(RuntimeError, match="probe overflow"):
            handle.resolve()


class ReplicaHarness:
    """A solo replica served directly through on_request_group_pipelined
    (the TCP bus's path), clock pinned so reply bytes compare across
    engines.  With ``group`` off, serve() hands the replica one request a
    commit group: a group of one is the ungrouped path."""

    group = True  # a subclass with an __init__ of its own serves whole groups

    def __init__(self, tmp, name, depth, group):
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        self.wire = wire
        path = os.path.join(tmp, f"{name}.tb")
        Replica.format(path, cluster=5, cluster_config=TEST_MIN)
        self.r = Replica(path, cluster_config=TEST_MIN, ledger_config=CFG,
                         batch_lanes=LANES, time_ns=lambda: 0)
        self.r.open()
        self.r.pipeline_depth = depth
        self.group = group
        self.sessions = {}

    def request(self, client, request_n, op, body):
        wire = self.wire
        h = wire.new_header(
            wire.Command.request, cluster=5, client=client,
            request=request_n, session=self.sessions.get(client, 0),
            operation=int(op),
        )
        h["size"] = wire.HEADER_SIZE + len(body)
        # A sampled request carries a trace id, stamped as client.py does
        # (0 while sampling is off: the legacy bytes).
        h["trace"] = txtrace.maybe_trace(client)
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

    def serve(self, reqs, deferred_replies=False):
        if self.group:
            return self.r.on_request_group_pipelined(
                reqs, deferred_replies=deferred_replies
            )
        assert not deferred_replies
        replies, fs = [], None
        for req in reqs:
            out, fs = self.r.on_request_group_pipelined([req])
            replies.extend(out)
        return replies, fs  # the IO pool is FIFO: the last fsync covers all

    def close(self):
        self.r.close()


def _mixed_stream(h: ReplicaHarness):
    """Three commit groups: plain runs, a lookup splitting a run, a linked
    (refused) batch mid-run, and a duplicate batch.  Returns the reply
    RESULT bodies in request order plus the transfers batches in op order
    (for the model)."""
    wire = h.wire
    clients = [0x300 + i for i in range(4)]
    for c in clients:
        h.register(c)
    h.setup_accounts(clients[0])
    bodies, op_batches = [], []

    groups = [
        # group 1: three groupable batches + a lookup in the middle
        [("t", batch(10_000, 10)), ("t", batch(20_000, 12)),
         ("lk", [10_001, 10_002, 77]), ("t", batch(30_000, 9))],
        # group 2: linked chain mid-run (fast-path refusal) + duplicates
        [("t", batch(40_000, 8)), ("t", linked_batch(50_000, 6)),
         ("t", batch(40_000, 8))],
        # group 3: back to plain
        [("t", batch(60_000, 14)), ("t", batch(70_000, 5))],
    ]
    kinds = []
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
        replies, fs = h.serve(reqs)
        if fs is not None:
            fs.result()
        for rl in replies:
            assert rl, "request dropped"
            bodies.append(rl[0][256:])
    return bodies, op_batches, kinds


class TestReplicaDifferential:
    @pytest.mark.parametrize("group", [False, True])
    def test_depths_bitwise_identical_and_match_model(self, tmp_path, group):
        tmp = str(tmp_path)
        outs = {}
        for depth in (1, 2, 4):
            h = ReplicaHarness(tmp, f"d{depth}g{int(group)}", depth, group)
            bodies, op_batches, kinds = _mixed_stream(h)
            outs[depth] = (
                bodies, h.r.machine.digest(),
                h.r.machine.balances_snapshot(),
                h.r.machine._balance_bound,
            )
            h.close()
        assert outs[1] == outs[2] == outs[4]

        # Scalar-oracle differential: replay the same transfers batches in
        # op order (clock pinned to 0 on both sides) and compare the wire
        # result bodies event by event.
        ref = make_model(wall_clock_ns=0)
        transfer_bodies = [
            body for body, kind in zip(outs[1][0], kinds) if kind == "t"
        ]
        assert len(transfer_bodies) == len(op_batches)
        for b, body in zip(op_batches, transfer_bodies):
            want = ref.create_transfers(
                [M.transfer_from_row(r) for r in b]
            )
            arr = np.frombuffer(body, dtype=types.EVENT_RESULT_DTYPE)
            got = [(int(e["index"]), int(e["result"])) for e in arr]
            assert got == want
        assert outs[1][2] == ref.balances_snapshot()

    def test_deferred_replies_promise_and_busy_guard(self, tmp_path):
        h = ReplicaHarness(str(tmp_path), "promise", 2, True)
        wire = h.wire
        c1, c2 = 0x400, 0x401
        h.register(c1)
        h.register(c2)
        h.setup_accounts(c1)
        reqs = [h.request(c1, 2, wire.Operation.create_transfers,
                          batch(80_000, 6).tobytes())]
        replies, fs = h.serve(reqs, deferred_replies=True)
        assert isinstance(replies, concurrent.futures.Future)
        assert h.r.pipeline_pending
        # A second request from the SAME client while its group is pending
        # must be dropped (session state not yet updated — a resend could
        # double-commit); a different client proceeds.
        reqs2 = [
            h.request(c1, 3, wire.Operation.create_transfers,
                      batch(81_000, 4).tobytes()),
            h.request(c2, 2, wire.Operation.create_transfers,
                      batch(82_000, 4).tobytes()),
        ]
        replies2, fs2 = h.serve(reqs2, deferred_replies=True)
        # Group 1's promise came due with group 2's admission.
        out1 = replies.result(timeout=10)
        assert out1[0] and out1[0][0][256:] == b""
        h.r.pipeline_flush()
        out2 = (
            replies2.result(timeout=10)
            if isinstance(replies2, concurrent.futures.Future) else replies2
        )
        assert out2[0] == []  # busy client: dropped, retries later
        assert out2[1] and out2[1][0][256:] == b""
        for f in (fs, fs2):
            if f is not None:
                f.result()
        assert not h.r.pipeline_pending
        h.close()

    def test_pipeline_metrics_recorded(self, tmp_path):
        from tigerbeetle_tpu.obs.metrics import registry

        registry.reset()
        registry.enable()
        try:
            h = ReplicaHarness(str(tmp_path), "metrics", 2, True)
            _mixed_stream(h)
            # One more group whose refused run (plain, then a linked
            # chain) sits past the group's head, behind a lookup.
            wire = h.wire
            replies, fs = h.serve([
                h.request(0x300, 5, wire.Operation.lookup_transfers,
                          (10_001).to_bytes(16, "little")),
                h.request(0x301, 5, wire.Operation.create_transfers,
                          batch(90_000, 7).tobytes()),
                h.request(0x302, 5, wire.Operation.create_transfers,
                          linked_batch(95_000, 6).tobytes()),
            ])
            if fs is not None:
                fs.result()
            assert all(replies)
            h.close()
            snap = registry.snapshot()
            counters = snap["counters"]
            assert counters.get("pipeline.groups", 0) >= 4
            assert counters.get("pipeline.dispatches", 0) >= 3
            assert counters.get("pipeline.resolves", 0) == counters.get(
                "pipeline.dispatches"
            )
            # The lookup mid-group and the refused linked run must have
            # recorded their stall reasons.
            assert counters.get("pipeline.stall.barrier", 0) >= 1
            assert counters.get("pipeline.stall.refusal", 0) >= 1
            assert "pipeline.inflight" in snap["histograms"]
        finally:
            registry.reset()
            registry.disable()


@pytest.mark.slow
def test_vopr_seed_stable_under_pipeline(monkeypatch):
    """TB_PIPELINE=2 must not shift any VOPR schedule: the simulator
    commits per-op through consensus (the pipelined engine is a serving-
    path feature), so commits/exit/reason and the rendered event grid are
    bit-stable against the default run."""
    from tigerbeetle_tpu.sim.vopr import run_seed

    seed, ticks = 1234, 1200

    monkeypatch.delenv("TB_PIPELINE", raising=False)
    base = run_seed(seed, ticks=ticks, viz=True)
    monkeypatch.setenv("TB_PIPELINE", "2")
    piped = run_seed(seed, ticks=ticks, viz=True)
    assert (base.exit_code, base.commits, base.ticks, base.reason) == (
        piped.exit_code, piped.commits, piped.ticks, piped.reason
    )
    assert hash(base.viz) == hash(piped.viz)
    assert base.viz == piped.viz
