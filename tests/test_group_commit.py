"""Grouped device commit (machine.commit_group_fast + the replica's
_group_device_runs): a run of consecutive create_transfers prepares
executes in ONE device dispatch, amortizing the per-dispatch host<->device
round trip the per-op path pays for every batch.

Results must be bit-identical to the per-batch path: loop order == op
order, per-op prepare timestamps ride along.  The dispatch runs one loop
step per batch of the run (never the GROUP_K its operands are shaped to),
in ONE compiled program whatever the run's length.  Grouping is no
setting: a replica built with defaults groups whatever run the machine
accepts, on every backend.  The reference side of a comparison commits
batch by batch (machine level) or is fed one request a commit group
(replica level): a group of one is the ungrouped path.
"""

import types as pytypes

import numpy as np
import pytest

from tigerbeetle_tpu import machine, types
from tigerbeetle_tpu.config import TEST_MIN, ClusterConfig, LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.ops import staging
from tigerbeetle_tpu.testing import model as M
from tigerbeetle_tpu.vsr import wire
from tigerbeetle_tpu.vsr.replica import Replica

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)


def accounts_batch():
    return types.accounts_array(
        [types.account(id=i + 1, ledger=1, code=10) for i in range(16)]
    )


def make_machine() -> TpuStateMachine:
    m = TpuStateMachine(CFG, batch_lanes=LANES)
    assert m.create_accounts(accounts_batch(), wall_clock_ns=1000) == []
    return m


def batch(first_id, n, amount=3):
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % 16,
            credit_account_id=1 + (i + 3) % 16, amount=amount + i % 5,
            ledger=1, code=10,
        )
        for i in range(n)
    ])


class TestMachineGroupParity:
    @staticmethod
    def _run_of(k, first_id=10_000):
        """k batches: a lane that fails in the first, the second a full
        duplicate of the first, the rest fresh."""
        batches = [batch(first_id + 100 * j, 9 + j % 7) for j in range(k)]
        batches[0]["debit_account_id_lo"][3] = 999  # no such account
        batches[1] = batches[0].copy()
        return batches

    @staticmethod
    def _commit_both(grouped, serial, batches):
        # Assign timestamps exactly as the replica's _prepare would.
        tss = [
            grouped.prepare("create_transfers", len(b), 0) for b in batches
        ]
        res_g = grouped.commit_group_fast(batches, tss)
        res_s = []
        for b, ts in zip(batches, tss):
            serial.prepare("create_transfers", len(b), 0)
            res_s.append(serial.commit_batch("create_transfers", b, ts))
        return res_g, res_s

    def test_grouped_equals_per_batch(self):
        grouped = make_machine()
        serial = make_machine()
        batches = [batch(1000 * (k + 1), 20 + k) for k in range(5)]
        res_g, res_s = self._commit_both(grouped, serial, batches)
        assert res_g is not None, "eligible run must group"
        assert res_g == res_s
        assert grouped.digest() == serial.digest()

    def test_failures_identical(self):
        grouped = make_machine()
        serial = make_machine()
        b1 = batch(2000, 12)
        b2 = batch(2000, 12)  # full duplicate of b1: every lane 'exists'
        b3 = batch(3000, 8)
        b3["debit_account_id_lo"][3] = 999  # no such account
        res_g, res_s = self._commit_both(grouped, serial, [b1, b2, b3])
        assert res_g is not None
        assert res_g == res_s
        assert grouped.digest() == serial.digest()
        # The duplicate batch must report per-lane 'exists' codes.
        assert len(res_g[1]) == 12

    @pytest.mark.parametrize("k", [2, 3, 7, 8, TpuStateMachine.GROUP_K])
    def test_run_of_k_equals_per_batch(self, k):
        grouped = make_machine()
        serial = make_machine()
        res_g, res_s = self._commit_both(grouped, serial, self._run_of(k))
        assert res_g is not None and len(res_g) == k
        assert res_g == res_s
        assert grouped.digest() == serial.digest()
        assert res_g[0] and len(res_g[1]) == len(res_s[1]) > 0

    def test_steps_past_the_run_are_not_run_and_their_codes_not_read(
        self, monkeypatch
    ):
        """The loop ends at the first empty row: a live batch planted past
        it is never applied and its codes row stays zeroed; and resolve()
        never looks at the rows past the run: poisoned, the results stay
        equal."""
        real = machine._group_fast_dispatch
        seen = []

        def planted_and_poisoned(ledger, cols64, cols32, meta):
            k = int(np.count_nonzero(np.asarray(meta[0])))
            cols64 = cols64.at[k + 1].set(cols64[0])
            id_lo = staging.column_row(types.TRANSFER_DTYPE, "id_lo")
            cols64 = cols64.at[k + 1, id_lo].add(5_000_000)
            ledger, codes, *rest = real(
                ledger, cols64, cols32.at[k + 1].set(cols32[0]),
                meta.at[0, k + 1].set(meta[0, 0]),
            )
            seen.append((k, np.asarray(codes)))
            return (ledger, codes.at[k:].set(0xFFFFFFFF), *rest)

        monkeypatch.setattr(machine, "_group_fast_dispatch",
                            planted_and_poisoned)
        grouped = make_machine()
        serial = make_machine()
        for n, k in enumerate((3, 6, 9)):
            res_g, res_s = self._commit_both(
                grouped, serial, self._run_of(k, 10_000 * (n + 1))
            )
            assert res_g == res_s
        assert grouped.digest() == serial.digest()
        assert [k for k, _ in seen] == [3, 6, 9]
        for k, codes in seen:
            # The stack's leading dimension goes by the run's length.
            assert codes.shape[0] == grouped._group_rows(k)
            assert codes[:k].any() and not codes[k:].any()
        assert [codes.shape[0] for _, codes in seen] == [
            TpuStateMachine.GROUP_ROWS_SHORT, TpuStateMachine.GROUP_ROWS_SHORT,
            TpuStateMachine.GROUP_K,
        ]

    def test_every_run_length_reuses_one_of_two_compiled_programs(self):
        """One program a leading dimension of the staged stack (8 rows for
        a run of at most 8, GROUP_K beyond), whatever the run's length."""
        grouped = make_machine()
        serial = make_machine()
        self._commit_both(grouped, serial, self._run_of(2))
        self._commit_both(grouped, serial, self._run_of(9, 5_000))
        warmed = machine._group_fast_dispatch._cache_size()
        assert warmed >= 2
        for n, k in enumerate((3, 7, 8, TpuStateMachine.GROUP_K, 2)):
            res_g, res_s = self._commit_both(
                grouped, serial, self._run_of(k, 10_000 * (n + 2))
            )
            assert res_g == res_s
            assert machine._group_fast_dispatch._cache_size() == warmed
        assert grouped.digest() == serial.digest()

    def test_ineligible_run_refused(self):
        m = make_machine()
        balancing = types.transfers_array([
            types.transfer(
                id=5000, debit_account_id=1, credit_account_id=2, amount=5,
                ledger=1, code=10,
                flags=types.TransferFlags.BALANCING_DEBIT,
            )
        ])
        assert m.commit_group_fast(
            [batch(6000, 4), balancing],
            [m.prepare("create_transfers", 4, 0),
             m.prepare("create_transfers", 1, 0)]
        ) is None  # balancing/post/void/linked flags leave the fast path

    def test_single_batch_refused(self):
        m = make_machine()
        assert m.commit_group_fast(
            [batch(7000, 4)], [m.prepare("create_transfers", 4, 0)]
        ) is None


# Room for a run past GROUP_K: 33 sessions, and a WAL that holds their
# registers and one group of 33 without a checkpoint in between.
WIDE = ClusterConfig(
    message_size_max=TEST_MIN.message_size_max, journal_slot_count=256,
    clients_max=64,
)


def open_replica(tmp_path, name, cluster_config=TEST_MIN, **kwargs):
    """A solo replica built with defaults: nothing about grouping is set."""
    path = str(tmp_path / f"{name}.tb")
    Replica.format(path, cluster=5, replica=0, replica_count=1,
                   cluster_config=cluster_config)
    r = Replica(path, cluster_config=cluster_config, ledger_config=CFG,
                batch_lanes=LANES, **kwargs)
    r.open()
    return r


def request(client_id, session, request_n, op, body):
    h = wire.new_header(
        wire.Command.request, cluster=5, client=client_id,
        request=request_n, session=session, operation=int(op),
    )
    h["size"] = wire.HEADER_SIZE + len(body)
    return wire.set_checksums(h, body), body


def commit(r, reqs, group=True):
    """One commit group, or (the reference side) one group a request."""
    replies = []
    for chunk in ([reqs] if group else [[q] for q in reqs]):
        out, fsync = r.on_request_group_pipelined(chunk)
        if fsync is not None:
            fsync.result()
        replies.extend(out)
    return replies


def register(r, clients) -> dict:
    """One group of registers; a client's session is its register's op."""
    replies = commit(r, [
        request(c, 0, 0, wire.Operation.register, b"") for c in clients])
    return {
        c: int(wire.decode_header(reply[:wire.HEADER_SIZE])[0]["commit"])
        for c, (reply,) in zip(clients, replies)
    }


def open_sessions(r, n, first=0x200):
    """n registered clients, and the 16 accounts created by the first."""
    clients = [first + i for i in range(n)]
    sessions = register(r, clients)
    (reply,) = commit(r, [request(
        clients[0], sessions[clients[0]], 1,
        wire.Operation.create_accounts, accounts_batch().tobytes(),
    )])
    assert reply[0][256:] == b"", "account setup failed"
    return clients, sessions


def transfer_requests(clients, sessions, batches):
    return [
        request(c, sessions[c], 2 if i == 0 else 1,
                wire.Operation.create_transfers, b.tobytes())
        for i, (c, b) in enumerate(zip(clients, batches))
    ]


def results_of(reply) -> list:
    arr = np.frombuffer(reply[0][256:], dtype=types.EVENT_RESULT_DTYPE)
    return [(int(e["index"]), int(e["result"])) for e in arr]


def make_model() -> M.ReferenceStateMachine:
    ref = M.ReferenceStateMachine()
    assert ref.create_accounts(
        [M.account_from_row(row) for row in accounts_batch()], 0
    ) == []
    return ref


class TestReplicaGroupParity:
    def test_twelve_sessions_give_a_run_past_the_short_stack(self, tmp_path):
        """With more than `GROUP_ROWS_SHORT` sessions a commit group holds
        a run the short stack cannot take: the run is ONE dispatch on the
        `GROUP_K`-row stack (one put of `GROUP_K` rows), and answers as the
        same requests committed one by one.  No benchmark cell has more
        than 8 sessions, so this side of `_group_rows` is held here, on
        the CPU, and timed only by `tools/stage_probe.py` (ROADMAP B-I)."""
        n_sessions = 12
        short, long_ = TpuStateMachine.GROUP_ROWS_SHORT, TpuStateMachine.GROUP_K
        assert short < n_sessions <= long_
        outs = {}
        for group in (False, True):
            r = open_replica(tmp_path, f"s{int(group)}")
            clients, sessions = open_sessions(r, n_sessions)
            reqs = transfer_requests(clients, sessions, [
                batch(10_000 * (i + 1), 5 + i) for i in range(n_sessions)
            ])
            with registry.enabled_scope():
                replies = commit(r, reqs, group)
                counters = registry.snapshot()["counters"]
            outs[group] = [rl[0][256:] for rl in replies]
            # (Each replica stamps from its own clock: balances and the
            # count of rows written are compared, not the digest.)
            balances = r.machine.lookup_accounts(list(range(1, 17)))
            outs[(group, "state")] = (
                balances[["debits_posted_lo", "credits_posted_lo"]].tolist(),
                r.machine.lookup_transfers(
                    [10_000 * (i + 1) + j for i in range(n_sessions)
                     for j in range(5 + i)]).size,
            )
            if group:
                rows = staging.stage_group(
                    [batch(1, 1)], LANES, [1], long_)
                assert counters["ops.group.batches"] == n_sessions
                assert counters["ops.dispatch"] == 1
                assert counters["stage.puts"] == 1
                assert counters["stage.bytes"] == sum(
                    a.nbytes for a in rows)
            else:
                assert "ops.group.batches" not in counters
                assert counters["ops.dispatch"] == n_sessions
            r.close()
        assert len(outs[True]) == n_sessions
        assert outs[True] == outs[False]
        assert outs[(True, "state")] == outs[(False, "state")]
        assert outs[(True, "state")][1] == sum(
            5 + i for i in range(n_sessions))

    def test_mixed_group_bitwise_parity(self, tmp_path):
        outs = {}
        for group in (False, True):
            r = open_replica(tmp_path, f"g{int(group)}")
            clients = [(0x100 + i) for i in range(4)]
            sessions = register(r, clients)
            # One commit group: three groupable create_transfers runs split
            # by a lookup (non-groupable op) in the middle.
            reqs = []
            for i, c in enumerate(clients[:3]):
                body = batch(10_000 * (i + 1), 10 + i).tobytes()
                reqs.append(request(
                    c, sessions[c], 1, wire.Operation.create_transfers, body,
                ))
            ids = np.asarray([10_001, 10_002], dtype=np.uint64)
            lk_body = b"".join(
                int(i).to_bytes(16, "little") for i in ids
            )
            reqs.insert(2, request(
                clients[3], sessions[clients[3]], 1,
                wire.Operation.lookup_transfers, lk_body,
            ))
            replies = commit(r, reqs, group)
            outs[group] = [
                rl[0] if rl else None for rl in replies
            ]
            digest = r.machine.digest()
            outs[(group, "digest")] = digest
            r.close()
        assert outs[(False, "digest")] == outs[(True, "digest")]
        assert len(outs[False]) == len(outs[True])
        for a, b in zip(outs[False], outs[True]):
            # Reply headers embed per-op checksums over identical bodies;
            # byte-compare the RESULT bodies (headers differ only in
            # replica-local fields like view timestamps).
            assert (a is None) == (b is None)
            if a is not None:
                assert a[256:] == b[256:], "result bodies diverge"


class TestDefaultReplicaGroups:
    """Tier-1's default path is the cells' path: nothing here names a
    backend or sets a switch."""

    @pytest.mark.parametrize("k", [2, 8, 9, 32, 33])
    def test_a_default_replica_groups_a_run_of_k(self, tmp_path, k):
        """A commit group of k transfers requests goes to the device as
        the runs `_group_device_runs` cuts (GROUP_K at most; 33 = a run of
        32 and a lone request on the per-batch program), and answers as
        the model does."""
        cap = TpuStateMachine.GROUP_K
        runs = [cap] * (k // cap) + ([k % cap] if k % cap else [])
        r = open_replica(tmp_path, f"k{k}", WIDE, time_ns=lambda: 0)
        clients, sessions = open_sessions(r, k)
        batches = [batch(1000 * (i + 1), 3 + i % 5) for i in range(k)]
        batches[1] = batches[0].copy()  # every lane of it 'exists'
        with registry.enabled_scope():
            replies = commit(r, transfer_requests(clients, sessions, batches))
            counters = registry.snapshot()["counters"]
        grouped = sum(n for n in runs if n >= 2)
        assert counters["ops.route.grouped"] == grouped
        assert counters["ops.group.batches"] == grouped
        assert counters.get("ops.route.fast", 0) == runs.count(1)
        assert counters["ops.dispatch"] == len(runs)
        ref = make_model()
        for b, reply in zip(batches, replies):
            assert results_of(reply) == ref.create_transfers(
                [M.transfer_from_row(row) for row in b])
        assert len(results_of(replies[1])) == len(batches[1])
        assert r.machine.balances_snapshot() == ref.balances_snapshot()
        r.close()

    @pytest.mark.parametrize("depth", [1, 2])
    def test_a_run_with_an_ineligible_batch_is_refused_whole(
        self, tmp_path, depth
    ):
        """The second batch of the run posts the first one's pendings (a
        two-phase flag: not fast-path eligible): the machine refuses the
        run, nothing of it is grouped, and its three requests execute
        inline in op order (the posts succeed only if the pendings went
        before them; the third is a duplicate of the first)."""
        r = open_replica(tmp_path, f"d{depth}", time_ns=lambda: 0)
        r.pipeline_depth = depth
        clients, sessions = open_sessions(r, 3)
        pendings = batch(1000, 8)
        pendings["flags"] = int(types.TransferFlags.PENDING)
        posts = types.transfers_array([
            types.transfer(
                id=2000 + i, pending_id=1000 + i, ledger=1, code=10,
                flags=types.TransferFlags.POST_PENDING_TRANSFER,
            )
            for i in range(8)
        ])
        batches = [pendings, posts, pendings.copy()]
        with registry.enabled_scope():
            replies = commit(r, transfer_requests(clients, sessions, batches))
            counters = registry.snapshot()["counters"]
        assert "ops.route.grouped" not in counters
        assert "ops.group.batches" not in counters
        assert counters["ops.route.fast"] == 2
        assert counters["ops.route.general"] == 1
        ref = make_model()
        for b, reply in zip(batches, replies):
            assert results_of(reply) == ref.create_transfers(
                [M.transfer_from_row(row) for row in b])
        assert results_of(replies[1]) == []
        assert len(results_of(replies[2])) == 8
        assert r.machine.balances_snapshot() == ref.balances_snapshot()
        r.close()


def _prepared(ops: str):
    """(index, prepare header, body) triples as the engines hold them:
    T a one-row create_transfers, A a create_accounts, L a lookup."""
    kinds = {
        "T": (wire.Operation.create_transfers, batch(1, 1).tobytes()),
        "A": (wire.Operation.create_accounts, accounts_batch().tobytes()),
        "L": (wire.Operation.lookup_accounts, (1).to_bytes(16, "little")),
    }
    return [
        (i, wire.new_header(wire.Command.prepare, operation=int(kinds[c][0]),
                            timestamp=100 + i), kinds[c][1])
        for i, c in enumerate(ops)
    ]


@pytest.mark.parametrize("ops,single_ok,machine_k,hash_log,want", [
    # Cut by an account request; the blocking engine wants runs of >= 2.
    ("TTATTTA", False, 32, None, {0: [0, 1], 3: [3, 4, 5]}),
    # Cut by a lookup: the lone request on each side is no run ...
    ("TLTTLT", False, 32, None, {2: [2, 3]}),
    # ... except for the pipelined engine, which defers singles too.
    ("TLTTLT", True, 32, None, {0: [0], 2: [2, 3], 5: [5]}),
    # The machine's GROUP_K caps a run; the rest starts the next one.
    ("TTTTT", False, 2, None, {0: [0, 1], 2: [2, 3]}),
    # The determinism oracle wants per-op digests: no runs at all.
    ("TTT", True, 32, object(), {}),
    # A stand-in machine that cannot group (sim/mc.py's GROUP_K = 1) ...
    ("TTT", False, 1, None, {}),
    ("TTT", True, 1, None, {0: [0], 1: [1], 2: [2]}),
    # ... or that says nothing about it.
    ("TTT", False, None, None, {}),
], ids=["accounts-cut", "lookup-cut", "lookup-cut-singles", "cap",
        "hash-log", "k1", "k1-singles", "no-k"])
def test_group_device_runs_by_shape(ops, single_ok, machine_k, hash_log,
                                    want):
    stand_in = pytypes.SimpleNamespace(
        hash_log=hash_log,
        machine=pytypes.SimpleNamespace(
            **({} if machine_k is None else {"GROUP_K": machine_k})),
    )
    prepared = _prepared(ops)
    runs = Replica._group_device_runs(stand_in, prepared, single_ok=single_ok)
    assert {j: [jj for jj, _b, _t in run] for j, run in runs.items()} == want
    for run in runs.values():
        for jj, b, t in run:
            assert b.tobytes() == prepared[jj][2] and t == 100 + jj
