"""Grouped device commit (machine.commit_group_fast + the replica's
_group_device_runs): a run of consecutive create_transfers prepares
executes in ONE device dispatch, amortizing the per-dispatch host<->device
round trip the per-op path pays for every batch.

Results must be bit-identical to the per-batch path: loop order == op
order, per-op prepare timestamps ride along.  The dispatch runs one loop
step per batch of the run (never the GROUP_K its operands are shaped to),
in ONE compiled program whatever the run's length.  The auto-gate enables
grouping only on the TPU backend, so these tests force it on.
"""

import numpy as np
import pytest

from tigerbeetle_tpu import machine, types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.ops import staging

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)


def make_machine(group: bool) -> TpuStateMachine:
    m = TpuStateMachine(CFG, batch_lanes=LANES)
    m.group_device_commit = group
    accounts = types.accounts_array(
        [types.account(id=i + 1, ledger=1, code=10) for i in range(16)]
    )
    assert m.create_accounts(accounts, wall_clock_ns=1000) == []
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
        grouped = make_machine(True)
        serial = make_machine(False)
        batches = [batch(1000 * (k + 1), 20 + k) for k in range(5)]
        res_g, res_s = self._commit_both(grouped, serial, batches)
        assert res_g is not None, "eligible run must group"
        assert res_g == res_s
        assert grouped.digest() == serial.digest()

    def test_failures_identical(self):
        grouped = make_machine(True)
        serial = make_machine(False)
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
        grouped = make_machine(True)
        serial = make_machine(False)
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
        grouped = make_machine(True)
        serial = make_machine(False)
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
        grouped = make_machine(True)
        serial = make_machine(False)
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
        m = make_machine(True)
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
        m = make_machine(True)
        assert m.commit_group_fast(
            [batch(7000, 4)], [m.prepare("create_transfers", 4, 0)]
        ) is None


class TestReplicaGroupParity:
    def _serve(self, tmp_path, name, group):
        from tigerbeetle_tpu.vsr import wire
        from tigerbeetle_tpu.vsr.replica import Replica

        from tigerbeetle_tpu.config import TEST_MIN

        path = str(tmp_path / f"{name}.tb")
        Replica.format(path, cluster=5, replica=0, replica_count=1,
                       cluster_config=TEST_MIN)
        r = Replica(path, cluster_config=TEST_MIN, ledger_config=CFG,
                    batch_lanes=LANES)
        r.open()
        r.machine.group_device_commit = group
        return r, wire

    def _request(self, wire, client_id, session, request_n, op, body,
                 parent=0):
        h = wire.new_header(
            wire.Command.request, cluster=5, client=client_id,
            request=request_n, parent=parent, session=session,
            operation=int(op),
        )
        h["size"] = wire.HEADER_SIZE + len(body)
        h = wire.set_checksums(h, body)
        return h, body

    def _register(self, r, wire, client_id):
        h, body = self._request(
            wire, client_id, 0, 0, wire.Operation.register, b""
        )
        replies, _ = r.on_request_group_pipelined([(h, body)])
        (reply,) = replies[0]
        rh, _cmd = wire.decode_header(reply[:wire.HEADER_SIZE])
        return int(rh["commit"])  # session = register op

    def test_twelve_sessions_give_a_run_past_the_short_stack(self, tmp_path):
        """With more than `GROUP_ROWS_SHORT` sessions a commit group holds
        a run the short stack cannot take: the run is ONE dispatch on the
        `GROUP_K`-row stack (one put of `GROUP_K` rows), and answers as the
        same requests committed one by one.  No benchmark cell has more
        than 8 sessions, so this side of `_group_rows` is held here, on
        the CPU, and timed only by `tools/stage_probe.py` (ROADMAP B-I)."""
        from tigerbeetle_tpu.obs.metrics import registry

        n_sessions = 12
        short, long_ = TpuStateMachine.GROUP_ROWS_SHORT, TpuStateMachine.GROUP_K
        assert short < n_sessions <= long_
        outs = {}
        for group in (False, True):
            r, wire = self._serve(tmp_path, f"s{int(group)}", group)
            clients = [0x200 + i for i in range(n_sessions)]
            sessions = {c: self._register(r, wire, c) for c in clients}
            accounts = types.accounts_array([
                types.account(id=i + 1, ledger=1, code=10) for i in range(16)
            ])
            replies, fsync = r.on_request_group_pipelined([self._request(
                wire, clients[0], sessions[clients[0]], 1,
                wire.Operation.create_accounts, accounts.tobytes(),
            )])
            if fsync is not None:
                fsync.result()
            reqs = [
                self._request(
                    wire, c, sessions[c], 2 if i == 0 else 1,
                    wire.Operation.create_transfers,
                    batch(10_000 * (i + 1), 5 + i).tobytes(),
                )
                for i, c in enumerate(clients)
            ]
            with registry.enabled_scope():
                replies, fsync = r.on_request_group_pipelined(reqs)
                if fsync is not None:
                    fsync.result()
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
            r.close()
        assert len(outs[True]) == n_sessions
        assert outs[True] == outs[False]
        assert outs[(True, "state")] == outs[(False, "state")]
        assert outs[(True, "state")][1] == sum(
            5 + i for i in range(n_sessions))

    def test_mixed_group_bitwise_parity(self, tmp_path):
        outs = {}
        for group in (False, True):
            r, wire = self._serve(tmp_path, f"g{int(group)}", group)
            clients = [(0x100 + i) for i in range(4)]
            sessions = {c: self._register(r, wire, c) for c in clients}
            # One commit group: three groupable create_transfers runs split
            # by a lookup (non-groupable op) in the middle.
            reqs = []
            for i, c in enumerate(clients[:3]):
                body = batch(10_000 * (i + 1), 10 + i).tobytes()
                reqs.append(self._request(
                    wire, c, sessions[c], 1,
                    wire.Operation.create_transfers, body,
                ))
            ids = np.asarray([10_001, 10_002], dtype=np.uint64)
            lk_body = b"".join(
                int(i).to_bytes(16, "little") for i in ids
            )
            reqs.insert(2, self._request(
                wire, clients[3], sessions[clients[3]], 1,
                wire.Operation.lookup_transfers, lk_body,
            ))
            replies, fsync = r.on_request_group_pipelined(reqs)
            if fsync is not None:
                fsync.result()
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
