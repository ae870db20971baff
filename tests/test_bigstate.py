"""A ledger whose state is many times a batch (`benchmarks/configs/
tb-bigstate-1r`, the cell `bigstate-s8`), at sizes a CPU test can hold.

(a) Accounts many times the lanes, in a table at load 0.3 and 0.49: batches
whose lanes touch no account twice (what 8190 uniform pairs over millions
of accounts are) and batches that do, through the lone, the grouped and the
blocking route, against the scalar oracle (`testing/model.py`): results,
balances, `get_account_transfers`.  (b) The served replica crosses two
checkpoints under `async_checkpoint` while its clients keep sending: the
replies are the oracle's, a restart restores the digest, and the spans,
counters and gauges of the checkpoint path read what happened.  (c) Index
levels first filled inside later requests (one past what a set-up filled)
answer as the oracle does.  (d) The table gauges."""

import asyncio
import threading

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.client import Client
from tigerbeetle_tpu.config import TEST_MIN, LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.net import bus as bus_mod
from tigerbeetle_tpu.net.bus import ReplicaServer
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import STAGES, txtrace
from tigerbeetle_tpu.testing import model as M
from tigerbeetle_tpu.vsr import checkpoint as checkpoint_mod
from tigerbeetle_tpu.vsr.replica import Replica

LANES = 64
ACCOUNT_SLOTS_LOG2 = 11
CFG = LedgerConfig(accounts_capacity_log2=ACCOUNT_SLOTS_LOG2,
                   transfers_capacity_log2=13, posted_capacity_log2=10,
                   max_probe=1 << 10)
CHECKPOINT_SPANS = ("checkpoint_capture", "checkpoint_d2h",
                    "checkpoint_digest", "checkpoint_write")


def _accounts(first, n):
    return types.accounts_array([
        types.account(id=first + i, ledger=1, code=10) for i in range(n)])


def _transfers(first_id, debit, credit, rng):
    return types.transfers_array([
        types.transfer(id=first_id + i, debit_account_id=int(d),
                       credit_account_id=int(c),
                       amount=int(rng.integers(1, 1000)), ledger=1, code=10)
        for i, (d, c) in enumerate(zip(debit, credit))])


def _distinct_pairs(rng, n_accounts, n=LANES):
    """`n` lanes over 2n DISTINCT accounts: no in-batch duplicate."""
    ids = rng.permutation(n_accounts)[: 2 * n] + 1
    return ids[:n], ids[n:]


def _hot_pairs(rng, n_accounts, n=LANES, hot=6):
    """`n` lanes over `hot` accounts: every account many times a batch."""
    pool = rng.permutation(n_accounts)[:hot] + 1
    debit = rng.choice(pool, n)
    credit = pool[(np.searchsorted(np.sort(pool), debit)
                   + rng.integers(1, hot, n)) % hot]
    credit = np.where(credit == debit, pool[0], credit)
    credit = np.where(credit == debit, pool[1], credit)
    return debit, credit


class Pair:
    """A machine with `n_accounts` accounts beside the oracle."""

    def __init__(self, n_accounts, cfg=CFG):
        self.n_accounts = n_accounts
        self.m = TpuStateMachine(cfg, batch_lanes=LANES)
        self.ref = M.ReferenceStateMachine()
        for first in range(1, n_accounts + 1, LANES):
            rows = _accounts(first, min(LANES, n_accounts + 1 - first))
            assert self.m.create_accounts(rows, wall_clock_ns=0) == []
            assert self.ref.create_accounts(
                [M.account_from_row(r) for r in rows]) == []

    def _oracle(self, b):
        return self.ref.create_transfers([M.transfer_from_row(r) for r in b])

    def lone(self, b):
        handle = self.m.commit_fast_deferred(
            b, self.m.prepare("create_transfers", len(b), 0))
        assert handle is not None
        (res,) = handle.resolve()
        assert res == self._oracle(b)

    def grouped(self, run):
        tss = [self.m.prepare("create_transfers", len(b), 0) for b in run]
        got = self.m.commit_group_fast(run, tss)
        assert got is not None
        for b, res in zip(run, got):
            assert res == self._oracle(b)

    def blocking(self, b):
        assert self.m.create_transfers(b, wall_clock_ns=0) == self._oracle(b)

    def check(self, accounts):
        assert self.m.balances_snapshot() == self.ref.balances_snapshot()
        for account in accounts:
            for flags in (1, 2, 3, 3 | 4):  # debits, credits, both, reversed
                f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
                f["account_id_lo"], f["limit"], f["flags"] = (
                    int(account), 8000, flags)
                assert [int(r["id_lo"])
                        for r in self.m.get_account_transfers(f)] == [
                    t.id for t in self.ref.get_account_transfers(
                        int(account), 0, 0, 8000, flags)
                ], (account, flags)


# -- (a) accounts many times the lanes ---------------------------------------------

@pytest.mark.parametrize("pairs", [_distinct_pairs, _hot_pairs],
                         ids=["no_account_twice", "hot_accounts"])
@pytest.mark.parametrize("n_accounts", [614, 1003], ids=["load30", "load49"])
def test_batches_over_many_accounts_commit_as_the_oracle_says(
        n_accounts, pairs):
    slots = 1 << ACCOUNT_SLOTS_LOG2
    assert n_accounts >= 9 * LANES and 0.29 < n_accounts / slots < 0.5
    rng = np.random.default_rng([n_accounts, pairs is _hot_pairs])
    p = Pair(n_accounts)
    assert p.m.ledger.accounts.capacity == slots        # nothing grew
    touched, next_id = set(), 10_000
    batches = []
    for _ in range(2 + TpuStateMachine.GROUP_K):
        debit, credit = pairs(rng, n_accounts)
        if pairs is _distinct_pairs:
            assert len(set(debit) | set(credit)) == 2 * LANES
        else:
            assert len(set(debit) | set(credit)) < LANES // 4
        batches.append(_transfers(next_id, debit, credit, rng))
        touched |= set(debit[:3]) | set(credit[:3])
        next_id += LANES
    # An account nothing exists for, and every event repeated: both reject.
    batches[1]["debit_account_id_lo"][7] = n_accounts + 5
    p.lone(batches[0])
    p.blocking(batches[1])
    p.grouped(batches[2:])
    p.lone(batches[0].copy())                            # every lane `exists`
    assert p.m.ledger.accounts.capacity == slots
    p.check(sorted(touched)[:12] + [n_accounts, n_accounts + 5])


# -- (b) the served replica crosses checkpoints --------------------------------------

CLUSTER = 0xB5
SESSIONS = 4
INTERVAL = TEST_MIN.vsr_checkpoint_interval
N_ACCOUNTS = 20


class Served:
    """A `TEST_MIN` replica (64 journal slots: a checkpoint every 23 ops)
    behind a ReplicaServer on a loop thread of its own, as `run_server`
    serves it."""

    def __init__(self, path):
        self.replica = Replica(path, cluster_config=TEST_MIN,
                               ledger_config=CFG, batch_lanes=LANES,
                               time_ns=lambda: 0)
        self.replica.open()
        self.replica.async_checkpoint = True  # as run_server does
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        assert self._ready.wait(30)

    def _run(self):
        async def main():
            server = ReplicaServer(self.replica, "127.0.0.1", 0)
            self.port = await server.start()
            self._loop = asyncio.get_running_loop()
            self._stop = asyncio.Event()
            self._ready.set()
            await self._stop.wait()
            await server.close()

        asyncio.run(main(), loop_factory=bus_mod.ServingLoop)

    def client(self, k):
        return Client([("127.0.0.1", self.port)], cluster=CLUSTER,
                      config=TEST_MIN, timeout_s=60,
                      client_id=0x700 + 2 * k + 1)

    def close(self):
        self._loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(30)
        assert not self._thread.is_alive()
        self.replica._checkpoint_drain()
        self.replica.close()


def test_two_checkpoints_under_load_replies_digest_and_instruments(tmp_path):
    path = str(tmp_path / "served.tb")
    Replica.format(path, cluster=CLUSTER, cluster_config=TEST_MIN)
    rounds = -(-2 * INTERVAL // SESSIONS) + 2      # past the second checkpoint
    rng = np.random.default_rng(41)
    plan = [[_transfers(100_000 * (s + 1) + 100 * r,
                        *_distinct_pairs(rng, N_ACCOUNTS, 8), rng)
             for r in range(rounds)] for s in range(SESSIONS)]
    codes = [[None] * rounds for _ in range(SESSIONS)]
    in_flight_seen = []
    with registry.enabled_scope(), txtrace.attribution_scope():
        served = Served(path)
        clients = [served.client(k) for k in range(SESSIONS)]
        assert clients[0].create_accounts(_accounts(1, N_ACCOUNTS)) == []
        for c in clients[1:]:
            c.lookup_accounts([1])                   # registers the session

        def session(s):
            for r in range(rounds):
                codes[s][r] = clients[s].create_transfers(plan[s][r])
                in_flight_seen.append(
                    registry.gauge("replica.checkpoint.inflight").value)

        threads = [threading.Thread(target=session, args=(s,), daemon=True)
                   for s in range(SESSIONS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
        for c in clients:
            c.close()
        ops = served.replica.commit_min
        served.close()                               # drains the last write
        op_checkpoint = served.replica.op_checkpoint
        snap = registry.snapshot()
        totals = txtrace.stage_totals()
        ledger = served.replica.machine.ledger
        digest = served.replica.machine.digest()
        balances = served.replica.machine.balances_snapshot()

    # No result of this plan depends on the order of commit: the oracle
    # replays it session by session.
    assert all(c == [] for per in codes for c in per)
    ref = M.ReferenceStateMachine()
    ref.create_accounts([M.account_from_row(r)
                         for r in _accounts(1, N_ACCOUNTS)])
    for per in plan:
        for b in per:
            assert ref.create_transfers(
                [M.transfer_from_row(r) for r in b]) == []
    assert [b[:5] for b in balances] == [
        b[:5] for b in ref.balances_snapshot()]

    # What the instruments read: every capture, its bytes, its spans.
    # (A capture falls at the first group boundary INTERVAL ops or more
    # after the last one: groups of up to SESSIONS requests.)
    counters, gauges = snap["counters"], snap["gauges"]
    captures = counters["replica.checkpoint.captures"]
    assert 2 <= captures <= ops // INTERVAL
    assert captures * INTERVAL <= op_checkpoint <= ops
    assert counters["replica.checkpoints"] == captures    # all adopted
    table_bytes = gauges["ledger.table_bytes"]
    assert table_bytes == (
        (1 << CFG.accounts_capacity_log2) * 129
        + (1 << CFG.transfers_capacity_log2) * 133
        + (1 << CFG.posted_capacity_log2) * 21)
    # A capture is a host copy of every slot of every table, and the
    # history's columns beside them.
    one = sum(a.nbytes for a in
              checkpoint_mod.ledger_to_arrays(ledger).values())
    assert table_bytes < one
    assert counters["replica.checkpoint.bytes"] == captures * one
    assert gauges["replica.checkpoint.inflight"] == 0
    assert set(in_flight_seen) <= {0, 1}
    assert set(totals) <= set(STAGES)
    for name in CHECKPOINT_SPANS:
        assert totals[name]["count"] == captures, name
    assert totals["checkpoint_capture"]["us"] >= (
        totals["checkpoint_d2h"]["us"] + totals["checkpoint_digest"]["us"])
    # The write ran on its own thread, the capture on the serving thread.
    assert counters["txtrace.self_us.checkpoint.checkpoint_write"] > 0
    assert "txtrace.self_us.serving.checkpoint_write" not in counters
    assert counters["txtrace.self_us.serving.checkpoint_d2h"] > 0
    rows = SESSIONS * rounds * 8
    assert gauges["ledger.transfers.load"] == pytest.approx(
        rows / (1 << CFG.transfers_capacity_log2))
    assert gauges["ledger.accounts.load"] == pytest.approx(
        N_ACCOUNTS / (1 << CFG.accounts_capacity_log2))

    # A restart restores what was served.
    again = Replica(path, cluster_config=TEST_MIN, ledger_config=CFG,
                    batch_lanes=LANES, time_ns=lambda: 0)
    again.open()
    assert again.op_checkpoint == op_checkpoint
    assert again.machine.digest() == digest
    assert again.machine.balances_snapshot() == balances
    again.close()


def test_a_synchronous_checkpoint_carries_the_same_spans_on_one_thread(
        tmp_path):
    """The simulator's mode (no `async_checkpoint`): capture and write on
    the calling thread, no write ever in flight."""
    from test_pipeline import ReplicaHarness, batch

    with registry.enabled_scope(), txtrace.attribution_scope():
        h = ReplicaHarness(str(tmp_path), "sync", depth=1, group=False)
        h.register(0xA1)
        h.setup_accounts(0xA1)
        for n in range(INTERVAL):
            replies, fs = h.serve([h.request(
                0xA1, 2 + n, h.wire.Operation.create_transfers,
                batch(5_000 + 100 * n, 4).tobytes())])
            if fs is not None:
                fs.result()
            assert replies[0][0][256:] == b""
        assert h.r.op_checkpoint == INTERVAL
        snap, totals = registry.snapshot(), txtrace.stage_totals()
        h.close()
    assert snap["counters"]["replica.checkpoint.captures"] == 1
    assert "replica.checkpoint.inflight" not in snap["gauges"]
    for name in CHECKPOINT_SPANS:
        assert totals[name]["count"] == 1
        assert snap["counters"][f"txtrace.self_us.serving.{name}"] >= 0
    assert not [k for k in snap["counters"]
                if k.startswith("txtrace.self_us.checkpoint.")]


def test_a_full_wal_waits_for_the_write_in_flight_and_drops_nothing(
        tmp_path, monkeypatch):
    """The WAL has journal_slot_count - interval - 1 = 40 ops of room past a
    capture.  A write that outlasts them used to get the 41st request
    dropped (a single-replica client resends only after its whole timeout:
    300 s in the benchmark); now the request waits for the write."""
    import time

    from test_pipeline import ReplicaHarness, batch

    room = TEST_MIN.journal_slot_count - INTERVAL - 1
    assert room == 40
    with registry.enabled_scope():
        h = ReplicaHarness(str(tmp_path), "walfull", depth=1, group=False)
        h.r.async_checkpoint = True
        write = h.r.forest.checkpoint_arrays

        slow = [True]

        def slow_write(*args):
            # The first one: in flight until the WAL is full, and a little
            # longer.
            deadline = time.monotonic() + 30
            while slow[0] and h.r.op < h.r.op_prepare_max and (
                    time.monotonic() < deadline):
                time.sleep(0.005)
            if slow[0]:
                time.sleep(0.2)
            slow[0] = False
            return write(*args)

        monkeypatch.setattr(h.r.forest, "checkpoint_arrays", slow_write)
        h.register(0xA7)
        h.setup_accounts(0xA7)
        t0 = time.monotonic()
        for n in range(INTERVAL + room + 6):
            replies, fs = h.serve([h.request(
                0xA7, 2 + n, h.wire.Operation.create_transfers,
                batch(9_000 + 10 * n, 2).tobytes())])
            if fs is not None:
                fs.result()
            assert replies[0] and replies[0][0][256:] == b"", n  # no drop
        waited = time.monotonic() - t0
        snap = registry.snapshot()["counters"]
        assert h.r.op_checkpoint >= INTERVAL          # adopted on the way
        h.close()
    assert snap["replica.checkpoint.wal_full_waits"] >= 1
    assert snap["replica.checkpoint.captures"] >= 2
    assert waited >= 0.2                              # it did wait


@pytest.mark.parametrize("async_checkpoint", [True, False],
                         ids=["async", "sync"])
def test_a_group_behind_a_pending_one_that_crosses_a_checkpoint_is_not_cut(
        tmp_path, async_checkpoint):
    """The pipelined engine keeps one group PENDING (journaled, its commits
    and its capture not yet done).  A second group that would pass
    `op_prepare_max` behind it finds no write in flight to wait for: the
    pending group is settled first, its checkpoint taken and adopted, and
    every request of the second group is admitted (the replica used to
    drop the ones past the bound)."""
    import concurrent.futures
    import dataclasses
    import os

    from test_pipeline import ReplicaHarness, batch

    class Wide(ReplicaHarness):
        """TEST_MIN with sessions for more clients than the WAL has room
        (a group holds one request a client)."""

        def __init__(self, tmp, name):
            from tigerbeetle_tpu.vsr import wire
            config = dataclasses.replace(TEST_MIN, clients_max=80)
            self.wire, self.sessions = wire, {}
            path = os.path.join(tmp, f"{name}.tb")
            Replica.format(path, cluster=5, cluster_config=config)
            self.r = Replica(path, cluster_config=config, ledger_config=CFG,
                             batch_lanes=LANES, time_ns=lambda: 0)
            self.r.open()
            self.r.pipeline_depth = 2

    first = [0xB100 + i for i in range(62)]
    second = [0xB200 + i for i in range(8)]
    with registry.enabled_scope():
        h = Wide(str(tmp_path), "behind")
        h.r.async_checkpoint = async_checkpoint
        for client in first + second:
            h.register(client)
        h.setup_accounts(first[0])
        h.r.pipeline_flush()
        h.r._checkpoint_drain()          # set-up's last write: adopted
        room = h.r.op_prepare_max - h.r.op
        assert INTERVAL + len(second) <= room <= len(first)
        leading = [
            h.request(c, 2 if c == first[0] else 1,
                      h.wire.Operation.create_transfers,
                      batch(20_000 + 10 * n, 2).tobytes())
            for n, c in enumerate(first[:room - 2])]
        replies, fs = h.serve(leading, deferred_replies=True)
        assert isinstance(replies, concurrent.futures.Future)
        assert h.r.pipeline_pending and h.r._ckpt_thread is None
        checkpoint_before = h.r.op_checkpoint
        trailing = [
            h.request(c, 1, h.wire.Operation.create_transfers,
                      batch(30_000 + 10 * n, 2).tobytes())
            for n, c in enumerate(second)]
        assert h.r.op + len(trailing) > h.r.op_prepare_max
        replies2, fs2 = h.serve(trailing, deferred_replies=True)
        h.r.pipeline_flush()
        for got in (replies, replies2):
            out = got.result(timeout=10) if isinstance(
                got, concurrent.futures.Future) else got
            assert all(r and r[0][256:] == b"" for r in out)   # none cut
        for f in (fs, fs2):
            if f is not None:
                f.result()
        assert h.r.op_checkpoint > checkpoint_before
        waits = registry.snapshot()["counters"][
            "replica.checkpoint.wal_full_waits"]
        h.close()
    assert waits == 1


# -- (c) index levels first filled inside later requests -----------------------------

@pytest.mark.parametrize("route", ["lone", "grouped"])
def test_a_level_first_filled_after_set_up_answers_as_the_oracle(route):
    """Set-up fills the index through level 2 (4 requests); the requests
    after it fill level 3 for the first time (its first merge, inside a
    request), then carry on: the queries see every transfer."""
    rng = np.random.default_rng(7)
    n_accounts = 40
    p = Pair(n_accounts, LedgerConfig(
        accounts_capacity_log2=8, transfers_capacity_log2=12,
        posted_capacity_log2=10, max_probe=1 << 10))
    next_id = 50_000

    def more(n):
        nonlocal next_id
        out = []
        for _ in range(n):
            debit = rng.integers(1, n_accounts + 1, LANES)
            credit = (debit + rng.integers(1, n_accounts, LANES) - 1) % (
                n_accounts) + 1
            out.append(_transfers(next_id, debit, credit, rng))
            next_id += LANES
        return out

    for b in more(4):                                    # the set-up
        p.lone(b)
    assert p.m.index.occupied == [False, False, True]
    events = p.m.index.shape_class_events
    later = more(5)
    if route == "lone":
        for b in later:
            p.lone(b)
    else:
        p.grouped(later[:4])
        p.lone(later[4])
    assert p.m.index.occupied == [True, False, False, True]
    assert p.m.index.shape_class_events == events + 1    # level 3 is new
    p.check(range(1, n_accounts + 1, 3))


# -- (d) the table gauges ---------------------------------------------------------

def test_table_bytes_follow_a_growth_and_loads_the_bounds():
    cfg = LedgerConfig(accounts_capacity_log2=7, transfers_capacity_log2=8,
                       posted_capacity_log2=6, max_probe=1 << 10)

    def table_bytes(accounts_log2, transfers_log2):
        return ((1 << accounts_log2) * 129 + (1 << transfers_log2) * 133
                + (1 << 6) * 21)

    rng = np.random.default_rng(3)
    with registry.enabled_scope():
        gauges = lambda: registry.snapshot()["gauges"]  # noqa: E731
        p = Pair(60, cfg)
        assert gauges()["ledger.table_bytes"] == table_bytes(7, 8)
        assert gauges()["ledger.accounts.load"] == pytest.approx(60 / 128)
        p.lone(_transfers(1_000, *_distinct_pairs(rng, 60, 20), rng))
        assert gauges()["ledger.transfers.load"] == pytest.approx(20 / 256)
        # 64 + 64 more rows pass half of 256 slots: the table doubles.
        p.blocking(_transfers(2_000, *_hot_pairs(rng, 60), rng))
        p.lone(_transfers(3_000, *_hot_pairs(rng, 60), rng))
        assert p.m.ledger.transfers.capacity == 512
        assert gauges()["ledger.table_bytes"] == table_bytes(7, 9)
        assert gauges()["ledger.transfers.load"] == pytest.approx(148 / 512)
        # A fifth account batch passes half of 128 slots.
        extra = _accounts(61, 10)
        assert p.m.create_accounts(extra, wall_clock_ns=0) == []
        assert gauges()["ledger.table_bytes"] == table_bytes(8, 9)
        assert gauges()["ledger.accounts.load"] == pytest.approx(70 / 256)
    with registry.enabled_scope():
        TpuStateMachine(cfg, batch_lanes=LANES, host_engine=True)
        assert "ledger.table_bytes" not in registry.snapshot()["gauges"]
