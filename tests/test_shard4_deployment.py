"""The default load on a sharded ledger as a deployment
(`benchmarks/configs/tb-default-4shard`: `start --shards 4`).

A seeded `plain-s8`-shaped plan of the benchmark's generator through
`TpuStateMachine(shards=4)`'s own routing on a 4-device CPU mesh, in the
groups the serving loop forms (one lone fast request, then the other
sessions' requests as one grouped run), against the benchmark's plain
reference: it knows no layout, so the same operations on the same data give
the same answers at any shard count.  And `start` refusing an option leaves
the environment as it found it."""

import os

import jax
import pytest

from benchmarks.generators import ledger_mix
from benchmarks.harness import check
from benchmarks.reference.ledger import ReferenceLedger
from tigerbeetle_tpu import cli, types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import STAGES, txtrace

# plain-s8's shape, small: every session one request in flight, plain
# transfers over uniform pairs, a preload and a window.
MIX = {
    "generator": "ledger_mix", "accounts": 96, "batch": 48, "sessions": 4,
    "cycle": ["plain"], "preload_per_session": 2,
    "window_cap_per_session": 3, "amount_max": 1000,
}
LANES = 64
SEEDS = [11, 3000000019, 77]
RESULT = types.CreateTransferResult


def _plan(seed):
    """The plan's requests as the closed loop's rounds (accounts first, then
    one request a session a round), with two lanes the mix never sends: a
    transfer id that an earlier request created, and an account nobody
    created.  So not every code is 0."""
    plan = ledger_mix.build(MIX, seed)
    accounts = [step for queue in plan["setup"][0]["queues"]
                for step in queue]
    queues = [pre + win for pre, win in zip(plan["setup"][1]["queues"],
                                            plan["window"])]
    rounds = [[queue[k][1].copy() for queue in queues]
              for k in range(len(queues[0]))]
    rounds[2][1][5] = rounds[0][3][7]             # a duplicate, field for field
    rounds[3][2]["debit_account_id_lo"][9] = MIX["accounts"] + 5
    return accounts, rounds


def _machine(shards):
    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, have {len(jax.devices())}")
    m = TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=9, transfers_capacity_log2=12,
                     posted_capacity_log2=8),
        batch_lanes=LANES, shards=shards)
    return m


def _commit(m, accounts, rounds):
    """Every request's codes, committed as the serving loop groups a round
    of the closed loop: the first session's request alone, the others as
    one grouped run (`vsr/replica.py` `_dispatch_run`)."""
    codes = []
    for _operation, rows in accounts:
        codes.append(m.create_accounts(rows.view(types.ACCOUNT_DTYPE),
                                       wall_clock_ns=0))
    for requests in rounds:
        batches = [rows.view(types.TRANSFER_DTYPE) for rows in requests]
        codes.append(m.create_transfers(batches[0], wall_clock_ns=0))
        timestamps = [m.prepare("create_transfers", len(b), 0)
                      for b in batches[1:]]
        grouped = m.commit_group_fast(batches[1:], timestamps)
        assert grouped is not None
        codes.extend(grouped)
    return [[(int(i), int(c)) for i, c in got] for got in codes]


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_plan_answers_as_the_plain_reference(seed):
    accounts, rounds = _plan(seed)
    ref = ReferenceLedger()
    want = [ref.execute(op, rows) for op, rows in accounts] + [
        ref.execute("create_transfers", rows)
        for requests in rounds for rows in requests]
    want = [[(int(i), int(c)) for i, c in w] for w in want]
    assert sorted(c for w in want for _i, c in w) == sorted(
        (int(RESULT.exists), int(RESULT.debit_account_not_found)))

    m = _machine(shards=4)
    assert m.shards == 4 and m._ledger_is_sharded
    assert len(m.ledger.accounts.key_lo.sharding.device_set) == 4
    batches = sum(len(r) for r in rounds)
    with registry.enabled_scope(), txtrace.attribution_scope():
        assert _commit(m, accounts, rounds) == want
        committed = registry.snapshot()["counters"]
        assert "unshard" not in txtrace.stage_totals()
        ids = [int(i) for requests in rounds for rows in requests
               for i in rows["id_lo"]] + [ledger_mix.FIRST_UNUSED_ID]
        account_ids = list(range(1, MIX["accounts"] + 1))
        got_accounts = m.lookup_accounts(account_ids)
        got_transfers = m.lookup_transfers(ids)
        looked_up = registry.snapshot()["counters"]
        totals = txtrace.stage_totals()
    # The routes: one lone fast request and one grouped run a round, every
    # batch through a sharded program, and the ledger still on the mesh.
    assert committed["ops.route.grouped"] == batches - len(rounds)
    assert committed["ops.route.fast"] == len(rounds)
    assert committed["sharding.batches"] == batches
    assert committed["sharding.lanes"] == batches * MIX["batch"]
    assert 0 < committed["sharding.cross_shard_lanes"] < committed[
        "sharding.lanes"]
    assert committed.get("sharding.seq_fallbacks", 0) == 0
    assert m._ledger_is_sharded
    # The canonical copy is rebuilt at the first read, once, under its span.
    assert committed.get("sharding.unshards", 0) == 0
    assert looked_up["sharding.unshards"] == 1
    assert totals["unshard"]["count"] == 1 and "unshard" in STAGES

    assert check._rows_differing(
        got_accounts, ref.lookup_accounts(account_ids)) == 0
    assert got_accounts["debits_posted_lo"].sum() > 0
    want_rows = ref.lookup_transfers(ids)
    assert len(want_rows) == len(ids) - 2   # the refused one, the unused id
    assert check._rows_differing(got_transfers, want_rows) == 0

    # The same plan on one device: the same codes, the same state.
    single = _machine(shards=0)
    assert _commit(single, accounts, rounds) == want
    assert not single._ledger_is_sharded
    assert single.digest() == m.digest()
    assert single.balances_snapshot() == m.balances_snapshot()


@pytest.mark.parametrize("flags,message", [
    (["--shards", "4", "--cache-posted-log2", "1"], "error: --cache-posted"),
    (["--shards", "2", "--merkle"], "error: --merkle needs"),
    (["--shards", "2", "--overload-control", "--engine"], "pick one"),
    (["--shards", "3"], "power of two"),
])
def test_a_refused_start_leaves_the_environment_as_it_was(
        tmp_path, capsys, monkeypatch, flags, message):
    monkeypatch.delenv("TB_SHARDS", raising=False)
    monkeypatch.delenv("TB_OVERLOAD", raising=False)
    monkeypatch.delenv("TB_SCRUB_INTERVAL", raising=False)
    before = dict(os.environ)
    rc = cli.main(["start", str(tmp_path / "never_opened.tb")] + flags)
    assert rc == 1
    assert message in capsys.readouterr().err
    assert dict(os.environ) == before
