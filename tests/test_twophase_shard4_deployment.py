"""Two-phase payments on a sharded ledger as a deployment
(`benchmarks/configs/tb-twophase-4shard`: `start --shards 4`).

A seeded `twophase-resolve-s8-w576`-shaped plan of the benchmark's generator
through `TpuStateMachine(shards=4)`'s own routing on a 4-device CPU mesh, in
the groups the serving loop forms (a round of pendings: one lone fast
request, then the other sessions' as one grouped run; a round of resolving
requests: one blocking general commit each), against the benchmark's plain
reference, which knows no layout.  Then what the configuration's arithmetic
rests on: the posted table's growth point with and without shards, and the
general route's spans under shards."""

import jax
import numpy as np
import pytest

from benchmarks.generators import ledger_mix
from benchmarks.harness import check
from benchmarks.reference.ledger import ReferenceLedger
from test_twophase_deployment import CHILDREN, _pending_then_resolve
from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import STAGES, txtrace

# twophase-resolve-s8-w576's shape, small: every session one request in
# flight, a pending batch, then the request that resolves it 80 / 15 / 5.
MIX = {
    "generator": "ledger_mix", "accounts": 96, "batch": 40, "sessions": 8,
    "cycle": ["pending", "resolve"],
    "resolve": {"post_pct": 80, "void_pct": 15},
    "preload_per_session": 2, "window_cap_per_session": 2,
    "amount_max": 1000,
}
LANES = 64
SEEDS = [11, 3000000019, 77]
RESULT = types.CreateTransferResult


def _plan(seed):
    """The plan's requests as the closed loop's rounds (accounts first, then
    one request a session a round: all pending or all resolving), and one
    more round of each kind with three lanes the mix never sends: a pending
    whose id an earlier request created, a post of a pending that is already
    posted, and a void of a pending nobody created."""
    plan = ledger_mix.build(MIX, seed)
    accounts = [step for queue in plan["setup"][0]["queues"]
                for step in queue]
    queues = [pre + win for pre, win in zip(plan["setup"][1]["queues"],
                                            plan["window"])]
    rounds = [[queue[k][1].copy() for queue in queues]
              for k in range(len(queues[0]))]
    fresh = ledger_mix.FIRST_UNUSED_ID + 1
    again = []
    for rows in rounds[2][:3]:                    # three more pending requests
        rows = rows.copy()
        rows["id_lo"] = np.arange(fresh, fresh + len(rows), dtype=np.uint64)
        fresh += len(rows)
        again.append(rows)
    again[1][5] = rounds[0][3][7]                 # a duplicate, field for field
    resolved = rounds[1][4]                       # a resolving request, again
    was_post = np.flatnonzero(resolved["flags"] == ledger_mix.TF_POST)
    wrong = resolved[was_post[:3]].copy()
    wrong["id_lo"] = np.arange(fresh, fresh + 3, dtype=np.uint64)
    wrong["pending_id_lo"][1] = ledger_mix.FIRST_UNUSED_ID + 999
    wrong["flags"][1] = ledger_mix.TF_VOID
    wrong["amount_lo"][1] = 0
    wrong = wrong[:2]                             # already posted; not found
    return accounts, rounds + [again, [wrong]]


def _machine(shards, posted_log2=12):
    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, have {len(jax.devices())}")
    m = TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=9, transfers_capacity_log2=13,
                     posted_capacity_log2=posted_log2),
        batch_lanes=LANES, shards=shards)
    return m


def _resolves(rows) -> bool:
    return bool(rows["pending_id_lo"].any())


def _commit(m, accounts, rounds):
    """Every request's codes, committed as the serving loop groups a round
    (`vsr/replica.py` `_dispatch_run`): pendings as one lone request and one
    grouped run, resolving requests one blocking commit each."""
    codes = []
    for _operation, rows in accounts:
        codes.append(m.create_accounts(rows.view(types.ACCOUNT_DTYPE),
                                       wall_clock_ns=0))
    for requests in rounds:
        batches = [rows.view(types.TRANSFER_DTYPE) for rows in requests]
        if _resolves(requests[0]):
            codes.extend(m.create_transfers(b, wall_clock_ns=0)
                         for b in batches)
            continue
        codes.append(m.create_transfers(batches[0], wall_clock_ns=0))
        timestamps = [m.prepare("create_transfers", len(b), 0)
                      for b in batches[1:]]
        grouped = m.commit_group_fast(batches[1:], timestamps)
        assert grouped is not None
        codes.extend(grouped)
    return [[(int(i), int(c)) for i, c in got] for got in codes]


@pytest.mark.parametrize("seed", SEEDS)
def test_sharded_twophase_plan_answers_as_the_plain_reference(seed):
    accounts, rounds = _plan(seed)
    ref = ReferenceLedger()
    want = [ref.execute(op, rows) for op, rows in accounts] + [
        ref.execute("create_transfers", rows)
        for requests in rounds for rows in requests]
    want = [[(int(i), int(c)) for i, c in w] for w in want]
    assert sorted(c for w in want for _i, c in w) == sorted(
        (int(RESULT.exists), int(RESULT.pending_transfer_already_posted),
         int(RESULT.pending_transfer_not_found)))

    m = _machine(shards=4)
    assert m.shards == 4 and m._ledger_is_sharded
    assert len(m.ledger.posted.key_lo.sharding.device_set) == 4
    resolving = sum(_resolves(r) for requests in rounds for r in requests)
    pending_rounds = sum(not _resolves(requests[0]) for requests in rounds)
    batches = sum(len(r) for r in rounds)
    with registry.enabled_scope(), txtrace.attribution_scope():
        assert _commit(m, accounts, rounds) == want
        committed = registry.snapshot()["counters"]
        spans = txtrace.stage_totals()
        ids = [int(i) for requests in rounds for rows in requests
               for i in rows["id_lo"]] + [ledger_mix.FIRST_UNUSED_ID]
        account_ids = list(range(1, MIX["accounts"] + 1))
        got_accounts = m.lookup_accounts(account_ids)
        got_transfers = m.lookup_transfers(ids)
        looked_up = registry.snapshot()["counters"]
    # The routes: every resolving request the sharded general program, one
    # blocking commit each; the pendings one lone fast request and one
    # grouped run a round; nothing grew, nothing fell back, and the ledger
    # is still on the mesh.
    assert committed["ops.route.general"] == resolving == 8 * 2 + 1
    assert spans["general_commit"]["count"] == resolving
    assert spans["full_sync"]["count"] == resolving
    assert committed["ops.route.fast"] == pending_rounds == 3
    assert committed["ops.route.grouped"] == (
        batches - resolving - pending_rounds)
    assert committed["ops.general.lanes"] == sum(
        len(r) for requests in rounds for r in requests if _resolves(r))
    assert committed["ops.general.postvoid_lanes"] == committed[
        "ops.general.lanes"]
    assert committed.get("ops.general.retries", 0) == 0
    assert committed["sharding.batches"] == batches
    assert committed.get("sharding.grows", 0) == 0
    assert committed.get("sharding.seq_fallbacks", 0) == 0
    assert m._ledger_is_sharded
    # The canonical copy is rebuilt at the first read, once.
    assert committed.get("sharding.unshards", 0) == 0
    assert "unshard" not in spans
    assert looked_up["sharding.unshards"] == 1

    assert check._rows_differing(
        got_accounts, ref.lookup_accounts(account_ids)) == 0
    assert got_accounts["debits_pending_lo"].sum() > 0   # the 5 % left open
    assert got_accounts["debits_posted_lo"].sum() > 0
    want_rows = ref.lookup_transfers(ids)
    # Two refused lanes and the id nobody sent have no row; the duplicate's
    # id has the earlier request's.
    assert len(want_rows) == len(ids) - 3
    assert check._rows_differing(got_transfers, want_rows) == 0

    # The same plan on one device: the same codes, the same state.
    single = _machine(shards=0)
    assert _commit(single, accounts, rounds) == want
    assert not single._ledger_is_sharded
    assert single.digest() == m.digest()
    assert single.balances_snapshot() == m.balances_snapshot()


# -- the posted table's growth point ---------------------------------------------


@pytest.mark.parametrize("shards,log2,at", [
    (0, 8, 1 << 7), (4, 8, 1 << 6), (0, 10, 1 << 9), (4, 10, 1 << 8),
])
def test_posted_table_grows_at_half_load_and_at_a_quarter_under_shards(
        shards, log2, at):
    """`--cache-posted-log2 k` holds 2^(k-1) resolved rows before it grows,
    2^(k-2) under `--shards` (`_grow_if_needed` doubles the target there:
    a posted key's owner is not known on the host).  tb-twophase-4shard's
    2^24 slots for at most 3,236,480 rows rest on this."""
    m = _machine(shards, posted_log2=log2)
    grown = []
    grow = m._table_grow
    m._table_grow = lambda table, name, capacity: (
        grown.append((name, capacity)), grow(table, name, capacity))[1]
    m._grow_if_needed(posted=at)
    assert grown == [] and m.ledger.posted.capacity == 1 << log2
    m._grow_if_needed(posted=at + 1)
    assert grown == [("posted", 1 << (log2 + 1))]
    assert m.ledger.posted.capacity == 1 << (log2 + 1)
    assert int(np.asarray(m.ledger.posted.count).sum()) == 0


def test_a_presized_sharded_posted_table_never_grows_through_a_plan():
    """The served twin of the rule: the plan resolves 17 requests of at most
    38 lanes (646 rows at most); 2^12 slots hold 1,024 under shards."""
    accounts, rounds = _plan(5)
    m = _machine(shards=4, posted_log2=12)
    with registry.enabled_scope():
        _commit(m, accounts, rounds)
        counters = registry.snapshot()["counters"]
    assert counters.get("sharding.grows", 0) == 0
    assert m.ledger.posted.capacity == 1 << 12
    assert m._posted_bound * 4 <= 1 << 12


# -- the general route's spans under shards ----------------------------------------

@pytest.fixture(scope="module", params=[0, 4], ids=["one_chip", "shards4"])
def warm_machine(request):
    m = _machine(request.param)
    accounts, _rounds = _plan(1)
    for _operation, rows in accounts:
        m.create_accounts(rows.view(types.ACCOUNT_DTYPE), wall_clock_ns=0)
    for rows in _pending_then_resolve(m, 10_000):  # both routes compiled
        m.commit_batch("create_transfers", rows,
                       m.prepare("create_transfers", len(rows), 0))
    return m


def test_one_resolving_request_one_general_commit_span_with_its_children(
        warm_machine):
    """Sharded or not, a resolving request is one `general_commit` on the
    calling thread with the same children, so `general_commit_ms` and
    `general_sync_ms` read a sharded server with no reader of their own."""
    m = warm_machine
    pending, resolve = _pending_then_resolve(m, 20_000)
    assert len(resolve) == 32 + 6                 # 80 % + 15 % of 40
    with registry.enabled_scope(), txtrace.attribution_scope():
        m.commit_batch("create_transfers", pending,
                       m.prepare("create_transfers", len(pending), 0))
        fast = txtrace.stage_totals()
        txtrace.reset_stages()
        m.commit_batch("create_transfers", resolve,
                       m.prepare("create_transfers", len(resolve), 0))
        totals = txtrace.stage_totals()
        snapshot = registry.snapshot()
    assert "general_commit" not in fast and "full_sync" not in fast
    # The eligibility checks are span `route` on the blocking routes too:
    # the balance bound, and under shards the owners' `mix64` passes.
    checks = {"route": 2 if m.shards else 1}
    if m.shards:  # the blocking sharded fast route: staged, then dispatched
        assert {k: v["count"] for k, v in fast.items()} == dict.fromkeys(
            ("device_execute", "grow", "stage_h2d", "dispatch"), 1) | checks
    counters, histograms = snapshot["counters"], snapshot["histograms"]
    assert {k: v["count"] for k, v in totals.items()} == dict.fromkeys(
        ("device_execute", "general_commit") + CHILDREN, 1) | checks
    assert set(totals) <= set(STAGES)
    # One thread, one top-level span: the self times sum to its duration
    # (`stage_h2d`, a child here, is counted once), and the route's own
    # time is what its five children leave of it.
    assert sum(v["self_us"] for v in totals.values()) == pytest.approx(
        totals["device_execute"]["us"], abs=1.0)
    assert totals["general_commit"]["self_us"] == pytest.approx(
        totals["general_commit"]["us"]
        - sum(totals[c]["us"] for c in CHILDREN), abs=1.0)
    assert all(totals[c]["self_us"] == totals[c]["us"] for c in CHILDREN)
    # Nested: the route inside the closure, the children inside the route.
    assert totals["device_execute"]["us"] >= totals["general_commit"]["us"]
    assert totals["general_commit"]["us"] >= sum(
        totals[c]["us"] for c in CHILDREN)
    assert counters["ops.route.general"] == 1
    assert counters["ops.general.lanes"] == 38
    assert counters["ops.general.postvoid_lanes"] == 38
    assert counters.get("ops.general.retries", 0) == 0
    assert counters.get("sharding.batches", 0) == (2 if m.shards else 0)
    assert histograms["txtrace.stage.full_sync"]["count"] == 1
    assert histograms["txtrace.stage.general_commit"]["count"] == 1
