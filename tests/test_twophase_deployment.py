"""Two-phase payments as a deployment (`benchmarks/configs/tb-twophase-1r`).

`start`'s three table options, each on its own, reaching `LedgerConfig` and
the device ledger; a seeded pending/resolve plan of the benchmark's generator
through `TpuStateMachine`'s normal routing against the benchmark's plain
reference; the posted table pre-sized against grown from small; and the
general route's spans and counters."""

import argparse

import numpy as np
import pytest

from benchmarks.generators import ledger_mix
from benchmarks.harness import check
from benchmarks.reference.ledger import ReferenceLedger
from tigerbeetle_tpu import cli, types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs import txtrace as txtrace_mod
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import STAGES, txtrace
from tigerbeetle_tpu.vsr.replica import Replica

# -- start's table options -----------------------------------------------------


def _args(accounts=None, transfers=None, posted=None, shards=None):
    return argparse.Namespace(
        cache_accounts_log2=accounts, cache_transfers_log2=transfers,
        cache_posted_log2=posted, shards=shards)


@pytest.mark.parametrize("given,want", [
    ({}, (16, 18, 16)),                                  # today's defaults
    ({"accounts": 10}, (10, 12, 16)),                    # transfers follow
    ({"transfers": 20}, (16, 20, 16)),
    ({"posted": 22}, (16, 18, 22)),
    ({"accounts": 21, "transfers": 23, "posted": 22}, (21, 23, 22)),
    ({"accounts": 12, "posted": 9, "shards": 4}, (12, 14, 9)),
])
def test_each_table_option_applies_on_its_own(given, want):
    config = cli._ledger_config(_args(**given))
    assert (config.accounts_capacity_log2, config.transfers_capacity_log2,
            config.posted_capacity_log2) == want
    # Nothing else of the default moves.
    assert config == LedgerConfig(*want)


@pytest.mark.parametrize("flags", [
    ["--cache-posted-log2", "-1"],
    ["--cache-posted-log2", "33"],
    ["--cache-transfers-log2", "64"],
    ["--cache-accounts-log2", "31"],      # its transfers table would be 2^33
    ["--cache-posted-log2", "1", "--shards", "4"],
])
def test_a_size_the_tables_cannot_take_is_refused_at_start(
        tmp_path, capsys, monkeypatch, flags):
    monkeypatch.setenv("TB_SHARDS", "0")   # `--shards` writes its env twin
    rc = cli.main(["start", str(tmp_path / "never_opened.tb")] + flags)
    assert rc == 1
    err = capsys.readouterr().err
    assert "error: --cache-" in err and "slots" in err


@pytest.fixture
def started(tmp_path, monkeypatch):
    """`cli.main(["start", ...])` up to the serve loop: the replica that
    `run_server` would have served, without warm-up."""
    from tigerbeetle_tpu.net import bus

    served = []
    monkeypatch.setattr(bus, "run_server",
                        lambda replica, *a, **kw: served.append(replica))
    monkeypatch.setattr(TpuStateMachine, "warmup", lambda self: None)
    monkeypatch.setattr(cli, "_install_sigterm_atexit", lambda: None)
    monkeypatch.setenv("TB_SHARDS", "0")   # `--shards` writes its env twin
    path = str(tmp_path / "start.tb")
    Replica.format(path, cluster=0)

    def start(*flags):
        assert cli.main(["start", path, "--addresses", "127.0.0.1:0",
                         "--no-engine", *flags]) == 0
        (replica,) = served
        return replica

    yield start
    for replica in served:
        replica.close()


@pytest.mark.parametrize("flags,want", [
    ((), (16, 18, 16)),
    (("--cache-posted-log2", "12"), (16, 18, 12)),
    (("--cache-accounts-log2", "10", "--cache-transfers-log2", "12",
      "--cache-posted-log2", "11", "--shards", "2"), (10, 12, 11)),
])
def test_start_sizes_the_device_ledger(started, flags, want):
    machine = started(*flags).machine
    config = machine.config
    assert (config.accounts_capacity_log2, config.transfers_capacity_log2,
            config.posted_capacity_log2) == want
    ledger = machine.ledger
    assert (ledger.accounts.capacity, ledger.transfers.capacity,
            ledger.posted.capacity) == tuple(1 << n for n in want)
    assert machine.shards == (2 if "--shards" in flags else 0)


# -- the plan through the machine's normal routing -------------------------------

MIX = {
    "generator": "ledger_mix", "accounts": 64, "batch": 48, "sessions": 3,
    "cycle": ["pending", "resolve"],
    "resolve": {"post_pct": 80, "void_pct": 15},
    "preload_per_session": 2, "window_cap_per_session": 4,
    "amount_max": 1000,
}
LANES = 64


def _requests(seed):
    """The plan's requests in a commit order: accounts, then the sessions'
    queues round-robin (a session resolves only what it had acknowledged)."""
    plan = ledger_mix.build(MIX, seed)
    for phase in plan["setup"]:
        for queue in phase["queues"]:
            yield from queue
    depth = max(map(len, plan["window"]))
    for k in range(depth):
        for queue in plan["window"]:
            if k < len(queue):
                yield queue[k]


def _machine(posted_log2):
    m = TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=8, transfers_capacity_log2=12,
                     posted_capacity_log2=posted_log2),
        batch_lanes=LANES)
    grown = []
    grow = m._table_grow
    m._table_grow = lambda table, name, capacity: (
        grown.append(name), grow(table, name, capacity))[1]
    return m, grown


def _run(m, seed):
    """Every request's codes, and every id this plan could have created."""
    codes, ids = [], []
    for operation, rows in _requests(seed):
        got = getattr(m, operation)(rows.view(
            types.ACCOUNT_DTYPE if operation == "create_accounts"
            else types.TRANSFER_DTYPE), wall_clock_ns=0)
        codes.append([(int(i), int(c)) for i, c in got])
        if operation == "create_transfers":
            ids.extend(int(i) for i in rows["id_lo"])
    return codes, ids


@pytest.mark.parametrize("seed", [11, 3000000019, 77])
def test_twophase_plan_answers_as_the_plain_reference(seed):
    m, _grown = _machine(posted_log2=10)
    ref = ReferenceLedger()
    general = []
    route = m._commit_general
    m._commit_general = lambda *a: (general.append(1), route(*a))[1]
    codes, ids = _run(m, seed)
    want = [ref.execute(op, rows) for op, rows in _requests(seed)]
    assert codes == [[(int(i), int(c)) for i, c in w] for w in want]
    assert all(c == [] for c in codes)            # the mix never fails
    # Half the transfer requests resolve, and only those take the general
    # kernel: machine._fast_path_ok's routing, nothing picked by the test.
    assert len(general) == MIX["sessions"] * 3
    accounts = list(range(1, MIX["accounts"] + 1))
    assert check._rows_differing(
        m.lookup_accounts(accounts), ref.lookup_accounts(accounts)) == 0
    got = m.lookup_accounts(accounts)
    assert got["debits_pending_lo"].sum() > 0     # the 5 % left pending
    assert got["debits_posted_lo"].sum() > 0
    ids.append(ledger_mix.FIRST_UNUSED_ID)        # never created
    want_rows = ref.lookup_transfers(ids)
    assert len(want_rows) == len(ids) - 1
    assert check._rows_differing(m.lookup_transfers(ids), want_rows) == 0


@pytest.mark.parametrize("second", ["same", "opposite"])
def test_a_second_resolve_reads_the_posted_row_back(second):
    """What the window's mix never sends: a post or void of a pending that
    is already resolved.  Its code comes from the posted table's fulfillment
    row and nothing else, so a dropped or wrong write there shows here."""
    m, _grown = _machine(posted_log2=10)
    ref = ReferenceLedger()
    _run(m, 11)
    requests = list(_requests(11))
    for operation, rows in requests:
        ref.execute(operation, rows)
    first = [rows for op, rows in requests
             if op == "create_transfers" and rows["pending_id_lo"].any()][-1]
    pending = [rows for op, rows in requests
               if op == "create_transfers"
               and first["pending_id_lo"][0] in rows["id_lo"]][0]
    was_post = first["flags"] == ledger_mix.TF_POST
    assert was_post.any() and not was_post.all()
    still_open = np.setdiff1d(pending["id_lo"], first["pending_id_lo"])
    assert len(still_open) >= 1                   # the 5 % left pending
    again = np.zeros(len(first) + 1, dtype=first.dtype)
    again[:-1] = first
    again["pending_id_lo"][-1] = still_open[0]
    again["flags"][-1] = ledger_mix.TF_VOID
    again["id_lo"] = ledger_mix.FIRST_UNUSED_ID + 1 + np.arange(len(again))
    if second == "opposite":
        again["flags"][:-1] = np.where(was_post, ledger_mix.TF_VOID,
                                       ledger_mix.TF_POST)
        again["amount_lo"] = 0                    # a void; a post in full
    got = m.create_transfers(again.view(types.TRANSFER_DTYPE),
                             wall_clock_ns=0)
    result = types.CreateTransferResult
    want = [(i, int(result.pending_transfer_already_posted if p
                    else result.pending_transfer_already_voided))
            for i, p in enumerate(was_post)]      # the open one: 0, unlisted
    assert [(int(i), int(c)) for i, c in got] == want
    assert [(int(i), int(c))
            for i, c in ref.execute("create_transfers", again)] == want
    accounts = list(range(1, MIX["accounts"] + 1))
    assert check._rows_differing(
        m.lookup_accounts(accounts), ref.lookup_accounts(accounts)) == 0


def test_posted_table_presized_equals_grown():
    sized, sized_grown = _machine(posted_log2=10)
    small, small_grown = _machine(posted_log2=4)
    (codes, ids), other = _run(sized, 5), _run(small, 5)
    assert (codes, ids) == other
    assert np.array_equal(sized.lookup_transfers(ids),
                          small.lookup_transfers(ids))
    assert sized_grown == []                      # pre-sized: never grows
    assert set(small_grown) == {"posted"} and len(small_grown) >= 4
    assert small.ledger.posted.capacity == sized.ledger.posted.capacity
    for name in ("accounts", "transfers", "posted"):
        a, b = getattr(sized.ledger, name), getattr(small.ledger, name)
        assert int(a.count) == int(b.count)
    assert sized.balances_snapshot() == small.balances_snapshot()
    assert sized.digest() == small.digest()


# -- the general route's spans and counters ---------------------------------------

CHILDREN = ("grow", "stage_h2d", "dispatch", "full_sync", "index_append")


def _pending_then_resolve(m, first_id):
    pending = ledger_mix._transfers(
        np.arange(first_id, first_id + 40, dtype=np.uint64), 64,
        np.random.default_rng(first_id), 1000, ledger_mix.TF_PENDING)
    resolve = ledger_mix._resolve(
        np.arange(first_id + 100, first_id + 140, dtype=np.uint64), pending,
        np.random.default_rng(first_id + 1), MIX["resolve"])
    return (pending.view(types.TRANSFER_DTYPE),
            resolve.view(types.TRANSFER_DTYPE))


@pytest.fixture(scope="module")
def warm_machine():
    m, _ = _machine(posted_log2=10)
    for operation, rows in _requests(1):
        if operation == "create_accounts":
            m.create_accounts(rows.view(types.ACCOUNT_DTYPE), wall_clock_ns=0)
    pending, resolve = _pending_then_resolve(m, 10_000)
    for rows in (pending, resolve):               # both routes compiled
        m.commit_batch("create_transfers", rows,
                       m.prepare("create_transfers", len(rows), 0))
    return m


def test_one_general_request_one_span_with_its_children(warm_machine):
    m = warm_machine
    pending, resolve = _pending_then_resolve(m, 20_000)
    assert len(resolve) == 32 + 6                 # 80 % + 15 % of 40
    with registry.enabled_scope(), txtrace.attribution_scope():
        m.commit_batch("create_transfers", pending,
                       m.prepare("create_transfers", len(pending), 0))
        assert "general_commit" not in txtrace.stage_totals()
        txtrace.reset_stages()
        m.commit_batch("create_transfers", resolve,
                       m.prepare("create_transfers", len(resolve), 0))
        totals = txtrace.stage_totals()
        snapshot = registry.snapshot()
    counters, histograms = snapshot["counters"], snapshot["histograms"]
    assert {k: v["count"] for k, v in totals.items()} == dict.fromkeys(
        ("device_execute", "route", "general_commit") + CHILDREN, 1)
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
    assert histograms["txtrace.stage.full_sync"]["count"] == 1
    assert histograms["txtrace.stage.general_commit"]["count"] == 1


def test_general_route_sites_are_free_when_off(warm_machine, monkeypatch):
    """Off, the route reads no clock for its spans and builds none."""
    m = warm_machine
    pending, resolve = _pending_then_resolve(m, 30_000)
    assert not txtrace.active
    monkeypatch.setattr(
        txtrace_mod, "_StageSpan",
        lambda *a: pytest.fail("an inactive stage built a span"))
    handed = []
    stage = txtrace.stage
    monkeypatch.setattr(
        txtrace, "stage",
        lambda *a, **kw: (handed.append(stage(*a, **kw)), handed[-1])[1])
    for rows in (pending, resolve):
        m.commit_batch("create_transfers", rows,
                       m.prepare("create_transfers", len(rows), 0))
    assert len(handed) >= 2 + len(CHILDREN)
    assert all(h is txtrace_mod._STAGE_OFF for h in handed)
