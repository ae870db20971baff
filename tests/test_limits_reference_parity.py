"""Accounts that may not overdraw, as a deployment (`benchmarks/configs/
tb-limits-1r`, the cell `hot-limits-s8`), at sizes a CPU test can hold.

Seeded plans of the benchmark's `tpcc_payment` generator through
`TpuStateMachine`'s normal routing against the benchmark's plain reference:
result codes of every request and every account row, over seeds and over an
opening balance of nothing, little and plenty; the passes the kernel ran
against `tools/limit_passes.py`'s model of them (which set the cell's opening
balance); a request built to cascade deeper than `jacobi_max_passes`, which
takes the sequential route and still agrees; and the general route's new
counters."""

import os
import sys

import numpy as np
import pytest

from benchmarks.generators import tpcc_payment
from benchmarks.harness import check
from benchmarks.reference.ledger import ReferenceLedger
from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import limit_passes  # noqa: E402

EXCEEDS_CREDITS = int(types.CreateTransferResult.exceeds_credits)
LANES = 64
MIX = {
    "generator": "tpcc_payment", "warehouses": 3,
    "districts_per_warehouse": 4, "customers_per_district": 12,
    "nurand_a": 7, "batch": 48, "sessions": 3, "payment_pct": 75,
    "payment_amount": [100, 500_000], "topup_amount": [300, 1_500_000],
    "opening_balance": 400_000,
    "preload_per_session": 2, "window_cap_per_session": 4,
}


def _requests(plan):
    """(is it one of the window's kind, operation, rows) for the plan's
    requests in a commit order: the set-up's phases, then the sessions'
    window queues round-robin."""
    for phase in plan["setup"]:
        for queue in phase["queues"]:
            for operation, rows in queue:
                yield phase["name"] == "preload", operation, rows
    for k in range(max(map(len, plan["window"]))):
        for queue in plan["window"]:
            if k < len(queue):
                yield (True,) + queue[k]


def _machine():
    m = TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=9, transfers_capacity_log2=12,
                     posted_capacity_log2=6),
        batch_lanes=LANES)
    return m


def _execute(m, operation, rows):
    got = getattr(m, operation)(rows.view(
        types.ACCOUNT_DTYPE if operation == "create_accounts"
        else types.TRANSFER_DTYPE), wall_clock_ns=0)
    return [(int(i), int(c)) for i, c in got]


def _pairs(codes):
    return [(int(i), int(c)) for i, c in codes]


@pytest.mark.parametrize("opening", [0, 400_000, 10**9])
@pytest.mark.parametrize("seed", [11, 3000000019, 4400000077])
def test_the_plan_answers_as_the_plain_reference(seed, opening):
    mix = dict(MIX, opening_balance=opening)
    plan = tpcc_payment.build(mix, seed)
    m, ref = _machine(), ReferenceLedger()
    sequential = []
    route = m._sequential
    m._sequential = lambda *a: (sequential.append(1), route(*a))[1]
    refused = sent = 0
    for traffic, operation, rows in _requests(plan):
        got = _execute(m, operation, rows)
        assert got == _pairs(ref.execute(operation, rows))
        if traffic:
            assert {c for _i, c in got} <= {EXCEEDS_CREDITS}
            refused += len(got)
            sent += len(rows)
    assert sequential == []
    ids = plan["account_ids"]
    assert len(ids) == 1 + 12 + 144
    assert check._rows_differing(
        m.lookup_accounts(ids), ref.lookup_accounts(ids)) == 0
    if opening == 10**9:
        assert refused == 0
    else:
        assert 0 < refused < sent
    if opening == 0:     # nobody was ever funded: top-ups alone pay
        assert refused > sent // 4


@pytest.mark.parametrize("seed", [5, 4400000013])
def test_the_passes_the_kernel_ran_are_the_models(seed):
    """`tools/limit_passes.py` set the cell's opening balance from its count
    of passes and refusals: both are the machine's, request by request, in
    the model's own order (session by session, which the mix allows)."""
    plan = tpcc_payment.build(MIX, seed)
    m = _machine()
    rows = []
    with registry.enabled_scope():
        for phase in plan["setup"][:2]:
            for queue in phase["queues"]:
                for operation, batch in queue:
                    _execute(m, operation, batch)
        for preload, window in zip(plan["setup"][2]["queues"],
                                   plan["window"]):
            for k, (operation, batch) in enumerate(preload + window):
                before = registry.snapshot()["histograms"].get(
                    "waves.jacobi_passes", {"sum": 0})["sum"]
                got = _execute(m, operation, batch)
                after = registry.snapshot()["histograms"][
                    "waves.jacobi_passes"]["sum"]
                rows.append((int(k >= len(preload)), len(got),
                             after - before))
        counters = registry.snapshot()["counters"]
    want = limit_passes.replay(MIX, seed, MIX["window_cap_per_session"])
    assert rows == [tuple(r) for r in want.tolist()]
    refused = sum(r[1] for r in rows)
    assert refused > 0 and max(r[2] for r in rows) >= 3
    # The new counters: refused lanes of committed general batches, and the
    # lanes a limit is evaluated on (the payments: a customer is debited).
    assert counters["ops.general.rejected_lanes"] == refused
    lanes = len(rows) * MIX["batch"]
    funding = MIX["warehouses"] * 4 * 12
    assert counters["ops.general.lanes"] == lanes + funding
    assert counters["ops.general.limit_lanes"] == lanes * 75 // 100
    assert "ops.general.seq_handovers" not in counters
    assert counters.get("ops.sequential_batches", 0) == 0


# Ten cents to spend and 24 payments in a row: each pass settles one more of
# them (13 passes by the model), past the kernel's 8.
DEEP = [2, 10, 1, 8, 1, 7, 2, 5, 6, 10, 3, 11, 4, 10, 9, 7, 1, 2, 9, 2, 10,
        3, 10, 1]


def test_a_cascade_deeper_than_the_pass_budget_takes_the_sequential_route():
    mix = dict(MIX, opening_balance=0)
    plan = tpcc_payment.build(mix, 7)
    m, ref = _machine(), ReferenceLedger()
    for phase in plan["setup"][:2]:
        for queue in phase["queues"]:
            for operation, rows in queue:
                assert _execute(m, operation, rows) == _pairs(
                    ref.execute(operation, rows))
    customer = tpcc_payment.customer_id(mix, 0, 0)
    n = len(DEEP)
    pays = np.ones(n, dtype=bool)
    amount = np.array(DEEP, dtype=np.int64)
    passes, ok = limit_passes.request_passes(
        np.full(customer + 1, 10, dtype=np.int64),
        np.full(n, customer, dtype=np.int64), pays, amount)
    assert passes == 13 > m.config.jacobi_max_passes
    first = tpcc_payment.FIRST_UNUSED_ID + 1
    fund = tpcc_payment._transfers(
        np.array([first], dtype=np.uint64), tpcc_payment.BANK_ID, customer,
        10)
    deep = tpcc_payment._transfers(
        np.arange(first + 1, first + 1 + n, dtype=np.uint64), customer,
        tpcc_payment.FIRST_DISTRICT_ID, amount)
    with registry.enabled_scope():
        for rows in (fund, deep):
            got = _execute(m, "create_transfers", rows)
            assert got == _pairs(ref.execute("create_transfers", rows))
        counters = registry.snapshot()["counters"]
    assert got == [(int(i), EXCEEDS_CREDITS) for i in np.flatnonzero(~ok)]
    assert 0 < len(got) < n
    assert counters["ops.general.seq_handovers"] == 1
    assert counters["ops.sequential_batches"] == 1
    assert counters["ops.general.retries"] == 1
    assert counters["ops.route.general"] == 1          # the funding alone
    ids = plan["account_ids"]
    assert check._rows_differing(
        m.lookup_accounts(ids), ref.lookup_accounts(ids)) == 0
    sent = np.concatenate([fund["id_lo"], deep["id_lo"]]).tolist()
    assert check._rows_differing(
        m.lookup_transfers(sent), ref.lookup_transfers(sent)) == 0
