"""`benchmarks/generators/tpcc_payment.py`: the plan `hot-limits-s8` sends.

What lets the benchmark's unedited check replay session by session (no
flagged account is shared between sessions, and any interleaving of the
sessions' requests gives the same codes and rows), and the shapes the issue
names: TPC-C's cardinalities, NURand(1023, 1, 3000), the shares and amounts,
the opening balance by a customer's share of draws."""

import json
import os

import numpy as np
import pytest

from benchmarks.generators import tpcc_payment
from benchmarks.harness import check
from benchmarks.reference.ledger import AF_DEBITS_LE_CREDITS, ReferenceLedger

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmarks/traffic/tpcc-payment-limits-s8.json")) as f:
    REAL = json.load(f)
SHORT = dict(REAL, preload_per_session=1, window_cap_per_session=2)
SMALL = {
    "generator": "tpcc_payment", "warehouses": 4,
    "districts_per_warehouse": 3, "customers_per_district": 20,
    "nurand_a": 7, "batch": 64, "sessions": 4, "payment_pct": 75,
    "payment_amount": [100, 500_000], "topup_amount": [300, 1_500_000],
    "opening_balance": 300_000,
    "preload_per_session": 3, "window_cap_per_session": 5,
}


@pytest.fixture(scope="module")
def short_plan():
    return tpcc_payment.build(SHORT, 4400000021)     # over 2**31, as the
                                                     # driver's seeds are


def _flagged(plan):
    rows = np.concatenate([r for q in plan["setup"][0]["queues"]
                           for _op, r in q])
    return set(rows["id_lo"][rows["flags"] == AF_DEBITS_LE_CREDITS].tolist())


def _traffic(plan, session):
    return [rows for _op, rows in
            plan["setup"][2]["queues"][session] + plan["window"][session]]


def test_the_deployment_has_tpc_cs_cardinalities(short_plan):
    assert tpcc_payment.counts(REAL) == (80, 240_000, 240_081)
    assert short_plan["account_ids"] == list(range(1, 240_082))
    accounts, funding, _preload = short_plan["setup"]
    assert [p["name"] for p in short_plan["setup"]] == [
        "accounts", "funding", "preload"]
    assert [len(q) for q in accounts["queues"]] == [4] * 8
    assert [len(q) for q in funding["queues"]] == [4] * 8
    rows = np.concatenate([r for q in accounts["queues"] for _op, r in q])
    assert len(rows) == len(set(rows["id_lo"].tolist())) == 240_081
    assert len(_flagged(short_plan)) == 240_000
    assert set(rows["flags"].tolist()) == {0, AF_DEBITS_LE_CREDITS}
    plain = rows["id_lo"][rows["flags"] == 0]
    assert sorted(plain.tolist()) == list(range(1, 82))   # bank, districts
    # Every customer is funded once, by the bank, before any traffic.
    paid = np.concatenate([r for q in funding["queues"] for _op, r in q])
    assert set(paid["credit_account_id_lo"].tolist()) == _flagged(short_plan)
    assert len(paid) == 240_000
    assert set(paid["debit_account_id_lo"].tolist()) == {tpcc_payment.BANK_ID}


def test_no_two_sessions_share_a_flagged_account(short_plan):
    flagged = _flagged(short_plan)
    seen = []
    for s in range(REAL["sessions"]):
        rows = np.concatenate(_traffic(short_plan, s))
        touched = (set(rows["debit_account_id_lo"].tolist())
                   | set(rows["credit_account_id_lo"].tolist()))
        mine = touched & flagged
        # Warehouse s's customers, and what else it touches has no flag.
        low = tpcc_payment.customer_id(REAL, 10 * s, 0)
        assert min(mine) >= low and max(mine) < low + 30_000
        districts = {2 + 10 * s + d for d in range(10)}
        assert touched - mine == districts | {tpcc_payment.BANK_ID}
        seen.append(mine)
    for a in range(len(seen)):
        for b in range(a + 1, len(seen)):
            assert not seen[a] & seen[b]


def test_a_request_is_the_issues_mix(short_plan):
    flagged = _flagged(short_plan)
    for rows in _traffic(short_plan, 3):
        assert len(rows) == 8190
        pays = np.isin(rows["debit_account_id_lo"], list(flagged))
        assert pays.sum() == 8190 * 75 // 100 == 6142
        # Seeded random order, not payments first.
        assert 0.70 < pays[:2048].mean() < 0.80
        amounts = rows["amount_lo"]
        assert amounts[pays].min() >= 100 and amounts[pays].max() <= 500_000
        assert amounts[~pays].min() >= 300
        assert amounts[~pays].max() <= 1_500_000
        assert 2.8 < amounts[~pays].mean() / amounts[pays].mean() < 3.2
        # A payment credits its customer's own district.
        district = (rows["debit_account_id_lo"][pays].astype(np.int64)
                    - tpcc_payment.customer_id(REAL, 0, 0)) // 3000
        assert np.array_equal(rows["credit_account_id_lo"][pays],
                              2 + district.astype(np.uint64))
        assert set(rows["debit_account_id_lo"][~pays].tolist()) == {1}
        # ~614 payment legs a district slot.
        legs = np.bincount(district - 30, minlength=10)
        assert legs.sum() == 6142 and legs.min() > 500 and legs.max() < 730


def test_nurand_is_the_specs_and_its_hot_customers_are_few():
    draws = tpcc_payment.nurand_draws(1023, 3000, 0)
    assert draws.sum() == 1024 * 3000 and draws.min() >= 1
    share = draws / draws.sum()
    top = np.sort(share)[::-1]
    # x | y with the low ten bits all ones: 1023 and 2047 (3071 is past 3000).
    assert 0.018 < top[1] <= top[0] < 0.020
    assert np.argsort(share)[::-1][:2].tolist() in ([1023, 2047], [2047, 1023])
    assert np.median(share) * 3000 < 0.3        # most customers are cold
    rng = np.random.default_rng(3)
    got = tpcc_payment.nurand(rng, 1023, 3000, 0, 400_000)
    assert got.min() >= 0 and got.max() < 3000
    seen = np.bincount(got, minlength=3000) / len(got)
    assert abs(seen[1023] - share[1023]) < 0.002
    # The constant C rotates the customers and nothing else.
    assert np.array_equal(tpcc_payment.nurand_draws(1023, 3000, 17),
                          np.roll(draws, 17))


def test_the_opening_balance_goes_by_a_customers_share_of_draws():
    mix = dict(REAL, opening_balance=2_500_000)
    opening = tpcc_payment.opening_balances(mix, 0)
    draws = tpcc_payment.nurand_draws(1023, 3000, 0)
    assert np.array_equal(opening, 2_500_000 * draws // 1024)
    assert abs(opening.mean() - 2_500_000) < 1     # F is the mean customer's
    assert opening.min() >= 2_000                  # nobody opens with nothing


@pytest.mark.parametrize("seed", [1, 4400000099])
def test_the_same_seed_gives_the_same_plan_and_unique_ids(seed):
    a, b = tpcc_payment.build(SMALL, seed), tpcc_payment.build(SMALL, seed)
    other = tpcc_payment.build(SMALL, seed + 1)
    ids = []
    differs = False
    for s in range(SMALL["sessions"]):
        for mine, same, new in zip(_traffic(a, s), _traffic(b, s),
                                   _traffic(other, s)):
            assert np.array_equal(mine, same)
            differs |= not np.array_equal(mine, new)
            ids.extend(mine["id_lo"].tolist())
    assert differs
    for queue in a["setup"][1]["queues"]:
        for _op, rows in queue:
            ids.extend(rows["id_lo"].tolist())
    assert len(ids) == len(set(ids))
    assert max(ids) < a["unused_ids"]


def _replay(plan, order):
    """The plan through the reference with the window's requests in the
    given order of (session, index) pairs: codes by request, account rows."""
    ledger = ReferenceLedger()
    check.replay_setup(ledger, plan)
    codes = {(s, k): ledger.execute(*plan["window"][s][k]) for s, k in order}
    return codes, ledger.lookup_accounts(plan["account_ids"])


@pytest.mark.parametrize("seed", [2, 4400000031, 77])
def test_any_interleaving_of_the_sessions_gives_the_same_answers(seed):
    """What lets `check.replay_window` replay session by session."""
    plan = tpcc_payment.build(SMALL, seed)
    sessions, depth = SMALL["sessions"], SMALL["window_cap_per_session"]
    by_session = [(s, k) for s in range(sessions) for k in range(depth)]
    round_robin = [(s, k) for k in range(depth) for s in range(sessions)]
    rng = np.random.default_rng(seed)
    at = [0] * sessions
    shuffled = []
    while len(shuffled) < sessions * depth:     # a session's own order kept
        s = int(rng.choice([s for s in range(sessions) if at[s] < depth]))
        shuffled.append((s, at[s]))
        at[s] += 1
    want_codes, want_rows = _replay(plan, by_session)
    assert any(want_codes.values())             # payments were refused
    last_first = sorted(by_session, key=lambda sk: (-sk[0], sk[1]))
    for order in (round_robin, shuffled, last_first):
        codes, rows = _replay(plan, order)
        assert codes == want_codes
        assert check._rows_differing(rows, want_rows) == 0
    # The property is the mix's, not the reference's: a session's own
    # requests out of order do change the answers.
    backwards = [(s, depth - 1 - k) for s, k in by_session]
    codes, _rows = _replay(plan, backwards)
    assert codes != want_codes
