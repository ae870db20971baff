"""TPC-C Payment against accounts that may not overdraw: set-up plan and
window plan from a seed and a mix's parameters.

The source is TPC-C rev. 5.11, clause 2.5 (the Payment transaction) with the
cardinalities of clause 4.3.3; the ledger's side of it is upstream's
`AccountFlags.debits_must_not_exceed_credits` on the account that pays.  A
mix (`benchmarks/traffic/<name>.json`) gives:

    warehouses                W; session s is the one terminal of warehouse s
    districts_per_warehouse   10 in TPC-C
    customers_per_district    3,000 in TPC-C
    nurand_a                  A of NURand(A, 1, customers_per_district): 1023
    batch                     events per request
    sessions                  client sessions (= warehouses)
    payment_pct               whole percent of a request's events that pay;
                              the rest top a customer up
    payment_amount            [least, most] in cents, uniform
    topup_amount              [least, most] in cents, uniform
    opening_balance           F: bank -> every customer, once, in set-up, F
                              times the customer's share of NURand's draws
                              relative to the mean (`opening_balances`)
    preload_per_session       requests of the window's kind sent in set-up
    window_cap_per_session    the most a session may send inside the window

Accounts: id 1 the bank, ids 2..1+W*D the districts, then the customers,
warehouse by warehouse and district by district.  Only customers carry
`debits_must_not_exceed_credits`.  A payment debits a customer and credits
that customer's district; a top-up debits the bank and credits a customer.
The district is uniform over the warehouse's own, the customer within it
NURand(A, 1, customers) with one constant C a run, drawn from the seed
(clause 2.1.6).

Session s touches only warehouse s: its districts, its customers, and the
bank, and neither the bank nor a district carries a flag.  So a flagged
account sees one session alone, in that session's own order (one request in
flight), credits to the unflagged accounts commute, and no result depends on
the order in which DIFFERENT sessions' requests commit: the check may replay
session by session.  Results that do depend on order lie INSIDE a request: a
payment is refused (`exceeds_credits`, 54) by what earlier events of the same
request left of its customer's balance, top-ups included.  Every transfer id
is unique.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from benchmarks.generators.ledger_mix import (
    FIRST_TRANSFER_ID, FIRST_UNUSED_ID, Step,
)
from benchmarks.reference.ledger import (
    ACCOUNT_DTYPE, AF_DEBITS_LE_CREDITS, TRANSFER_DTYPE,
)

BANK_ID = 1
FIRST_DISTRICT_ID = 2


def counts(mix: dict) -> Tuple[int, int, int]:
    """(districts, customers, accounts) of the whole deployment."""
    districts = mix["warehouses"] * mix["districts_per_warehouse"]
    customers = districts * mix["customers_per_district"]
    return districts, customers, 1 + districts + customers


def customer_id(mix: dict, district, customer):
    """The account of customer 0.. of global district 0.. (arrays or ints)."""
    return (FIRST_DISTRICT_ID + counts(mix)[0]
            + district * mix["customers_per_district"] + customer)


def nurand(rng, a: int, n: int, c: int, size: int) -> np.ndarray:
    """TPC-C clause 2.1.6, NURand(A, 1, n) less one: 0..n-1."""
    return ((rng.integers(0, a + 1, size) | rng.integers(1, n + 1, size))
            + c) % n


def nurand_draws(a: int, n: int, c: int) -> np.ndarray:
    """How many of the (A + 1) x n equally likely pairs `nurand` draws from
    give each customer 0..n-1: its exact share of draws, in whole pairs."""
    pairs = np.arange(a + 1)[:, None] | np.arange(1, n + 1)[None, :]
    return np.bincount((pairs.ravel() + c) % n, minlength=n)


def opening_balances(mix: dict, c: int) -> np.ndarray:
    """The opening balance of customers 0..n-1 of any district: F times the
    customer's share of draws relative to the mean (exact in integers), so
    that every customer opens with as many of ITS OWN requests' worth of
    payments, the hottest as the coldest.  One F for all does not do: the two
    customers of a district that NURand draws 58 times as often as the mean
    then run dry, and their refusals cascade deeper than the kernel's pass
    budget (`tools/limit_passes.py --flat`; PERF.md section 4)."""
    n = mix["customers_per_district"]
    draws = nurand_draws(mix["nurand_a"], n, c)
    return mix["opening_balance"] * draws // (mix["nurand_a"] + 1)


def _accounts(ids: np.ndarray, flags: int, rng) -> np.ndarray:
    rows = np.zeros(len(ids), dtype=ACCOUNT_DTYPE)
    rows["id_lo"] = ids
    rows["user_data_64"] = rng.integers(0, 1 << 62, len(ids), dtype=np.uint64)
    rows["ledger"] = 1
    rows["code"] = 10
    rows["flags"] = flags
    return rows


def _transfers(ids, debit, credit, amount) -> np.ndarray:
    rows = np.zeros(len(ids), dtype=TRANSFER_DTYPE)
    rows["id_lo"] = ids
    rows["debit_account_id_lo"] = debit
    rows["credit_account_id_lo"] = credit
    rows["amount_lo"] = amount
    rows["ledger"] = 1
    rows["code"] = 7
    return rows


def _chunks(operation: str, rows: np.ndarray, batch: int) -> List[Step]:
    return [(operation, rows[at:at + batch])
            for at in range(0, len(rows), batch)]


def _request(mix: dict, warehouse: int, ids, rng, c: int) -> np.ndarray:
    """One request of warehouse `warehouse`'s terminal: payments and top-ups
    in seeded random order."""
    n = len(ids)
    d_per_w = mix["districts_per_warehouse"]
    is_payment = np.zeros(n, dtype=bool)
    is_payment[: n * mix["payment_pct"] // 100] = True
    rng.shuffle(is_payment)
    district = warehouse * d_per_w + rng.integers(0, d_per_w, n)
    customer = customer_id(mix, district, nurand(
        rng, mix["nurand_a"], mix["customers_per_district"], c, n))
    pay, top = mix["payment_amount"], mix["topup_amount"]
    amount = np.where(is_payment,
                      rng.integers(pay[0], pay[1] + 1, n),
                      rng.integers(top[0], top[1] + 1, n))
    return _transfers(
        ids,
        np.where(is_payment, customer, BANK_ID),
        np.where(is_payment, FIRST_DISTRICT_ID + district, customer),
        amount)


def build(mix: dict, seed: int) -> dict:
    """{"setup": [phase...], "window": [queue per session], "account_ids",
    "unused_ids"}.  A phase is {"name", "queues": one list of steps per
    session}."""
    rng = np.random.default_rng(seed)
    batch, sessions = mix["batch"], mix["sessions"]
    if sessions != mix["warehouses"]:
        raise ValueError("one session a warehouse: a terminal has one home")
    d_per_w, c_per_d = (mix["districts_per_warehouse"],
                        mix["customers_per_district"])
    c = int(rng.integers(0, mix["nurand_a"] + 1))
    next_id = FIRST_TRANSFER_ID
    opening = opening_balances(mix, c)

    account_queues: List[List[Step]] = []
    funding: List[List[Step]] = []
    for s in range(sessions):
        mine = np.arange(s * d_per_w, (s + 1) * d_per_w, dtype=np.uint64)
        customers = customer_id(
            mix, np.repeat(mine, c_per_d),
            np.tile(np.arange(c_per_d, dtype=np.uint64), d_per_w))
        plain = FIRST_DISTRICT_ID + mine
        if s == 0:
            plain = np.concatenate([[np.uint64(BANK_ID)], plain])
        rows = np.concatenate([
            _accounts(plain, 0, rng),
            _accounts(customers, AF_DEBITS_LE_CREDITS, rng)])
        account_queues.append(_chunks("create_accounts", rows, batch))
        ids = np.arange(next_id, next_id + len(customers), dtype=np.uint64)
        next_id += len(customers)
        funding.append(_chunks("create_transfers", _transfers(
            ids, BANK_ID, customers, np.tile(opening, d_per_w)), batch))

    n_pre, n_win = mix["preload_per_session"], mix["window_cap_per_session"]
    preload: List[List[Step]] = []
    window: List[List[Step]] = []
    for s in range(sessions):
        steps: List[Step] = []
        for _k in range(n_pre + n_win):
            ids = np.arange(next_id, next_id + batch, dtype=np.uint64)
            next_id += batch
            steps.append(("create_transfers",
                          _request(mix, s, ids, rng, c)))
        preload.append(steps[:n_pre])
        window.append(steps[n_pre:])
    assert next_id < FIRST_UNUSED_ID
    return {
        "setup": [{"name": "accounts", "queues": account_queues},
                  {"name": "funding", "queues": funding},
                  {"name": "preload", "queues": preload}],
        "window": window,
        "account_ids": list(range(1, counts(mix)[2] + 1)),
        "unused_ids": FIRST_UNUSED_ID,
    }
