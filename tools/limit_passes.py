"""What a `tpcc_payment` mix does to the general kernel, without a chip: the
share of payments refused and the Jacobi passes every request needs.

    python3 tools/limit_passes.py <mix.json> --seeds 1,2,3 \
        [--opening 2500000,5000000] [--flat] [--window-requests 42]

A numpy model of the ONE feedback these batches have (`ops/transfer_full.py`,
`_kernel_core`): pass k evaluates every payment against its customer's
balance as the outcomes of pass k-1 leave it (pass 1 starts from nothing
accepted), and the loop ends after the first pass that repeats the last
one's outcomes, so a request whose refusals cascade d deep runs d + 2
passes; more than `jacobi_max_passes` (8) and the batch is handed to the
sequential route.  Sessions are replayed one after the other, which the mix
allows (no flagged account is shared).  `tests/test_limits_reference_parity.py`
holds `request_passes` to the machine's own `waves.jacobi_passes`.

Counts only: nothing here is a time, and nothing runs on a device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.generators import tpcc_payment  # noqa: E402


def request_passes(balance: np.ndarray, customer: np.ndarray,
                   is_payment: np.ndarray, amount: np.ndarray,
                   most: int = 64):
    """(passes the kernel's loop runs, accepted mask) for one request:
    `balance[customer]` is what a customer may still spend, a lane either
    pays from it or tops it up, in lane order."""
    n = len(customer)
    order = np.lexsort((np.arange(n), customer))
    who, pays, amt = customer[order], is_payment[order], amount[order]
    head = np.r_[True, who[1:] != who[:-1]]
    first = np.maximum.accumulate(np.where(head, np.arange(n), 0))
    start = balance[who]
    ok = np.zeros(n, dtype=bool)
    for passes in range(1, most + 1):
        delta = np.where(ok, np.where(pays, -amt, amt), 0)
        before = np.cumsum(delta) - delta
        new = ~pays | (start + before - before[first] >= amt)
        stable = passes > 1 and np.array_equal(new, ok)
        ok = new
        if stable:
            break
    accepted = np.empty(n, dtype=bool)
    accepted[order] = ok
    return passes, accepted


def replay(mix: dict, seed: int, window_requests: int, flat: bool = False):
    """One row a preloaded or window request, session by session:
    (in_window, refused payments, passes).  `flat` opens every customer with
    the mix's `opening_balance` itself, whatever its share of draws: the
    form the mix does not use."""
    plan = tpcc_payment.build(mix, seed)
    balance = np.zeros(len(plan["account_ids"]) + 1, dtype=np.int64)
    flagged = np.zeros(len(balance), dtype=bool)
    by_name = {phase["name"]: phase["queues"] for phase in plan["setup"]}
    for queue in by_name["accounts"]:
        for _op, rows in queue:
            flagged[rows["id_lo"][rows["flags"] != 0]] = True
    for queue in by_name["funding"]:
        for _op, rows in queue:
            balance[rows["credit_account_id_lo"]] = (
                mix["opening_balance"] if flat
                else rows["amount_lo"].astype(np.int64))
    rows_out = []
    for preload, window in zip(by_name["preload"], plan["window"]):
        steps = [(False, s) for s in preload] + [
            (True, s) for s in window[:window_requests]]
        for in_window, (_op, rows) in steps:
            debit = rows["debit_account_id_lo"].astype(np.int64)
            is_payment = flagged[debit]
            customer = np.where(
                is_payment, debit, rows["credit_account_id_lo"]).astype(
                    np.int64)
            amount = rows["amount_lo"].astype(np.int64)
            passes, ok = request_passes(balance, customer, is_payment, amount)
            np.add.at(balance, customer[ok],
                      np.where(is_payment, -amount, amount)[ok])
            rows_out.append((in_window, int((~ok).sum()), passes))
    return np.array(rows_out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("mix")
    p.add_argument("--seeds", required=True)
    p.add_argument("--opening", default=None,
                   help="values of opening_balance to try (default: the mix's)")
    p.add_argument("--flat", action="store_true",
                   help="one opening balance for all, not by share of draws")
    p.add_argument("--window-requests", type=int, default=42,
                   help="window requests a session replayed (a window the "
                        "clock ends holds fewer than its cap)")
    args = p.parse_args(argv)
    with open(args.mix) as f:
        mix = json.load(f)
    openings = ([int(v) for v in args.opening.split(",")] if args.opening
                else [mix["opening_balance"]])
    for opening in openings:
        mix["opening_balance"] = opening
        for seed in (int(s) for s in args.seeds.split(",")):
            rows = replay(mix, seed, args.window_requests, args.flat)
            window = rows[rows[:, 0] == 1]
            print(json.dumps({
                "opening_balance": opening, "seed": seed,
                "by_draws": not args.flat,
                "refused_pct_window": round(
                    100.0 * window[:, 1].sum() / (len(window) * mix["batch"]),
                    4),
                "passes_mean_window": round(float(window[:, 2].mean()), 4),
                "passes_histogram_window": np.bincount(window[:, 2]).tolist(),
                "passes_max_setup_and_window": int(rows[:, 2].max()),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
