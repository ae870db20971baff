"""The one general generator of ledger traffic: set-up plan and window plan
from a seed and a mix's parameters.

A mix (`benchmarks/traffic/<name>.json`) gives:

    accounts                  accounts created in set-up (ids 1..accounts)
    batch                     events per request
    sessions                  client sessions, each with one request in flight
    cycle                     the steps a session repeats, each one request:
                              "plain"   `batch` plain transfers
                              "pending" `batch` pending transfers, timeout 0
                              "resolve" posts/voids the session's PREVIOUS
                                        pending batch (shares under `resolve`)
    preload_per_session       requests of the cycle each session sends in set-up
    window_cap_per_session    the most it may send inside the window
    resolve.post_pct, resolve.void_pct  whole percents of a pending batch
                              posted in full and voided; the rest stays pending

Every seed gives the same sizes in the same order of steps; only ids' pairing,
amounts and which lanes are posted or voided change.  Every transfer id is
unique, no account carries a limit, and a session resolves only a batch it has
had acknowledged before: so every event's result is OK and no result depends
on the order in which sessions' requests commit.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from benchmarks.reference.ledger import (
    ACCOUNT_DTYPE, TF_PENDING, TF_POST, TF_VOID, TRANSFER_DTYPE,
)

FIRST_TRANSFER_ID = 1 << 32
# Ids that no plan ever creates: the lookups' "never created" share.
FIRST_UNUSED_ID = 1 << 48

Step = Tuple[str, np.ndarray]      # (operation, rows)


def _accounts(ids: np.ndarray, rng) -> np.ndarray:
    rows = np.zeros(len(ids), dtype=ACCOUNT_DTYPE)
    rows["id_lo"] = ids
    rows["user_data_64"] = rng.integers(0, 1 << 62, len(ids), dtype=np.uint64)
    rows["user_data_32"] = rng.integers(0, 1 << 31, len(ids), dtype=np.uint32)
    rows["ledger"] = 1
    rows["code"] = 10
    return rows


def _transfers(ids, n_accounts: int, rng, amount_max: int, flags: int
               ) -> np.ndarray:
    """Uniform random debit/credit pairs over the accounts, never equal
    (upstream `benchmark_load.zig`'s default distribution)."""
    n = len(ids)
    debit = rng.integers(0, n_accounts, n)
    credit = (debit + rng.integers(1, n_accounts, n)) % n_accounts
    rows = np.zeros(n, dtype=TRANSFER_DTYPE)
    rows["id_lo"] = ids
    rows["debit_account_id_lo"] = debit + 1
    rows["credit_account_id_lo"] = credit + 1
    rows["amount_lo"] = rng.integers(1, amount_max, n, dtype=np.uint64)
    rows["ledger"] = 1
    rows["code"] = 7
    rows["flags"] = flags
    return rows


def _resolve(ids, pending: np.ndarray, rng, share: dict) -> np.ndarray:
    n = len(pending)
    n_post = n * share["post_pct"] // 100
    n_void = n * share["void_pct"] // 100
    lanes = rng.permutation(n)[: n_post + n_void]
    is_post = np.zeros(n, dtype=bool)
    is_post[lanes[:n_post]] = True
    lanes.sort()
    rows = np.zeros(len(lanes), dtype=TRANSFER_DTYPE)
    rows["id_lo"] = ids[: len(lanes)]
    rows["pending_id_lo"] = pending["id_lo"][lanes]
    rows["amount_lo"] = np.where(is_post[lanes], pending["amount_lo"][lanes], 0)
    rows["flags"] = np.where(is_post[lanes], TF_POST, TF_VOID)
    return rows


def build(mix: dict, seed: int) -> dict:
    """{"setup": [phase...], "window": [queue per session], "unused_ids"}.
    A phase is {"name", "queues": one list of steps per session}."""
    rng = np.random.default_rng(seed)
    n_acc, batch, sessions = mix["accounts"], mix["batch"], mix["sessions"]
    amount_max = mix.get("amount_max", 1000)
    share = mix.get("resolve", {"post_pct": 0, "void_pct": 0})
    cycle: List[str] = mix["cycle"]
    next_id = FIRST_TRANSFER_ID

    account_rows = _accounts(np.arange(1, n_acc + 1, dtype=np.uint64), rng)
    # One request a session (more where they do not fit): every session is
    # then registered before the preload, which so starts as the window does,
    # all sessions at once.
    per_session = -(-n_acc // sessions)
    account_queues: List[List[Step]] = []
    for s in range(sessions):
        mine = account_rows[s * per_session:(s + 1) * per_session]
        account_queues.append([("create_accounts", mine[at:at + batch])
                               for at in range(0, len(mine), batch)])

    n_pre, n_win = mix["preload_per_session"], mix["window_cap_per_session"]
    preload: List[List[Step]] = []
    window: List[List[Step]] = []
    for _s in range(sessions):
        steps: List[Step] = []
        last_pending = None
        for k in range(n_pre + n_win):
            kind = cycle[k % len(cycle)]
            ids = np.arange(next_id, next_id + batch, dtype=np.uint64)
            next_id += batch
            if kind == "plain":
                rows = _transfers(ids, n_acc, rng, amount_max, 0)
            elif kind == "pending":
                rows = last_pending = _transfers(ids, n_acc, rng, amount_max,
                                                 TF_PENDING)
            elif kind == "resolve":
                if last_pending is None:
                    raise ValueError("cycle resolves before any pending step")
                rows = _resolve(ids, last_pending, rng, share)
                last_pending = None
            else:
                raise ValueError(f"unknown cycle step {kind!r}")
            steps.append(("create_transfers", rows))
        preload.append(steps[:n_pre])
        window.append(steps[n_pre:])
    assert next_id < FIRST_UNUSED_ID
    return {
        "setup": [{"name": "accounts", "queues": account_queues},
                  {"name": "preload", "queues": preload}],
        "window": window,
        "account_ids": list(range(1, n_acc + 1)),
        "unused_ids": FIRST_UNUSED_ID,
    }
