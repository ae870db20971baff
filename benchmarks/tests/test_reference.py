"""The benchmark's plain reference against the repo's own oracle
(`tigerbeetle_tpu/testing/model.py`): equal result codes for every batch and
equal rows for every id, on seeded batches that reach every failure code
these operations can give.  At run time the benchmark uses its own reference
alone; this test is what ties the two together."""

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.generators import ledger_mix  # noqa: E402
from benchmarks.reference import ledger  # noqa: E402
from tigerbeetle_tpu.testing import model  # noqa: E402

U64 = (1 << 64) - 1


def _oracle_batch(ref, operation, rows):
    convert = (model.accounts_from_batch if operation == "create_accounts"
               else model.transfers_from_batch)
    ts = ref.prepare(operation, len(rows))
    return [(int(i), int(c)) for i, c in
            ref.execute(operation, ts, convert(rows))]


def _faulty_batches(seed):
    """Accounts and transfers with deliberate failures of every kind the
    reference covers, interleaved with successes."""
    rng = np.random.default_rng(seed)
    n = 64
    acc = ledger_mix._accounts(np.arange(1, n + 1, dtype=np.uint64), rng)
    acc["flags"][10:14] = ledger.AF_DEBITS_LE_CREDITS
    acc["flags"][14:18] = ledger.AF_CREDITS_LE_DEBITS
    bad = acc[:16].copy()
    bad["id_lo"][0] = 0
    bad["id_lo"][1] = bad["id_hi"][1] = U64
    bad["reserved"][2] = 1
    bad["flags"][3] = 0x10
    bad["flags"][4] = 6
    bad["debits_pending_lo"][5] = 1
    bad["debits_posted_lo"][6] = 1
    bad["credits_pending_lo"][7] = 1
    bad["credits_posted_hi"][8] = 1
    bad["ledger"][9] = 0
    bad["code"][10] = 0
    bad["timestamp"][11] = 5
    bad["user_data_64"][12] += 1        # exists_with_different_*
    bad["user_data_32"][13] += 1
    bad["code"][14] += 1
    bad["user_data_128_lo"][15] = 9     # vs exact resend in [:16] of acc
    yield "create_accounts", acc
    yield "create_accounts", np.concatenate([bad, acc[20:24]])

    def transfers(count, flags=0):
        ids = np.arange(transfers.next, transfers.next + count,
                        dtype=np.uint64)
        transfers.next += count
        return ledger_mix._transfers(ids, n, rng, 1000, flags)

    transfers.next = 1000
    plain = transfers(200)
    yield "create_transfers", plain
    t = transfers(40)
    t["id_lo"][0] = 0
    t["id_lo"][1] = t["id_hi"][1] = U64
    t["flags"][2] = 0x40
    t["debit_account_id_lo"][3] = 0
    t["credit_account_id_lo"][4] = 0
    t["debit_account_id_lo"][5] = t["debit_account_id_hi"][5] = U64
    t["credit_account_id_lo"][6] = t["credit_account_id_hi"][6] = U64
    t["credit_account_id_lo"][7] = t["debit_account_id_lo"][7]
    t["pending_id_lo"][8] = 3
    t["amount_lo"][9] = 0
    t["ledger"][10] = 0
    t["code"][11] = 0
    t["debit_account_id_lo"][12] = 9999
    t["credit_account_id_lo"][13] = 9999
    t["ledger"][14] = 2
    t["timestamp"][15] = 1
    t[16] = plain[0]                               # exists
    for k, field in enumerate(("flags", "debit_account_id_lo",
                               "credit_account_id_lo", "amount_lo",
                               "user_data_128_lo", "user_data_64",
                               "user_data_32", "code")):
        t[17 + k] = plain[1 + k]
        if field == "flags":
            t[17 + k]["flags"] = ledger.TF_PENDING
        elif field.endswith("account_id_lo"):
            other = "credit" if field.startswith("debit") else "debit"
            t[17 + k][field] = (
                int(plain[1 + k][other + "_account_id_lo"]) % n + 1) or 1
            if t[17 + k][field] == plain[1 + k][other + "_account_id_lo"]:
                t[17 + k][field] = int(t[17 + k][field]) % n + 1
        else:
            t[17 + k][field] += 1
    t["amount_hi"][26] = U64                       # overflow ladders
    t["debit_account_id_lo"][27] = 11              # exceeds_credits
    t["amount_lo"][27] = 10**9
    t["credit_account_id_lo"][28] = 15             # exceeds_debits
    t["amount_lo"][28] = 10**9
    yield "create_transfers", t

    pend = transfers(100, ledger.TF_PENDING)
    yield "create_transfers", pend
    share = {"post_pct": 60, "void_pct": 30}
    res = ledger_mix._resolve(
        np.arange(5000, 5100, dtype=np.uint64), pend, rng, share)
    res["amount_lo"][::7] = 0                      # post/void in full by 0
    partial = (res["flags"] == ledger.TF_POST) & (res["amount_lo"] > 5)
    res["amount_lo"][partial] -= 1                 # partial posts
    yield "create_transfers", res
    r = res[:24].copy()
    r["id_lo"] = np.arange(6000, 6024)
    r["flags"][0] = ledger.TF_POST | ledger.TF_VOID
    r["flags"][1] = ledger.TF_POST | ledger.TF_PENDING
    r["pending_id_lo"][2] = 0
    r["pending_id_lo"][3] = r["pending_id_hi"][3] = U64
    r["pending_id_lo"][4] = r["id_lo"][4]
    r["pending_id_lo"][5] = 777777                 # not found
    r["pending_id_lo"][6] = plain["id_lo"][0]      # not pending
    r["debit_account_id_lo"][7] = 63
    r["credit_account_id_lo"][8] = 63
    r["ledger"][9] = 2
    r["code"][10] = 9
    r["amount_lo"][11] = 10**6                     # exceeds pending amount
    r["flags"][12] = ledger.TF_VOID
    r["amount_lo"][12] = 1                         # void, different amount
    r[13] = res[0]                                 # exists
    r[14] = res[1]
    r[14]["flags"] ^= ledger.TF_POST | ledger.TF_VOID
    r[15] = res[2]
    r[15]["amount_lo"] = int(r[15]["amount_lo"]) + 1 if int(
        res[2]["amount_lo"]) else 1
    r[16] = res[3]
    r[16]["user_data_64"] = 5
    # 17..23: fresh ids against pendings already posted or voided
    yield "create_transfers", r


@pytest.mark.parametrize("seed", [1, 2, 3000000007])
def test_reference_equals_the_repos_oracle(seed):
    ours, oracle = ledger.ReferenceLedger(), model.ReferenceStateMachine()
    seen_codes, transfer_ids = set(), []
    for operation, rows in _faulty_batches(seed):
        got = ours.execute(operation, rows)
        want = _oracle_batch(oracle, operation, rows)
        assert got == want, (operation, got[:5], want[:5])
        seen_codes.update((operation, c) for _i, c in got)
        if operation == "create_transfers":
            transfer_ids += rows["id_lo"].tolist()
    assert len(seen_codes) >= 45      # the failure ladder was really walked
    account_ids = list(range(0, 70))
    for kind, ids, convert in (
            ("accounts", account_ids, model.accounts_from_batch),
            ("transfers", transfer_ids, model.transfers_from_batch)):
        got = convert(getattr(ours, "lookup_" + kind)(ids))
        want = getattr(oracle, "lookup_" + kind)(ids)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g == dataclasses.replace(w, timestamp=0)


def test_unsupported_features_are_refused():
    rng = np.random.default_rng(5)
    ours = ledger.ReferenceLedger()
    ours.create_accounts(
        ledger_mix._accounts(np.arange(1, 9, dtype=np.uint64), rng))
    t = ledger_mix._transfers(np.arange(50, 54, dtype=np.uint64), 8, rng,
                              100, ledger.TF_LINKED)
    with pytest.raises(ledger.Unsupported):
        ours.create_transfers(t)
