"""Plain reference of the ledger's semantics: the yardstick `correct` is held to.

An event-at-a-time implementation of TigerBeetle's `create_accounts` and
`create_transfers` (plain, pending, post-pending, void-pending) over Python
dicts, written from upstream's published semantics (`src/state_machine.zig`:
`create_account`, `create_transfer`, `post_or_void_pending_transfer` and their
`*_exists` ladders; result codes as in `src/tigerbeetle.zig`).  It imports
nothing of the program under test and takes nothing the program has made: its
inputs are the wire rows the generator built from the seed.

Not covered, and refused loudly rather than answered wrongly: linked chains,
balancing transfers, account history, pending timeouts (expiry needs the
server's clock).  A mix that needs one of them brings a reference of its own.

`benchmarks/tests/test_reference.py` holds this file to the repo's own oracle
(`tigerbeetle_tpu/testing/model.py`) on seeded batches with every failure code
these operations can give; at run time the benchmark uses this file alone.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

# Wire layouts (tigerbeetle.zig Account / Transfer, 128 bytes each, u128 as
# two little-endian u64 halves).
ACCOUNT_DTYPE = np.dtype([
    ("id_lo", "<u8"), ("id_hi", "<u8"),
    ("debits_pending_lo", "<u8"), ("debits_pending_hi", "<u8"),
    ("debits_posted_lo", "<u8"), ("debits_posted_hi", "<u8"),
    ("credits_pending_lo", "<u8"), ("credits_pending_hi", "<u8"),
    ("credits_posted_lo", "<u8"), ("credits_posted_hi", "<u8"),
    ("user_data_128_lo", "<u8"), ("user_data_128_hi", "<u8"),
    ("user_data_64", "<u8"), ("user_data_32", "<u4"), ("reserved", "<u4"),
    ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"),
    ("timestamp", "<u8"),
])
TRANSFER_DTYPE = np.dtype([
    ("id_lo", "<u8"), ("id_hi", "<u8"),
    ("debit_account_id_lo", "<u8"), ("debit_account_id_hi", "<u8"),
    ("credit_account_id_lo", "<u8"), ("credit_account_id_hi", "<u8"),
    ("amount_lo", "<u8"), ("amount_hi", "<u8"),
    ("pending_id_lo", "<u8"), ("pending_id_hi", "<u8"),
    ("user_data_128_lo", "<u8"), ("user_data_128_hi", "<u8"),
    ("user_data_64", "<u8"), ("user_data_32", "<u4"), ("timeout", "<u4"),
    ("ledger", "<u4"), ("code", "<u2"), ("flags", "<u2"),
    ("timestamp", "<u8"),
])
assert ACCOUNT_DTYPE.itemsize == 128 and TRANSFER_DTYPE.itemsize == 128

U128_MAX = (1 << 128) - 1

# Account flags / transfer flags (tigerbeetle.zig).
AF_LINKED, AF_DEBITS_LE_CREDITS, AF_CREDITS_LE_DEBITS, AF_HISTORY = 1, 2, 4, 8
AF_PADDING = 0xFFF0
TF_LINKED, TF_PENDING, TF_POST, TF_VOID = 1, 2, 4, 8
TF_BALANCING = 16 | 32
TF_PADDING = 0xFFC0

# Account list slots.
_DP, _DPO, _CP, _CPO, _UD128, _UD64, _UD32, _LEDGER, _CODE, _FLAGS = range(10)
# Transfer tuple slots.
(_T_DR, _T_CR, _T_AMOUNT, _T_PENDING_ID, _T_UD128, _T_UD64, _T_UD32,
 _T_TIMEOUT, _T_LEDGER, _T_CODE, _T_FLAGS) = range(11)


class Unsupported(Exception):
    """The batch uses a feature this reference does not implement."""


def _u128(batch: np.ndarray, name: str) -> List[int]:
    lo = batch[name + "_lo"].tolist()
    hi_col = batch[name + "_hi"]
    if not hi_col.any():
        return lo
    return [l | (h << 64) for l, h in zip(lo, hi_col.tolist())]


class ReferenceLedger:
    """Accounts, transfers and fulfilments as dicts; one event at a time."""

    def __init__(self) -> None:
        self.accounts: Dict[int, list] = {}
        self.transfers: Dict[int, tuple] = {}
        self.fulfilled: Dict[int, int] = {}   # pending id -> TF_POST | TF_VOID

    # -- create_accounts ----------------------------------------------------

    def create_accounts(self, batch: np.ndarray) -> List[Tuple[int, int]]:
        out: List[Tuple[int, int]] = []
        accounts = self.accounts
        cols = zip(
            _u128(batch, "id"), _u128(batch, "debits_pending"),
            _u128(batch, "debits_posted"), _u128(batch, "credits_pending"),
            _u128(batch, "credits_posted"), _u128(batch, "user_data_128"),
            batch["user_data_64"].tolist(), batch["user_data_32"].tolist(),
            batch["reserved"].tolist(), batch["ledger"].tolist(),
            batch["code"].tolist(), batch["flags"].tolist(),
            batch["timestamp"].tolist(),
        )
        for i, (aid, dp, dpo, cp, cpo, ud128, ud64, ud32, reserved, ledger,
                code, flags, ts) in enumerate(cols):
            if flags & (AF_LINKED | AF_HISTORY):
                raise Unsupported("linked or history account")
            if ts != 0:
                r = 3
            elif reserved != 0:
                r = 4
            elif flags & AF_PADDING:
                r = 5
            elif aid == 0:
                r = 6
            elif aid == U128_MAX:
                r = 7
            elif flags & AF_DEBITS_LE_CREDITS and flags & AF_CREDITS_LE_DEBITS:
                r = 8
            elif dp != 0:
                r = 9
            elif dpo != 0:
                r = 10
            elif cp != 0:
                r = 11
            elif cpo != 0:
                r = 12
            elif ledger == 0:
                r = 13
            elif code == 0:
                r = 14
            else:
                e = accounts.get(aid)
                if e is None:
                    accounts[aid] = [0, 0, 0, 0, ud128, ud64, ud32, ledger,
                                     code, flags]
                    continue
                if flags != e[_FLAGS]:
                    r = 15
                elif ud128 != e[_UD128]:
                    r = 16
                elif ud64 != e[_UD64]:
                    r = 17
                elif ud32 != e[_UD32]:
                    r = 18
                elif ledger != e[_LEDGER]:
                    r = 19
                elif code != e[_CODE]:
                    r = 20
                else:
                    r = 21
            out.append((i, r))
        return out

    # -- create_transfers ---------------------------------------------------

    def create_transfers(self, batch: np.ndarray) -> List[Tuple[int, int]]:
        out: List[Tuple[int, int]] = []
        accounts, transfers = self.accounts, self.transfers
        cols = zip(
            _u128(batch, "id"), _u128(batch, "debit_account_id"),
            _u128(batch, "credit_account_id"), _u128(batch, "amount"),
            _u128(batch, "pending_id"), _u128(batch, "user_data_128"),
            batch["user_data_64"].tolist(), batch["user_data_32"].tolist(),
            batch["timeout"].tolist(), batch["ledger"].tolist(),
            batch["code"].tolist(), batch["flags"].tolist(),
            batch["timestamp"].tolist(),
        )
        for i, (tid, dr_id, cr_id, amount, pending_id, ud128, ud64, ud32,
                timeout, ledger, code, flags, ts) in enumerate(cols):
            if flags & (TF_LINKED | TF_BALANCING) or timeout:
                raise Unsupported("linked, balancing or timed-out transfer")
            if ts != 0:
                r = 3
            elif flags & TF_PADDING:
                r = 4
            elif tid == 0:
                r = 5
            elif tid == U128_MAX:
                r = 6
            elif flags & (TF_POST | TF_VOID):
                r = self._post_or_void(tid, dr_id, cr_id, amount, pending_id,
                                       ud128, ud64, ud32, ledger, code, flags)
            elif dr_id == 0:
                r = 8
            elif dr_id == U128_MAX:
                r = 9
            elif cr_id == 0:
                r = 10
            elif cr_id == U128_MAX:
                r = 11
            elif cr_id == dr_id:
                r = 12
            elif pending_id != 0:
                r = 13
            elif amount == 0:
                r = 18
            elif ledger == 0:
                r = 19
            elif code == 0:
                r = 20
            else:
                dr = accounts.get(dr_id)
                cr = accounts.get(cr_id) if dr is not None else None
                if dr is None:
                    r = 21
                elif cr is None:
                    r = 22
                elif dr[_LEDGER] != cr[_LEDGER]:
                    r = 23
                elif ledger != dr[_LEDGER]:
                    r = 24
                else:
                    e = transfers.get(tid)
                    if e is not None:
                        r = self._exists(e, dr_id, cr_id, amount, ud128, ud64,
                                         ud32, timeout, code, flags)
                    else:
                        r = self._apply(tid, dr, cr, dr_id, cr_id, amount,
                                        ud128, ud64, ud32, ledger, code, flags)
            if r:
                out.append((i, r))
        return out

    def _apply(self, tid, dr, cr, dr_id, cr_id, amount, ud128, ud64, ud32,
               ledger, code, flags) -> int:
        pending = flags & TF_PENDING
        if pending:
            if amount + dr[_DP] > U128_MAX:
                return 47
            if amount + cr[_CP] > U128_MAX:
                return 48
        if amount + dr[_DPO] > U128_MAX:
            return 49
        if amount + cr[_CPO] > U128_MAX:
            return 50
        if amount + dr[_DP] + dr[_DPO] > U128_MAX:
            return 51
        if amount + cr[_CP] + cr[_CPO] > U128_MAX:
            return 52
        if dr[_FLAGS] & AF_DEBITS_LE_CREDITS and (
                dr[_DP] + dr[_DPO] + amount > dr[_CPO]):
            return 54
        if cr[_FLAGS] & AF_CREDITS_LE_DEBITS and (
                cr[_CP] + cr[_CPO] + amount > cr[_DPO]):
            return 55
        self.transfers[tid] = (dr_id, cr_id, amount, 0, ud128, ud64, ud32, 0,
                               ledger, code, flags)
        if pending:
            dr[_DP] += amount
            cr[_CP] += amount
        else:
            dr[_DPO] += amount
            cr[_CPO] += amount
        return 0

    @staticmethod
    def _exists(e, dr_id, cr_id, amount, ud128, ud64, ud32, timeout, code,
                flags) -> int:
        if flags != e[_T_FLAGS]:
            return 36
        if dr_id != e[_T_DR]:
            return 37
        if cr_id != e[_T_CR]:
            return 38
        if amount != e[_T_AMOUNT]:
            return 39
        if ud128 != e[_T_UD128]:
            return 41
        if ud64 != e[_T_UD64]:
            return 42
        if ud32 != e[_T_UD32]:
            return 43
        if timeout != e[_T_TIMEOUT]:
            return 44
        if code != e[_T_CODE]:
            return 45
        return 46

    def _post_or_void(self, tid, dr_id, cr_id, amount, pending_id, ud128,
                      ud64, ud32, ledger, code, flags) -> int:
        post = flags & TF_POST
        if post and flags & TF_VOID:
            return 7
        if flags & TF_PENDING:
            return 7
        if pending_id == 0:
            return 14
        if pending_id == U128_MAX:
            return 15
        if pending_id == tid:
            return 16
        p = self.transfers.get(pending_id)
        if p is None:
            return 25
        if not p[_T_FLAGS] & TF_PENDING:
            return 26
        if dr_id > 0 and dr_id != p[_T_DR]:
            return 27
        if cr_id > 0 and cr_id != p[_T_CR]:
            return 28
        if ledger > 0 and ledger != p[_T_LEDGER]:
            return 29
        if code > 0 and code != p[_T_CODE]:
            return 30
        p_amount = p[_T_AMOUNT]
        final = amount if amount > 0 else p_amount
        if final > p_amount:
            return 31
        if not post and final < p_amount:
            return 32
        e = self.transfers.get(tid)
        if e is not None:
            if flags != e[_T_FLAGS]:
                return 36
            if (e[_T_AMOUNT] != p_amount) if amount == 0 else (
                    amount != e[_T_AMOUNT]):
                return 39
            if pending_id != e[_T_PENDING_ID]:
                return 40
            if (e[_T_UD128] != p[_T_UD128]) if ud128 == 0 else (
                    ud128 != e[_T_UD128]):
                return 41
            if (e[_T_UD64] != p[_T_UD64]) if ud64 == 0 else (
                    ud64 != e[_T_UD64]):
                return 42
            if (e[_T_UD32] != p[_T_UD32]) if ud32 == 0 else (
                    ud32 != e[_T_UD32]):
                return 43
            return 46
        done = self.fulfilled.get(pending_id)
        if done == TF_POST:
            return 33
        if done == TF_VOID:
            return 34
        self.transfers[tid] = (
            p[_T_DR], p[_T_CR], final, pending_id,
            ud128 or p[_T_UD128], ud64 or p[_T_UD64], ud32 or p[_T_UD32], 0,
            p[_T_LEDGER], p[_T_CODE], flags,
        )
        self.fulfilled[pending_id] = TF_POST if post else TF_VOID
        dr, cr = self.accounts[p[_T_DR]], self.accounts[p[_T_CR]]
        dr[_DP] -= p_amount
        cr[_CP] -= p_amount
        if post:
            dr[_DPO] += final
            cr[_CPO] += final
        return 0

    def execute(self, operation: str, batch: np.ndarray
                ) -> List[Tuple[int, int]]:
        if operation == "create_accounts":
            return self.create_accounts(batch)
        if operation == "create_transfers":
            return self.create_transfers(batch)
        raise Unsupported(operation)

    # -- lookups (rows as the wire gives them; `timestamp` left 0) ----------

    def lookup_accounts(self, ids: Sequence[int]) -> np.ndarray:
        found = [(i, self.accounts[i]) for i in ids if i in self.accounts]
        rows = np.zeros(len(found), dtype=ACCOUNT_DTYPE)
        _put128(rows, "id", [i for i, _ in found])
        for name, slot in (("debits_pending", _DP), ("debits_posted", _DPO),
                           ("credits_pending", _CP), ("credits_posted", _CPO),
                           ("user_data_128", _UD128)):
            _put128(rows, name, [a[slot] for _, a in found])
        for name, slot in (("user_data_64", _UD64), ("user_data_32", _UD32),
                           ("ledger", _LEDGER), ("code", _CODE),
                           ("flags", _FLAGS)):
            rows[name] = [a[slot] for _, a in found]
        return rows

    def lookup_transfers(self, ids: Sequence[int]) -> np.ndarray:
        found = [(i, self.transfers[i]) for i in ids if i in self.transfers]
        rows = np.zeros(len(found), dtype=TRANSFER_DTYPE)
        _put128(rows, "id", [i for i, _ in found])
        for name, slot in (("debit_account_id", _T_DR),
                           ("credit_account_id", _T_CR),
                           ("amount", _T_AMOUNT),
                           ("pending_id", _T_PENDING_ID),
                           ("user_data_128", _T_UD128)):
            _put128(rows, name, [t[slot] for _, t in found])
        for name, slot in (("user_data_64", _T_UD64), ("user_data_32", _T_UD32),
                           ("timeout", _T_TIMEOUT), ("ledger", _T_LEDGER),
                           ("code", _T_CODE), ("flags", _T_FLAGS)):
            rows[name] = [t[slot] for _, t in found]
        return rows


def _put128(rows: np.ndarray, name: str, values: List[int]) -> None:
    mask = (1 << 64) - 1
    rows[name + "_lo"] = np.array([v & mask for v in values], dtype=np.uint64)
    rows[name + "_hi"] = np.array([v >> 64 for v in values], dtype=np.uint64)
