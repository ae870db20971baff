"""Host data-plane bridge: numpy-backed ledger + native engine dispatch.

The solo-server OLTP hot path runs here when the per-batch device round trip
is latency-prohibitive or the accelerator is absent (XLA-CPU's gather/scatter
throughput is ~30x off native).  The native
engine (native/engine.cpp) is a sequential, exact port of the scalar oracle
(testing/model.py — the same semantics the device kernels are differentially
tested against).

Layout: hashing/probing matches ops/hash_table.py exactly (slot =
mix64(key) & (C-1), linear probe, tombstones), so slot assignment is
bit-identical to the device kernels and a host ledger converts losslessly to
the device representation; the PHYSICAL storage here is array-of-slots
(numpy structured arrays, one ~2-cache-line record per slot) because a random
insert into the device's 21-column struct-of-arrays layout costs ~23 line
fills against AoS's ~3 — measured 2-3x on the commit hot loop.

The reference's analogue is the whole native state machine
(src/state_machine.zig); here it is the host half of a two-executor design:
device kernels for batch/analytics/multi-chip scale, native engine for
latency-bound OLTP serving.
"""

from __future__ import annotations

import ctypes
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import types
from .ops import state_machine as sm

__all__ = ["HostLedger", "HostEngine", "engine_available"]

_HIST_ORDER = list(sm.HISTORY_COLS.keys())
assert _HIST_ORDER[-1] == "timestamp" and len(_HIST_ORDER) == 21

# AoS slot dtypes — field order/sizes mirror tb_acc_slot / tb_tr_slot /
# tb_po_slot in native/engine.cpp exactly (static_asserts there pin sizes).
ACC_SLOT_DTYPE = np.dtype({
    "names": [
        "key_lo", "key_hi",
        "dp_lo", "dp_hi", "dpo_lo", "dpo_hi",
        "cp_lo", "cp_hi", "cpo_lo", "cpo_hi",
        "ud128_lo", "ud128_hi", "ud64", "ts",
        "ud32", "ledger", "code", "flags", "tomb",
    ],
    "formats": ["<u8"] * 14 + ["<u4"] * 4 + ["u1"],
    "offsets": [8 * i for i in range(14)] + [112, 116, 120, 124, 128],
    "itemsize": 136,
})
TR_SLOT_DTYPE = np.dtype({
    "names": [
        "key_lo", "key_hi",
        "dr_lo", "dr_hi", "cr_lo", "cr_hi",
        "amt_lo", "amt_hi", "pid_lo", "pid_hi",
        "ud128_lo", "ud128_hi", "ud64", "ts",
        "ud32", "timeout", "ledger", "code", "flags", "tomb",
    ],
    "formats": ["<u8"] * 14 + ["<u4"] * 5 + ["u1"],
    "offsets": [8 * i for i in range(14)] + [112, 116, 120, 124, 128, 132],
    "itemsize": 136,
})
PO_SLOT_DTYPE = np.dtype({
    "names": ["key_lo", "key_hi", "fulfillment", "tomb"],
    "formats": ["<u8", "<u8", "<u4", "u1"],
    "offsets": [0, 8, 16, 20],
    "itemsize": 24,
})

# slot field -> device column name (ops/state_machine ACCOUNT_COLS /
# TRANSFER_COLS); key/tomb handled separately.
ACC_FIELD_TO_COL = {
    "dp_lo": "debits_pending_lo", "dp_hi": "debits_pending_hi",
    "dpo_lo": "debits_posted_lo", "dpo_hi": "debits_posted_hi",
    "cp_lo": "credits_pending_lo", "cp_hi": "credits_pending_hi",
    "cpo_lo": "credits_posted_lo", "cpo_hi": "credits_posted_hi",
    "ud128_lo": "user_data_128_lo", "ud128_hi": "user_data_128_hi",
    "ud64": "user_data_64", "ud32": "user_data_32",
    "ledger": "ledger", "code": "code", "flags": "flags",
    "ts": "timestamp",
}
TR_FIELD_TO_COL = {
    "dr_lo": "debit_account_id_lo", "dr_hi": "debit_account_id_hi",
    "cr_lo": "credit_account_id_lo", "cr_hi": "credit_account_id_hi",
    "amt_lo": "amount_lo", "amt_hi": "amount_hi",
    "pid_lo": "pending_id_lo", "pid_hi": "pending_id_hi",
    "ud128_lo": "user_data_128_lo", "ud128_hi": "user_data_128_hi",
    "ud64": "user_data_64", "ud32": "user_data_32",
    "timeout": "timeout", "ledger": "ledger", "code": "code",
    "flags": "flags", "ts": "timestamp",
}
PO_FIELD_TO_COL = {"fulfillment": "fulfillment"}

_TABLE_SPEC = {
    "accounts": (ACC_SLOT_DTYPE, ACC_FIELD_TO_COL),
    "transfers": (TR_SLOT_DTYPE, TR_FIELD_TO_COL),
    "posted": (PO_SLOT_DTYPE, PO_FIELD_TO_COL),
}


class _LedgerView(ctypes.Structure):
    """Mirror of tb_ledger_view in native/engine.cpp (field order is ABI)."""

    _fields_ = [
        ("acc", ctypes.c_void_p), ("acc_cap", ctypes.c_uint64),
        ("tr", ctypes.c_void_p), ("tr_cap", ctypes.c_uint64),
        ("po", ctypes.c_void_p), ("po_cap", ctypes.c_uint64),
        ("hist", ctypes.c_void_p * 21), ("hist_cap", ctypes.c_uint64),
        ("acc_count", ctypes.c_uint64), ("tr_count", ctypes.c_uint64),
        ("po_count", ctypes.c_uint64), ("hist_count", ctypes.c_uint64),
        ("max_probe", ctypes.c_uint64),
    ]


class _HostTable:
    """AoS numpy twin of ops/hash_table.Table (value-identical columns)."""

    def __init__(self, capacity: int, kind: str) -> None:
        dtype, field_to_col = _TABLE_SPEC[kind]
        self.kind = kind
        self.rows = np.zeros(capacity, dtype=dtype)
        self._field_to_col = field_to_col
        self.count = 0

    @property
    def capacity(self) -> int:
        return len(self.rows)

    # Device-compatible accessors (views into the AoS rows).
    @property
    def key_lo(self) -> np.ndarray:
        return self.rows["key_lo"]

    @property
    def key_hi(self) -> np.ndarray:
        return self.rows["key_hi"]

    @property
    def tombstone(self) -> np.ndarray:
        return self.rows["tomb"]

    @property
    def cols(self) -> Dict[str, np.ndarray]:
        return {
            col: self.rows[field]
            for field, col in self._field_to_col.items()
        }

    @classmethod
    def from_device(cls, table, kind: str) -> "_HostTable":
        t = cls(len(np.asarray(table.key_lo)), kind)
        t.rows["key_lo"] = np.asarray(table.key_lo)
        t.rows["key_hi"] = np.asarray(table.key_hi)
        t.rows["tomb"] = np.asarray(table.tombstone).astype(np.uint8)
        cols = table.cols
        for field, col in t._field_to_col.items():
            t.rows[field] = np.asarray(cols[col])
        t.count = int(table.count)
        return t

    def to_device(self):
        import jax.numpy as jnp

        from .ops import hash_table as ht

        return ht.Table(
            key_lo=jnp.asarray(np.ascontiguousarray(self.rows["key_lo"])),
            key_hi=jnp.asarray(np.ascontiguousarray(self.rows["key_hi"])),
            tombstone=jnp.asarray(
                np.ascontiguousarray(self.rows["tomb"]).astype(bool)
            ),
            cols={
                col: jnp.asarray(np.ascontiguousarray(self.rows[field]))
                for field, col in self._field_to_col.items()
            },
            count=jnp.uint64(self.count),
            probe_overflow=jnp.bool_(False),
        )


class HostLedger:
    """Numpy mirror of ops/state_machine.Ledger, mutated by the engine."""

    def __init__(self, accounts_capacity: int, transfers_capacity: int,
                 posted_capacity: int, history_capacity: int = 1 << 16) -> None:
        self.accounts = _HostTable(accounts_capacity, "accounts")
        self.transfers = _HostTable(transfers_capacity, "transfers")
        self.posted = _HostTable(posted_capacity, "posted")
        self.history = {n: np.zeros(history_capacity, np.uint64)
                        for n in _HIST_ORDER}
        self.history_count = 0

    @property
    def history_capacity(self) -> int:
        return len(self.history["timestamp"])

    def prefault(self) -> None:
        """Touch every table page for write (read-modify-write preserves
        contents).  A fresh multi-GB numpy table is lazily-mapped zero pages;
        faulting them during the serving hot loop costs more than the probes
        themselves (measured: 10x on the commit path)."""
        for table in (self.accounts, self.transfers, self.posted):
            flat = table.rows.view(np.uint8).reshape(-1)
            flat[::4096] |= 0

    @classmethod
    def from_device(cls, ledger: "sm.Ledger") -> "HostLedger":
        led = cls.__new__(cls)
        led.accounts = _HostTable.from_device(ledger.accounts, "accounts")
        led.transfers = _HostTable.from_device(ledger.transfers, "transfers")
        led.posted = _HostTable.from_device(ledger.posted, "posted")
        led.history = {n: np.array(c) for n, c in ledger.history.cols.items()}
        led.history_count = int(ledger.history.count)
        return led

    def to_device(self) -> "sm.Ledger":
        import jax.numpy as jnp

        return sm.Ledger(
            accounts=self.accounts.to_device(),
            transfers=self.transfers.to_device(),
            posted=self.posted.to_device(),
            history=sm.History(
                cols={n: jnp.asarray(c) for n, c in self.history.items()},
                count=jnp.uint64(self.history_count),
            ),
        )

    def grow_history(self, min_capacity: int) -> None:
        cap = self.history_capacity
        while cap < min_capacity:
            cap *= 2
        if cap == self.history_capacity:
            return
        self.history = {
            n: np.concatenate([c, np.zeros(cap - len(c), np.uint64)])
            for n, c in self.history.items()
        }


def engine_available() -> bool:
    from . import native

    lib = native.load()
    return lib is not None and hasattr(lib, "tb_engine_create_transfers")


class EngineError(RuntimeError):
    pass


class HostEngine:
    """ctypes dispatch into native/engine.cpp over a HostLedger."""

    def __init__(self, ledger: HostLedger, max_probe: int) -> None:
        from . import native

        lib = native.load()
        if lib is None or not hasattr(lib, "tb_engine_create_transfers"):
            raise EngineError("native engine unavailable")
        self._lib = lib
        self.ledger = ledger
        self.max_probe = max_probe

    # -- view construction ---------------------------------------------------

    def _view(self, ledger: Optional[HostLedger] = None) -> _LedgerView:
        led = ledger or self.ledger
        v = _LedgerView()
        v.acc = led.accounts.rows.ctypes.data
        v.acc_cap = led.accounts.capacity
        v.tr = led.transfers.rows.ctypes.data
        v.tr_cap = led.transfers.capacity
        v.po = led.posted.rows.ctypes.data
        v.po_cap = led.posted.capacity
        hist_ptrs = (ctypes.c_void_p * 21)()
        for i, name in enumerate(_HIST_ORDER):
            hist_ptrs[i] = led.history[name].ctypes.data
        v.hist = hist_ptrs
        v.hist_cap = led.history_capacity
        v.acc_count = led.accounts.count
        v.tr_count = led.transfers.count
        v.po_count = led.posted.count
        v.hist_count = led.history_count
        v.max_probe = self.max_probe
        return v

    def _writeback_counts(self, v: _LedgerView) -> None:
        self.ledger.accounts.count = int(v.acc_count)
        self.ledger.transfers.count = int(v.tr_count)
        self.ledger.posted.count = int(v.po_count)
        self.ledger.history_count = int(v.hist_count)

    # -- commits -------------------------------------------------------------

    def create_accounts(self, batch: np.ndarray, timestamp: int) -> np.ndarray:
        """Dense result codes (u32 per event), model-exact."""
        batch = np.ascontiguousarray(batch)
        count = len(batch)
        codes = np.zeros(count, np.uint32)
        if count == 0:
            return codes
        v = self._view()
        rc = self._lib.tb_engine_create_accounts(
            ctypes.byref(v), ctypes.c_void_p(batch.ctypes.data),
            ctypes.c_uint64(count), ctypes.c_uint64(timestamp),
            ctypes.c_void_p(codes.ctypes.data),
        )
        self._writeback_counts(v)
        if rc != 0:
            raise EngineError(f"create_accounts engine error {rc}")
        return codes

    def create_transfers(self, batch: np.ndarray, timestamp: int) -> np.ndarray:
        batch = np.ascontiguousarray(batch)
        count = len(batch)
        codes = np.zeros(count, np.uint32)
        if count == 0:
            return codes
        v = self._view()
        rc = self._lib.tb_engine_create_transfers(
            ctypes.byref(v), ctypes.c_void_p(batch.ctypes.data),
            ctypes.c_uint64(count), ctypes.c_uint64(timestamp),
            ctypes.c_void_p(codes.ctypes.data),
        )
        self._writeback_counts(v)
        if rc != 0:
            raise EngineError(f"create_transfers engine error {rc}")
        return codes

    # -- lookups -------------------------------------------------------------

    def _lookup(self, fn, ids: List[int], dtype) -> Tuple[np.ndarray, np.ndarray]:
        n = len(ids)
        id_arr = np.zeros(n, dtype=np.dtype([("lo", "<u8"), ("hi", "<u8")]))
        for i, ident in enumerate(ids):
            id_arr[i] = (ident & ((1 << 64) - 1), ident >> 64)
        out = np.zeros(n, dtype=dtype)
        found = np.zeros(n, np.uint8)
        v = self._view()
        rc = fn(
            ctypes.byref(v), ctypes.c_void_p(id_arr.ctypes.data),
            ctypes.c_uint64(n), ctypes.c_void_p(out.ctypes.data),
            ctypes.c_void_p(found.ctypes.data),
        )
        if rc != 0:
            raise EngineError(f"lookup engine error {rc}")
        return found.astype(bool), out

    def lookup_accounts(self, ids: List[int]) -> np.ndarray:
        found, rows = self._lookup(
            self._lib.tb_engine_lookup_accounts, ids, types.ACCOUNT_DTYPE
        )
        return rows[found]

    def lookup_transfers(self, ids: List[int]) -> Tuple[np.ndarray, np.ndarray]:
        """(found_mask, rows) — rows aligned with ids (missing rows zeroed)."""
        return self._lookup(
            self._lib.tb_engine_lookup_transfers, ids, types.TRANSFER_DTYPE
        )

    # -- growth --------------------------------------------------------------

    def grow(self, which: str, new_capacity: int) -> None:
        """Rehash a table into `new_capacity` slots (ht.grow parity: old-slot
        order insertion, tombstones dropped)."""
        led = self.ledger
        table = getattr(led, which)
        assert new_capacity >= table.capacity
        fresh = _HostTable(new_capacity, which)
        old_view = self._view()
        new_led = HostLedger.__new__(HostLedger)
        new_led.accounts = fresh if which == "accounts" else led.accounts
        new_led.transfers = fresh if which == "transfers" else led.transfers
        new_led.posted = fresh if which == "posted" else led.posted
        new_led.history = led.history
        new_led.history_count = led.history_count
        new_view = self._view(new_led)
        idx = {"accounts": 0, "transfers": 1, "posted": 2}[which]
        rc = self._lib.tb_engine_rehash(
            ctypes.byref(old_view), ctypes.byref(new_view), ctypes.c_int(idx)
        )
        if rc != 0:
            raise EngineError(f"rehash({which}) engine error {rc}")
        fresh.count = int(
            {"accounts": new_view.acc_count, "transfers": new_view.tr_count,
             "posted": new_view.po_count}[which]
        )
        setattr(led, which, fresh)
