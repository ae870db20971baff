"""HBM bytes one create_transfers batch MUST move: the roofline's numerator.

A copy of the count in `tigerbeetle_tpu/utils/roofline.py` as of d0bcfcd
(`fast_kernel_model`, `general_kernel_model`), kept here so that no later PR
can move the yardstick.  Copied: the per-lane traffic, counted from the
column dtypes of `ops/state_machine.py` (TRANSFER_COLS: 11 u64 + 5 u32 + the
u64 timestamp = 116 B of value columns; POSTED_COLS: one u32).  NOT copied:
that file's `OVERHEAD_US` brackets and tx/s predictions, which are assumed,
and two terms of its general count that are the implementation's and not
the algorithm's — the history append (no account of these mixes carries the
history flag) and the balance re-reads of the extra Jacobi passes.

The tables live in HBM; the 8190-lane batch itself is a few hundred KiB.
What is NOT counted, and so makes the share smaller, never larger: the
secondary index's appends and merges, and table-sized temporaries that the
compiled programs materialise.  This is the HBM (bandwidth) bound; the
kernels do no matrix arithmetic, so there is no compute bound to compare.
"""

KEY_BYTES = 16                       # id_lo, id_hi
TRANSFER_VALUE_BYTES = 11 * 8 + 5 * 4 + 8
POSTED_VALUE_BYTES = 4
ACCOUNT_META_BYTES = 4 + 4 + 4 + 8   # flags, ledger, code, timestamp
ACCOUNT_SIDE_BALANCE_BYTES = 4 * 8   # one side's two u128 balances' limbs
LOAD_FACTOR = 0.5                    # the tables grow at this load
PROBES = 1.0 / (1.0 - LOAD_FACTOR)   # expected probes per lookup


def fast_lane_bytes() -> float:
    """A plain or pending transfer: duplicate probe, row insert, two account
    probes, validation gather, balance read-modify-write on both sides, the
    result code."""
    return (
        PROBES * KEY_BYTES
        + KEY_BYTES + TRANSFER_VALUE_BYTES
        + 2 * PROBES * KEY_BYTES
        + 2 * ACCOUNT_META_BYTES
        + 2 * 2 * ACCOUNT_SIDE_BALANCE_BYTES
        + 4
    )


def resolve_lane_bytes() -> float:
    """A post or void: the above, plus the pending row's gather and the
    posted table's probe and fulfilment write."""
    pending_gather = PROBES * KEY_BYTES + TRANSFER_VALUE_BYTES
    posted = PROBES * KEY_BYTES + KEY_BYTES + POSTED_VALUE_BYTES
    return fast_lane_bytes() + pending_gather + posted
