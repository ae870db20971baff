"""HBM bytes one create_transfers batch of PLAIN lanes on the general route
MUST move, lane by lane: the numerator of `hazard_roofline`.

A lane here is a plain transfer (no post, no void) between two accounts of
which one may carry a balance limit, so the lane may be refused by what
earlier lanes of its own batch left of a balance.  Counted from the column
dtypes `harness/bytes_model.py` names, with its constants, and written out as
it is there.  Every lane, whatever its result:

    the duplicate probe of its id          PROBES x KEY_BYTES
    the probes of its two accounts         2 x PROBES x KEY_BYTES
    both accounts' flags, ledger, code,
      timestamp                            2 x ACCOUNT_META_BYTES
    both accounts' balances, read          2 x ACCOUNT_SIDE_BALANCE_BYTES
    its result code                        4

and an ACCEPTED lane besides:

    its row's insert                       KEY_BYTES + TRANSFER_VALUE_BYTES
    both accounts' balances, written       2 x ACCOUNT_SIDE_BALANCE_BYTES

A refused lane writes nothing.  An accepted lane's bytes equal
`bytes_model.fast_lane_bytes()`: the least work does not go by the route.
NOT counted, because the algorithm does not need them and another kernel
could do without: the Jacobi passes beyond the first (each re-reads the
batch's own running balances, which fit on the chip), the leg sort, the wave
schedule, the secondary index's appends.  So the share stays under 100 %
whatever implements the kernel, and a kernel that needs fewer passes reads
higher.
"""

from benchmarks.harness.bytes_model import (
    ACCOUNT_META_BYTES, ACCOUNT_SIDE_BALANCE_BYTES, KEY_BYTES, PROBES,
    TRANSFER_VALUE_BYTES,
)


def refused_lane_bytes() -> float:
    return (
        PROBES * KEY_BYTES
        + 2 * PROBES * KEY_BYTES
        + 2 * ACCOUNT_META_BYTES
        + 2 * ACCOUNT_SIDE_BALANCE_BYTES
        + 4
    )


def accepted_lane_bytes() -> float:
    return (
        refused_lane_bytes()
        + KEY_BYTES + TRANSFER_VALUE_BYTES
        + 2 * ACCOUNT_SIDE_BALANCE_BYTES
    )


def batch_bytes(lanes: int, refused_share: float) -> float:
    """One request of `lanes` plain lanes of which `refused_share` (0..1)
    were refused."""
    return lanes * (refused_share * refused_lane_bytes()
                    + (1.0 - refused_share) * accepted_lane_bytes())
