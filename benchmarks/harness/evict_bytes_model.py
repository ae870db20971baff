"""HBM bytes the cold tier's eviction MUST move on the device: the numerator
of `evict_roofline`.

An eviction (`tigerbeetle_tpu/ops/cold.py`) takes the older share of the hot
transfers table's live rows off the device and leaves the table holding the
rest.  Whatever implements it has to, once an eviction:

    read every slot's key, tombstone and timestamp   slots x SLOT_SCAN_BYTES
      (which rows are live, and which are the older ones)
    write the rows that leave, packed for the host    evicted x ROW_BYTES
    read the rows that stay and write them where
      the table without the others has them           2 x kept x ROW_BYTES

from the column dtypes `harness/bytes_model.py` names, with its constants.  The
leaving rows' READ is not counted apart: the scan has read their keys and
timestamps, and a kernel could keep the rest of a row it is about to write.
NOT counted, because the algorithm does not need them: the sort of the
timestamps (a selection needs no full sort), the argsort that compacts the
leaving rows, the rehash's probe loop and its occupancy bitmap, the fresh
table's zero fill.  So the share stays under 100 % whatever implements the
programs.  What happens on the HOST (the fetch, the sort by id, the run file,
the filter) moves no HBM byte and is not here: `cold_evict_ms` has all of it.
"""

from benchmarks.harness.bytes_model import KEY_BYTES, TRANSFER_VALUE_BYTES

SLOT_SCAN_BYTES = KEY_BYTES + 1 + 8          # key, tombstone, timestamp
ROW_BYTES = KEY_BYTES + TRANSFER_VALUE_BYTES  # a whole row: 132 B


def eviction_bytes(slots: int, evicted: int, kept: int) -> float:
    """One eviction of `evicted` rows out of a table of `slots` slots that
    keeps `kept` rows."""
    return (slots * SLOT_SCAN_BYTES + evicted * ROW_BYTES
            + 2 * kept * ROW_BYTES)
