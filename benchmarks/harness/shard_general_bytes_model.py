"""HBM bytes ONE chip of a sharded ledger must move for one resolving lane (a
post or a void) of a create_transfers request: `shard_general_roofline`'s
numerator.

Built the way `shard_bytes_model.py` is, counted from requests and lanes
alone, never from the program's operations.  Two terms a lane:

- the chip's share of the table traffic.  Every row a resolving lane touches
  is owned by `mix64(key) & (n - 1)`, uniformly: the new transfer's row, the
  pending's row, both accounts of the pending, the posted row (its key is
  the pending's timestamp).  So a chip moves one n-th of what one chip would
  move for the whole lane (`bytes_model.resolve_lane_bytes()`, 600 B);
- the context it must receive.  The validation core runs on every chip for
  every lane, so a chip must be told, for a lane it owns no key of, what the
  owners found: whether the new id exists; whether the pending exists and
  the columns of its row the core reads and the new row copies (the value
  columns, `bytes_model.TRANSFER_VALUE_BYTES`); for each of the pending's
  two accounts whether it exists, its global slot, the columns validation
  reads (`shard_bytes_model`'s account side) and that side's balances, which
  a post or void moves from pending to posted; whether a posted row exists
  and its value.  A chip owns one key in n, so (n - 1) / n of that arrives
  from other chips and is written to its memory once.

What is NOT counted, and so makes the share smaller, never larger: the
replicated core's passes (every chip runs the Jacobi loop, the wave schedule
and the ladder for ALL lanes: the implementation's way, not least work: one
chip could validate a lane and tell the others one code); every column of
every gathered row summed over all chips beyond the list above; table-sized
temporaries.  The mix this is read on sends no lane that is refused.
"""

from benchmarks.harness import bytes_model, shard_bytes_model


def context_lane_bytes() -> float:
    """What a chip that owns none of a resolving lane's keys must be told."""
    found = shard_bytes_model.FOUND_BYTES
    side = (found + shard_bytes_model.SLOT_BYTES
            + bytes_model.ACCOUNT_META_BYTES
            + bytes_model.ACCOUNT_SIDE_BALANCE_BYTES)
    pending = found + bytes_model.TRANSFER_VALUE_BYTES
    posted = found + bytes_model.POSTED_VALUE_BYTES
    return found + pending + 2 * side + posted


def resolve_lane_bytes_per_chip(shards: int) -> float:
    """A post or a void, on one of `shards` chips."""
    return (bytes_model.resolve_lane_bytes() / shards
            + context_lane_bytes() * (shards - 1) / shards)
