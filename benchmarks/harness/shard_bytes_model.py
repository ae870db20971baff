"""HBM bytes ONE chip of a sharded ledger must move for one create_transfers
request: `shard_commit_roofline`'s numerator.

Counted from requests and lanes alone, never from the program's operations,
so that it reads the same work whatever implements it.  Two terms a lane:

- the chip's share of the table traffic.  Rows are owned by `mix64(id) & (n -
  1)`, uniformly: of a request's lanes a chip owns one in n of the transfer
  rows and one in n of each account side, so it moves one n-th of what one
  chip would move for the whole request (`bytes_model.fast_lane_bytes()`);
- the context it must receive.  Validation needs, for every lane, what the
  OWNERS of its three keys found: for each account side whether it exists,
  its global slot (the balance plan is laid over global slots) and the
  columns validation reads (flags, ledger, code, timestamp); for the transfer
  id whether it exists.  A chip owns one key in n, so (n - 1) / n of that
  arrives from other chips and is written to its memory once.  A duplicate's
  existing row is not counted: a lane that finds one is refused, and the mix
  this is read on sends none.

What the implementation exchanges beyond that (every column of every gathered
row, summed over all chips) is its own, and makes the share smaller.
"""

from benchmarks.harness import bytes_model

FOUND_BYTES = 4                      # one flag a key
SLOT_BYTES = 8                       # an account's global slot


def context_lane_bytes() -> float:
    """What a chip that owns none of a lane's three keys must be told."""
    side = FOUND_BYTES + SLOT_BYTES + bytes_model.ACCOUNT_META_BYTES
    return 2 * side + FOUND_BYTES


def shards_of(config: dict):
    """The `--shards N` of a configuration's `server_args`; None without."""
    args = config.get("server_args", [])
    if "--shards" not in args:
        return None
    return int(args[args.index("--shards") + 1])


def fast_lane_bytes_per_chip(shards: int) -> float:
    """A plain or pending transfer, on one of `shards` chips."""
    return (bytes_model.fast_lane_bytes() / shards
            + context_lane_bytes() * (shards - 1) / shards)
