"""Canonical rebuilds (`machine._query_ledger`: the sharded ledger pulled to
the host, re-placed and uploaded to device 0) between window open and close:
d`sharding.unshards`.  0 is the cell's sizing invariant: a rebuild inside
the window is a stall of seconds.  None where the server is not sharded (the
gauge `sharding.shards` is set when a sharded machine is built)."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    if "sharding.shards" not in s["close"].get("gauges", {}):
        return None
    return snapshots.counter(s["open"], s["close"], "sharding.unshards")
