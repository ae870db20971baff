"""Requests the bus hands the replica per commit group, over the window:
the mean of `net.group_size` (`net/bus.py`) between window open and close."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    return snapshots.histogram_mean(s["open"], s["close"], "net.group_size")
