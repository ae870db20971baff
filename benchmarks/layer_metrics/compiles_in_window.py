"""XLA compiles (cache loads count too) between window open and close.  0
unless a cell's sizing rule is broken: an index level filled for the first
time, or a table grown, inside the window."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    return snapshots.counter(s["open"], s["close"], "jit.compiles")
