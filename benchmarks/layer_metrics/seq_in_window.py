"""Batches that ran on the sequential route between window open and close:
d`ops.sequential_batches` (`machine._sequential_impl`: an event-at-a-time
`lax.scan`, seconds a batch of 8190).  0 is the sizing invariant of a cell
whose batches cascade: one whose Jacobi loop does not converge within
`jacobi_max_passes` is handed over (`ops.general.seq_handovers` counts those
alone).  A counter that never moved is absent from a snapshot, which reads
0 here."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    return snapshots.counter(s["open"], s["close"], "ops.sequential_batches")
