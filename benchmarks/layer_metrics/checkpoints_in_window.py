"""Checkpoint captures between window open and close:
d`replica.checkpoint.captures` (`vsr/replica.py` `_checkpoint_capture`: every
table column copied to the host and the ledger's digest, inline on the
serving thread).  0 is the sizing invariant of a cell whose set-up crosses
checkpoints: a capture inside the window is a stall of seconds.  None where
the program counts no captures (a parent without the counter; a run that
never reached a checkpoint)."""

from benchmarks.harness import snapshots

COUNTER = "replica.checkpoint.captures"


def read(run):
    s = run["snapshots"]
    if COUNTER not in s["close"].get("counters", {}):
        return None
    return snapshots.counter(s["open"], s["close"], COUNTER)
