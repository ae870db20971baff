"""Share of the lanes of committed general batches whose result code is not
OK, over the window: d`ops.general.rejected_lanes` / d`ops.general.lanes`
(`machine._full_commit_success`), in percent.  In a cell whose accounts may
not overdraw these are the refused payments; a seed must not move it, because
the end-to-end numerator counts accepted events only.  None where the program
counts no rejected lanes (a parent without the counter) or no general batch
committed."""

from benchmarks.harness import snapshots

REJECTED = "ops.general.rejected_lanes"


def read(run):
    s = run["snapshots"]
    if REJECTED not in s["close"].get("counters", {}):
        return None
    lanes = snapshots.counter(s["open"], s["close"], "ops.general.lanes")
    if lanes <= 0:
        return None
    return 100.0 * snapshots.counter(s["open"], s["close"], REJECTED) / lanes
