"""Mean milliseconds of the general route's blocking commit, over the window
(`txtrace.stage.general_commit`): what one resolving request holds the
serving thread for, from the growth check through staging, the dispatch, the
device wait (`general_sync_ms`) and the index append."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"],
                                  "txtrace.stage.general_commit")
    return None if us is None else us / 1e3
