"""Mean milliseconds of the WAL append+fsync stage per commit group, over the
window (`txtrace.stage.wal_fsync`, microseconds on the server's host clock)."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"],
                                  "txtrace.stage.wal_fsync")
    return None if us is None else us / 1e3
