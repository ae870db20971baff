"""Mean milliseconds the serving thread waits in the join of a lane closure, per
dispatch handle, over the window (`txtrace.stage.dispatch_wait`)."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"], "txtrace.stage.dispatch_wait")
    return None if us is None else us / 1e3
