"""Mean milliseconds between a request being enqueued at the bus (body read and
verified) and its commit group being picked up, over the window
(`txtrace.request.admission_wait`): the serving thread was busy with earlier
groups."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"], "txtrace.request.admission_wait")
    return None if us is None else us / 1e3
