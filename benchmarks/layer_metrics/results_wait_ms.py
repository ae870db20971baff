"""Mean milliseconds between the replica call of a request's group returning and
its replies promise resolving, over the window
(`txtrace.request.results_wait`): the group stays pending until the next
group's call (or the idle flush) joins its dispatch and reads it back."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"], "txtrace.request.results_wait")
    return None if us is None else us / 1e3
