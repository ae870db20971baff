"""Blocking device dispatches per committed request, over the window:
d`ops.dispatch` / d`replica.commits`.  Grouping lowers it, the general route
raises it."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    commits = snapshots.counter(s["open"], s["close"], "replica.commits")
    if commits <= 0:
        return None
    return snapshots.counter(s["open"], s["close"], "ops.dispatch") / commits
