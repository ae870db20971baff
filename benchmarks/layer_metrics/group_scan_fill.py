"""Useful steps of the grouped scan over steps run, percent, over the window:
d`ops.group.batches` / d`ops.group.steps`.  The scan always runs GROUP_K
steps; a group of k requests uses k of them."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    steps = snapshots.counter(s["open"], s["close"], "ops.group.steps")
    if steps <= 0:
        return None
    return 100.0 * snapshots.counter(
        s["open"], s["close"], "ops.group.batches") / steps
