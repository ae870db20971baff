"""Mean milliseconds of the general route's blocking device wait, over the
window (`txtrace.stage.full_sync`): the read of the kernel's flags, which
returns when the device has run the commit; one a dispatch."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"],
                                  "txtrace.stage.full_sync")
    return None if us is None else us / 1e3
