"""Requests that miss a commit group by the length of their own body read,
mean per pickup over the window: connections whose request header has been
read and whose body has not been enqueued yet when the bus picks a group up
(`net.pickup.arriving`)."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    return snapshots.histogram_mean(s["open"], s["close"],
                                    "net.pickup.arriving")
