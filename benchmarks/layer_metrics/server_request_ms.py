"""Mean milliseconds a request spends inside the server, over the window: from
its header read to its reply written (`txtrace.request.total`, observed once
per released request in `net/bus.py`).  The six request intervals
(`ingress`, `admission_wait`, `commit_host`, `results_wait`, `barrier_wait`,
`reply_release`) are consecutive and sum to it exactly."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"], "txtrace.request.total")
    return None if us is None else us / 1e3
