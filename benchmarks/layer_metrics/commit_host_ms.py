"""Mean milliseconds of a request inside the synchronous replica call of its own
group, pickup to return, over the window (`txtrace.request.commit_host`):
prepare, staging, journal writes, and the PREVIOUS group's lane join, readback
and reply build, which run inside this call."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"], "txtrace.request.commit_host")
    return None if us is None else us / 1e3
