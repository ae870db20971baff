"""Host milliseconds a request spent being STAGED (span `stage_h2d`: the
staging fill and the `device_put` of its batch), whichever thread did it:
the sum over roles of d`txtrace.self_us.<role>.stage_h2d` over the requests
released in the window (the growth of `txtrace.request.total`'s count)."""

from benchmarks.harness import snapshots


def self_ms_per_request(run, span):
    s = run["snapshots"]
    before, after = s["open"], s["close"]
    series = [name for name in after["counters"]
              if name.startswith("txtrace.self_us.")
              and name.endswith("." + span)]
    total = after["histograms"].get("txtrace.request.total")
    if not series or total is None:
        return None
    requests = total["count"] - before["histograms"].get(
        "txtrace.request.total", {"count": 0})["count"]
    if requests <= 0:
        return None
    us = sum(snapshots.counter(before, after, name) for name in series)
    return us / requests / 1e3


def read(run):
    return self_ms_per_request(run, "stage_h2d")
