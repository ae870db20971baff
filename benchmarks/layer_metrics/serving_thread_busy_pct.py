"""Share of the window that the one serving thread spent inside its
synchronous sections: d`serve.busy_us` (the replica call of a group, the idle
flush, the reply writes, the ingress checksum) over the seconds from the
window's first send to its last reply.  It is busy while it blocks in a lane
join or a readback: high here beside a busy device means the thread waits,
not that it works."""

from benchmarks.harness import snapshots


def read(run):
    s, window = run["snapshots"], run["window"]
    if "serve.busy_us" not in s["close"]["counters"] or not window:
        return None
    seconds = (max(r.t_reply for r in window)
               - min(r.t_send for r in window))
    busy_us = snapshots.counter(s["open"], s["close"], "serve.busy_us")
    return 100.0 * busy_us / (seconds * 1e6) if seconds > 0 else None
