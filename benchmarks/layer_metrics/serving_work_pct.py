"""Share of the window in which the one serving thread WORKED: neither asleep
in its selector (span `loop_wait`: socket wait) nor asleep on the device (the
spans of DEVICE_WAIT: the lane's join, a deferred readback, the general
route's sync), each by its SELF time on that thread
(d`txtrace.self_us.serving.<name>`).  Socket wait + device wait + this = 100
by definition; the window is the one `serving_thread_busy_pct` divides by.
Beside `device_idle_pct`: where nothing the thread does overlaps the device,
the two agree.

"Work" is what is left, so a thread BLOCKED inside a call that is no wait by
name counts as working: an enqueue that the runtime holds until the device
has room (`index_append` behind a long program, PERF.md section 5) reads as
work here, beside a busy device."""

from benchmarks.harness import snapshots

SELF = "txtrace.self_us.serving."
DEVICE_WAIT = ("dispatch_wait", "readback", "full_sync")


def window_us(run):
    """Microseconds from the window's first send to its last reply, or None
    where the server kept no self times (a program older than they are)."""
    s, window = run["snapshots"], run["window"]
    if not window or not any(
            name.startswith(SELF) for name in s["close"]["counters"]):
        return None
    seconds = (max(r.t_reply for r in window)
               - min(r.t_send for r in window))
    return seconds * 1e6 if seconds > 0 else None


def self_us(run, *spans):
    """The serving thread's self time in the named spans over the window."""
    s = run["snapshots"]
    return sum(snapshots.counter(s["open"], s["close"], SELF + span)
               for span in spans)


def shares(run):
    """{"socket_wait", "device_wait", "work"} in % of the window."""
    us = window_us(run)
    if us is None:
        return None
    socket_wait = self_us(run, "loop_wait")
    device_wait = self_us(run, *DEVICE_WAIT)
    return {"socket_wait": 100.0 * socket_wait / us,
            "device_wait": 100.0 * device_wait / us,
            "work": 100.0 * (us - socket_wait - device_wait) / us}


def read(run):
    got = shares(run)
    return None if got is None else got["work"]
