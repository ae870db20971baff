"""Device milliseconds per committed request: the summed device time of every
program execution in the profiler's window (the trace's `XLA Modules` line)
over the requests committed between the trace-start and trace-stop
snapshots.  Index merges and lookups' programs are in it: it is what the
device spends per request, not one kernel's time."""

from benchmarks.harness import snapshots


def read(run):
    s, trace = run["snapshots"], run["trace"]
    commits = snapshots.counter(s["trace_start"], s["trace_stop"],
                                "replica.commits")
    if trace is None or commits <= 0:
        return None
    return trace["program_s"] * 1e3 / commits
