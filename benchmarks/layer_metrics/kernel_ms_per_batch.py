"""Device milliseconds per committed request, counted on the trace's own
clock: the summed device time of every program execution between the first
and the last commit program that began inside the profiler's window, over the
requests those commit programs carried (`harness/commit_programs.py`: one a
lone fast or general execution, a grouped dispatch as many as its loop ran
trips).  What an edge of the window cut is in neither term.  Index merges and
lookups' programs are in it: it is what the device spends per request, not
one kernel's time."""

from benchmarks.harness import commit_programs


def read(run):
    if run["trace"] is None:
        return None
    whole = commit_programs.whole_requests(run["trace"])
    if whole is None:
        return None
    return whole["program_s"] * 1e3 / (whole["fast"] + whole["general"])
