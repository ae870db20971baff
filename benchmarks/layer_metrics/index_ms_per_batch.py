"""Device milliseconds per committed request spent in the secondary index's
own programs (`ops/index.py`: `jit_build_runs*`, a request's sorted level-0
runs, and `jit__merge*`, the carries upward): the device time of their
executions begun inside the span of whole requests, over the requests that
span's commit programs carried: `kernel_ms_per_batch`'s span and requests
(`harness/commit_programs.py`), so the two divide alike and this one is a
part of that one.  None where the trace holds fewer than two commit
programs, or no index program at all (a lazy index: `start --shards`)."""

from benchmarks.harness import commit_programs

INDEX_PROGRAMS = ("jit__merge", "jit_build_runs")


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    whole = commit_programs.whole_requests(trace)
    if whole is None:
        return None
    first, _last = trace["device_span_ns"]
    begun = [e[1] for e in trace["executions"]
             if commit_programs.commits(e) and e[1] > first]
    lo, hi = begun[0], begun[-1]
    index_ns = [e[2] for e in trace["executions"]
                if lo <= e[1] < hi and e[0].startswith(INDEX_PROGRAMS)]
    if not index_ns:
        return None
    return sum(index_ns) / 1e6 / (whole["fast"] + whole["general"])
