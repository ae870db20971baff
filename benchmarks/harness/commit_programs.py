"""Which requests a traced profiler window holds WHOLE, counted on the
trace's own clock (the reduced trace's `executions`, device 0's).

A request's device work starts with its commit program: one execution of
`create_transfers_fast_probed*` or of `create_transfers_full*` (the general,
Jacobi kernel) commits one request; one of `_group_fast_dispatch*` commits as
many as its loop ran trips.  The index programs that follow belong to it,
up to the next commit program's start.  So the span that holds whole requests
and nothing else runs from the start of the first commit program that began
inside the trace to the start of the last one: what an edge of the profiler
window cut (a program under way when it opened, the last request's tail)
lies outside, numerator and denominator together.
"""

from __future__ import annotations

from typing import List, Optional

GENERAL = "create_transfers_full"
ONE_REQUEST = ("create_transfers_fast_probed", GENERAL)
GROUPED = "group_fast_dispatch"


def commits(execution: list) -> bool:
    return any(p in execution[0] for p in ONE_REQUEST + (GROUPED,))


def requests_of(execution: list) -> int:
    """Requests one commit program's execution committed."""
    name, _start, _dur, trips = execution
    return trips if GROUPED in name else 1


def whole_executions(trace: dict, program: str) -> List[list]:
    """The executions of `program` that neither edge of the trace cut: begun
    after the device's first event began, ended before its last one ended."""
    first, last = trace["device_span_ns"]
    return [e for e in trace["executions"]
            if program in e[0] and e[1] > first and e[1] + e[2] < last]


def whole_requests(trace: dict) -> Optional[dict]:
    """{"program_s": device seconds of every program execution that began in
    [start of the first commit program begun inside the trace, start of the
    last commit program), "fast": plain or pending requests committed there,
    "general": resolving ones, "span_s": that span}; None where the trace
    holds fewer than two commit programs, or a grouped one whose loop it
    does not show (a trace without the operations' line)."""
    first, _last = trace["device_span_ns"]
    begun = [e for e in trace["executions"] if commits(e) and e[1] > first]
    if len(begun) < 2:
        return None
    lo, hi = begun[0][1], begun[-1][1]
    inside = [e for e in trace["executions"] if lo <= e[1] < hi]
    carried = [(requests_of(e), GENERAL in e[0]) for e in inside if commits(e)]
    if not all(n for n, _general in carried):
        return None
    general = sum(n for n, is_general in carried if is_general)
    return {
        "program_s": sum(e[2] for e in inside) / 1e9,
        "fast": sum(n for n, _general in carried) - general,
        "general": general,
        "span_s": (hi - lo) / 1e9,
    }
