"""Device milliseconds per WHOLE execution of the general (Jacobi) commit
program inside the profiler's window: the `XLA Modules` events of
`jit_create_transfers_full*` that neither edge of the trace cut, seconds over
count.  One execution commits one resolving request; the secondary index's
programs that follow it are not in it (`kernel_ms_per_batch` has everything).
"""

from benchmarks.harness import commit_programs


def executions(trace):
    """(device seconds, executions) of the general program's whole
    executions in a reduced trace; (0.0, 0) where it never ran whole there."""
    found = commit_programs.whole_executions(trace, commit_programs.GENERAL)
    return sum(e[2] for e in found) / 1e9, len(found)


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    seconds, count = executions(trace)
    return seconds * 1e3 / count if count else None
