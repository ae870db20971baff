"""Device milliseconds per execution of the general (Jacobi) commit program
inside the profiler's window: the `XLA Modules` events of
`jit_create_transfers_full*` (the trace's `programs`), seconds over count.
One execution commits one resolving request; the secondary index's programs
that follow it are not in it (`kernel_ms_per_batch` has everything)."""

PROGRAM = "create_transfers_full"


def executions(trace):
    """(device seconds, executions) of the general program in a reduced
    trace; (0.0, 0) where it never ran there."""
    found = [v for name, v in trace["programs"].items() if PROGRAM in name]
    return sum(v[0] for v in found), sum(v[1] for v in found)


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    seconds, count = executions(trace)
    return seconds * 1e3 / count if count else None
