"""Device milliseconds of one WHOLE execution of the sharded fast commit
program (`jit_sharded_create_transfers_fast_probed`: one request, lone or one
of a grouped run's K dispatches) on device 0 inside the profiler's window:
the `XLA Modules` events that neither edge of the trace cut, seconds over
count.  Every chip runs the program for the whole replicated batch, so this
is what a request costs each of them."""

from benchmarks.harness import commit_programs

PROGRAM = "sharded_create_transfers_fast_probed"


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    found = commit_programs.whole_executions(trace, PROGRAM)
    if not found:
        return None
    return sum(e[2] for e in found) / 1e6 / len(found)
