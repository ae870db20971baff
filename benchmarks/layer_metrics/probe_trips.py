"""Mean trips of the probe loop in a lone fast request: the trips of the
longest `while` of every WHOLE execution of `jit_create_transfers_fast_probed`
inside the profiler's window (`trace["executions"]` carries them; an
execution an edge of the trace cut is left out).  The loop walks the
open-addressing tables until every lane has found its row or an empty slot,
so its trips go by the tables' LOAD and by the longest run of occupied slots
a lane of the batch meets, not by the tables' size.  None where no such
execution lies whole in the trace, or the trace shows no loop (it has no
operations' line)."""

from benchmarks.harness import commit_programs

PROGRAM = commit_programs.ONE_REQUEST[0]


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    trips = [e[3] for e in commit_programs.whole_executions(trace, PROGRAM)
             if e[3]]
    return sum(trips) / len(trips) if trips else None
