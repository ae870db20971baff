"""Share of the sharded general commit program's self time on device 0 that
lies in collective operations (`all-reduce*`, the asynchronous form's start
and done included): what the psum exchanges cost a resolving request, the
wait for the slowest chip among them.  `shard_collective_pct` is the same
share of the sharded FAST program.  The reduced trace's `ops` are keyed
`program:operation`, an operation's seconds its self time.  None where the
program ran no operation in the trace (an unsharded server, a plain mix)."""

from benchmarks.layer_metrics.shard_collective_pct import COLLECTIVE
from benchmarks.layer_metrics.shard_general_roofline import PROGRAM


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    total = collective = 0.0
    for key, (seconds, _count) in trace["ops"].items():
        program, _, operation = key.partition(":")
        if PROGRAM not in program:
            continue
        total += seconds
        if operation.lstrip("%").startswith(COLLECTIVE):
            collective += seconds
    return 100.0 * collective / total if total > 0 else None
