"""Share of the sharded fast commit program's self time on device 0 that
lies in collective operations (`all-reduce*`, the asynchronous form's start
and done included): what the psum exchange costs a request.  The reduced
trace's `ops` are keyed `program:operation`, an operation's seconds its self
time."""

from benchmarks.layer_metrics.shard_kernel_ms import PROGRAM

COLLECTIVE = "all-reduce"


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
