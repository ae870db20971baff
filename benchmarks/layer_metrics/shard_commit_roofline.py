"""Share of ONE chip's HBM roofline reached by the sharded commit work
inside the profiler's window: the least time a chip could take for its share
of the whole requests in the trace (`harness/shard_bytes_model.py`: one n-th
of a request's table traffic plus the context it must receive, over the
device's published HBM bandwidth) over device 0's time of every program
execution in the span that holds those requests.

Requests and device time are `kernel_ms_per_batch`'s, counted on the trace's
own clock (`harness/commit_programs.py`, device 0's executions): every chip
runs every program, so device 0's time is a chip's time.  `commit_roofline`
divides all of a request's bytes by one chip's bandwidth; this divides a
chip's bytes by it."""

from benchmarks.harness import commit_programs, shard_bytes_model


def read(run):
    trace, mix = run["trace"], run["mix"]
    shards = shard_bytes_model.shards_of(run["config"])
    if run["peaks"] is None or trace is None or not shards or shards < 2:
        return None
    whole = commit_programs.whole_requests(trace)
    if whole is None or whole["program_s"] <= 0 or whole["general"]:
        return None
    moved = (whole["fast"] * mix["batch"]
             * shard_bytes_model.fast_lane_bytes_per_chip(shards))
    least_s = moved / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / whole["program_s"]
