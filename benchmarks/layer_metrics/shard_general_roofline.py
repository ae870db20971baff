"""Share of ONE chip's HBM roofline reached by the sharded general (Jacobi)
commit program inside the profiler's window: the least time a chip could take
for its share of the resolving lanes its whole executions carried
(`harness/shard_general_bytes_model.py`: one n-th of a lane's table traffic
plus the context the chip must receive, over the device's published HBM
bandwidth) over those executions' device time on device 0.

One execution of `jit_sharded_create_transfers_full*` commits one resolving
request (the mix's posts and voids); every chip runs the program for the
whole replicated batch, so device 0's time is a chip's time.  An execution
that an edge of the trace cut is left out, numerator and denominator.
`general_roofline` divides ALL a request's bytes by one chip's bandwidth;
this divides a chip's bytes by it.  None off a sharded server, on a mix that
resolves nothing, or where the program never ran whole in the trace.

The numerator takes every whole execution for one request of the mix's
post_pct + void_pct of `batch` lanes (7,780 here).  A retry inside the
route's 8-attempt loop is an execution of its own, so it counts as a
request's bytes a second time, and a resolving request shorter than the mix's
would be counted at full length: both overstate the share.  The window's
counters say whether either happened (`ops.general.retries` 0 and
d`ops.general.lanes` = 7,780 x d`ops.route.general` in every run so far);
they cover the whole window and the trace 5 s of it, so the reader does not
divide by them.  The share is not cut off at 100: one above it is a wrong
count."""

from benchmarks.harness import (
    commit_programs, shard_bytes_model, shard_general_bytes_model,
)

PROGRAM = "sharded_create_transfers_full"


def read(run):
    trace, mix = run["trace"], run["mix"]
    share = mix.get("resolve")
    shards = shard_bytes_model.shards_of(run["config"])
    if (run["peaks"] is None or trace is None or share is None
            or not shards or shards < 2):
        return None
    found = commit_programs.whole_executions(trace, PROGRAM)
    seconds = sum(e[2] for e in found) / 1e9
    if seconds <= 0:
        return None
    lanes = (mix["batch"] * share["post_pct"] // 100
             + mix["batch"] * share["void_pct"] // 100)
    moved = (len(found) * lanes
             * shard_general_bytes_model.resolve_lane_bytes_per_chip(shards))
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
