"""Evictions of the cold tier between window open and close:
d`ops.compactions` (`machine._evict_cold_impl`: the older half of the hot
transfers table moved to a run file, inline on the serving thread, for
seconds).  0 is the sizing invariant of a cell whose set-up crosses the hot
window's ceiling: the window has to close before the next one is due.  None
where the program has no tier (no `cold.bloom_bits_log2` gauge: a parent, or a
server started without `--hot-transfers-log2-max`)."""

from benchmarks.harness import snapshots
from benchmarks.layer_metrics.cold_redispatch_pct import tiered


def read(run):
    if not tiered(run):
        return None
    s = run["snapshots"]
    return snapshots.counter(s["open"], s["close"], "ops.compactions")
