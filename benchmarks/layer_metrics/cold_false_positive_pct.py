"""Share of the window's requests WITHOUT a true cold id that were dispatched
twice all the same: d`cold.false_redispatches` (a FLAG_COLD batch none of
whose flagged ids the cold store held: the filter's false positives alone
cost it a second general execution) over the committed general batches less
those that did hold a cold id (d`cold.redispatches` -
d`cold.false_redispatches`), in percent.  The filter is sized so that this
stays near 0 (`start --cold-bloom-log2`: bits a BATCH, not an id).  None where
the program has no tier or no such counters, or no request without a cold id
committed."""

from benchmarks.harness import snapshots
from benchmarks.layer_metrics.cold_redispatch_pct import tiered


def read(run):
    if not tiered(run):
        return None
    s = run["snapshots"]

    def moved(name):
        return snapshots.counter(s["open"], s["close"], name)

    false = moved("cold.false_redispatches")
    without = moved("ops.route.general") - (moved("cold.redispatches") - false)
    if without <= 0:
        return None
    return 100.0 * false / without
