"""Share of the window's committed requests that the cold tier had
dispatched a second time: d`cold.redispatches` (`machine._resolve_cold`: one
for every general execution that came back with FLAG_COLD, i.e. some lane's id
or pending id missed the hot table and hit the filter, so the kernel applied
nothing, the host resolved the flagged lanes and the whole batch was
dispatched again) over d`ops.route.general` (committed general batches), in
percent.  In a mix whose every Nth request retries old ids it reads 100 / N
plus the false positives (`cold_false_positive_pct`).  None where the program
has no tier, or counts no re-dispatches (a parent), or no general batch
committed."""

from benchmarks.harness import snapshots

GAUGE = "cold.bloom_bits_log2"


def tiered(run) -> bool:
    """The program reports a cold tier's filter (this PR's gauge, set at
    start under `--hot-transfers-log2-max`)."""
    return GAUGE in run["snapshots"]["close"].get("gauges", {})


def read(run):
    if not tiered(run):
        return None
    s = run["snapshots"]
    requests = snapshots.counter(s["open"], s["close"], "ops.route.general")
    if requests <= 0:
        return None
    again = snapshots.counter(s["open"], s["close"], "cold.redispatches")
    return 100.0 * again / requests
