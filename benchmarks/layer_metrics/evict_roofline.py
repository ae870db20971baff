"""Share of the HBM roofline reached by the eviction's three device programs
in SET-UP: the least time the chip could take to move the bytes the set-up's
evictions must move (`harness/evict_bytes_model.py`: every slot scanned once,
the leaving rows written once, the kept rows read and written once; over the
device's published HBM bandwidth) over the time of the spans `cold_threshold`
+ `cold_extract` + `cold_rehash` in the snapshot taken at window open.

The source is the program's spans, not the device trace: the harness's one
profiler window lies inside the measured window, where no eviction runs (a
sizing invariant, `evictions_in_window`).  Each of the three spans holds one
program from its dispatch to a blocking read of its result, on a serving
thread that does nothing else meanwhile, so a span is its program's device
time plus a dispatch and a read of microseconds against seconds: the share can
only read LOW by that, never high.

The rows: `ops.rows_evicted` and `ops.compactions` at window open; the rows
an eviction keeps follow from the configuration's `eviction_fraction`; the
slots from its `hot_transfers_slots_log2_max`.  None off a device with
published peaks, where no eviction fell in set-up, or the program has no such
spans."""

from benchmarks.harness import evict_bytes_model

SPANS = ("cold_threshold", "cold_extract", "cold_rehash")


def read(run):
    tables = run["config"].get("tables", {})
    if run["peaks"] is None or "hot_transfers_slots_log2_max" not in tables:
        return None
    at_open = run["snapshots"]["open"]
    spans = at_open.get("histograms", {})
    found = [spans.get(f"txtrace.stage.{name}") for name in SPANS]
    evictions = at_open.get("counters", {}).get("ops.compactions", 0)
    evicted = at_open.get("counters", {}).get("ops.rows_evicted", 0)
    if not all(found) or evictions <= 0 or evicted <= 0:
        return None
    seconds = sum(h["sum"] for h in found) / 1e6
    if seconds <= 0:
        return None
    share = tables["eviction_fraction"]
    moved = evict_bytes_model.eviction_bytes(
        evictions << tables["hot_transfers_slots_log2_max"], evicted,
        int(evicted * (1 - share) / share))
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
