"""Mean milliseconds of one eviction of the cold tier in SET-UP: span
`cold_evict` (`txtrace.stage.cold_evict`) in the snapshot taken at window
open, as `checkpoint_capture_ms` reads its span.  The serving thread is held
for all of it (children `cold_threshold`, `cold_extract`, `cold_fetch`,
`cold_spill`, `cold_rehash`, `cold_filter`), and so is every session: it
moves `setup_s`.  None where no eviction fell in set-up, or the program has no
such span."""


def read(run):
    spans = run["snapshots"]["open"].get("histograms", {})
    evictions = spans.get("txtrace.stage.cold_evict")
    if not evictions or not evictions.get("count"):
        return None
    return evictions["sum"] / evictions["count"] / 1e3
