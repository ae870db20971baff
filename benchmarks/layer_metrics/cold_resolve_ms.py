"""Mean milliseconds of one host resolution of a FLAG_COLD batch, over the
window: span `cold_resolve` (`txtrace.stage.cold_resolve`, inside
`general_commit` on the serving thread): the flagged lanes' ids searched in
the cold store's runs (vectorised, `ColdStore.lookup_arrays`) and, child
`cold_rehydrate`, the rows found uploaded and inserted into the hot table by
one program.  The re-dispatch that follows is not in it (`general_commit_ms`
has both executions).  None where no batch was resolved in the window, or the
program has no such span."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"],
                                  "txtrace.stage.cold_resolve")
    return None if us is None else us / 1e3
