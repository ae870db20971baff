"""Mean Jacobi passes a committed general request took to its fixpoint, over
the window (`waves.jacobi_passes`, read back with the kernel's flags).  On a
TPU the pass loop has a static trip (4 passes, then 4 more only where the
first 4 did not settle it): this is the count up to and including the pass
that stabilized, not the passes the device ran."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    return snapshots.histogram_mean(s["open"], s["close"],
                                    "waves.jacobi_passes")
