"""Mean Jacobi passes the device RAN for a committed general request, over
the window (`waves.jacobi_passes`, read back with the kernel's flags).  The
pass loop is one gated scan on every backend: a pass runs only while the
loop's own exit has not been met, so this counts the passes executed (1.0
where every batch's wave bound proves one pass enough); the aux pass from the
final iterate is not in it."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    return snapshots.histogram_mean(s["open"], s["close"],
                                    "waves.jacobi_passes")
