"""Share of the window's committed general batches for which the wave
schedule proved no pass bound, in percent: d`waves.batches_unscheduled` over
that plus d`waves.batches_scheduled` (`machine._record_wave_metrics`).  An
unscheduled batch runs Jacobi passes until one repeats the last (the
stability exit: one verification pass more than its cascades are deep); a
scheduled one runs the count its conflict index proved.  None where no
general batch committed in the window."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    unscheduled = snapshots.counter(s["open"], s["close"],
                                    "waves.batches_unscheduled")
    total = unscheduled + snapshots.counter(s["open"], s["close"],
                                            "waves.batches_scheduled")
    return 100.0 * unscheduled / total if total > 0 else None
