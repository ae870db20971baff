"""Mean milliseconds of the readback stage, over the window.  On a TPU the
`device_execute` stage is only the enqueue: the wait for the device is here
(`machine._lane_dispatch`)."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"],
                                  "txtrace.stage.readback")
    return None if us is None else us / 1e3
