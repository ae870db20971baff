"""Mean milliseconds of one DEFERRED commit closure on the lane thread
(`txtrace.stage.device_execute.lane`): what `lane_execute_ms` reads where
no request takes a blocking route, and the lane's part of it where some do."""

from benchmarks.harness import snapshots


def closure_ms(run, role):
    """Mean span `device_execute` on the threads of one role, in ms."""
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"],
                                  f"txtrace.stage.device_execute.{role}")
    return None if us is None else us / 1e3


def read(run):
    return closure_ms(run, "lane")
