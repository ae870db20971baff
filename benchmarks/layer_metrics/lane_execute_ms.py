"""Mean milliseconds of a commit closure on the thread that runs it (the
`tb-dispatch` lane for a deferred dispatch), over the window
(`txtrace.stage.device_execute`): growth check, the jitted commit call, and
one index append per request.  On a TPU the jitted call is an enqueue; what
else the closure waits for is PERF.md section 5."""

from benchmarks.harness import snapshots


def read(run):
    s = run["snapshots"]
    us = snapshots.histogram_mean(s["open"], s["close"], "txtrace.stage.device_execute")
    return None if us is None else us / 1e3
