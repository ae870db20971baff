"""Host milliseconds a request spent being ENQUEUED (span `dispatch`: the
jitted commit call(s), on a TPU the enqueue), whichever thread did it: the
sum over roles of d`txtrace.self_us.<role>.dispatch` over the requests
released in the window."""

from benchmarks.layer_metrics.stage_ms_per_request import self_ms_per_request


def read(run):
    return self_ms_per_request(run, "dispatch")
