"""Mean milliseconds of one BLOCKING commit on the serving thread
(`txtrace.stage.device_execute.serving`): a resolving request's general
commit, staging and device wait included; the other part of
`lane_execute_ms`'s mixture."""

from benchmarks.layer_metrics.lane_closure_ms import closure_ms


def read(run):
    return closure_ms(run, "serving")
