"""Share of the profiler's window in which no operation ran on the device:
1 - union of the device's operation intervals / the traced window."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
