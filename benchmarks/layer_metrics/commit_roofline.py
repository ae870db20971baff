"""Share of the HBM roofline reached by the device's commit work inside the
profiler's window: the least time the chip could take to move the bytes the
committed requests must move (`harness/bytes_model.py`, over the device's
published HBM bandwidth) over the device time of every program execution.
The bound is HBM bandwidth; the kernels do no matrix arithmetic.

Requests and device time are `kernel_ms_per_batch`'s, counted on the trace's
own clock (`harness/commit_programs.py`): fast and grouped requests carry
`batch` plain or pending lanes, general requests the mix's resolving lanes
(`batch` where the mix has none).
"""

from benchmarks.harness import bytes_model, commit_programs


def read(run):
    trace, mix = run["trace"], run["mix"]
    if run["peaks"] is None or trace is None:
        return None
    whole = commit_programs.whole_requests(trace)
    if whole is None or whole["program_s"] <= 0:
        return None
    share = mix.get("resolve")
    resolve_lanes = mix["batch"] if share is None else (
        mix["batch"] * share["post_pct"] // 100
        + mix["batch"] * share["void_pct"] // 100)
    moved = (whole["fast"] * mix["batch"] * bytes_model.fast_lane_bytes()
             + whole["general"] * resolve_lanes
             * bytes_model.resolve_lane_bytes())
    least_s = moved / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / whole["program_s"]
