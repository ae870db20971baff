"""Share of the HBM roofline reached by the general (Jacobi) commit program
inside the profiler's window: the least time the chip could take to move the
bytes its whole executions must move (each carries the mix's resolving lanes, a
post or a void each: `harness/bytes_model.resolve_lane_bytes()`, over the
device's published HBM bandwidth) over that program's own device time.  The
bound is HBM bandwidth; the kernel does no matrix arithmetic.  The index
appends that follow an execution are in neither term."""

from benchmarks.harness import bytes_model
from benchmarks.layer_metrics.general_kernel_ms import executions


def read(run):
    trace, mix = run["trace"], run["mix"]
    share = mix.get("resolve")
    if run["peaks"] is None or trace is None or share is None:
        return None
    seconds, count = executions(trace)
    if seconds <= 0:
        return None
    lanes = (mix["batch"] * share["post_pct"] // 100
             + mix["batch"] * share["void_pct"] // 100)
    moved = count * lanes * bytes_model.resolve_lane_bytes()
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
