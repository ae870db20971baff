"""Share of the HBM roofline reached by the general (Jacobi) commit program
on batches of plain lanes that a balance limit may refuse: the least time the
chip could take to move the bytes its whole executions inside the profiler's
window must move (`harness/hazard_bytes_model.py`: `batch` lanes each, of
which the share the window's clients saw refused writes nothing; over the
device's published HBM bandwidth) over that program's own device time.  The
passes beyond the first are NOT least work and are not in the numerator.
`general_roofline` is the same share for resolving lanes; a mix that has
those is not read here."""

from benchmarks.harness import hazard_bytes_model
from benchmarks.layer_metrics.general_kernel_ms import executions


def read(run):
    trace, mix = run["trace"], run["mix"]
    if run["peaks"] is None or trace is None or "resolve" in mix:
        return None
    seconds, count = executions(trace)
    done = [r for r in run["window"] if not r.error]
    events = sum(r.events for r in done)
    if seconds <= 0 or events <= 0:
        return None
    refused = sum(len(r.codes) for r in done) / events
    moved = count * hazard_bytes_model.batch_bytes(mix["batch"], refused)
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
