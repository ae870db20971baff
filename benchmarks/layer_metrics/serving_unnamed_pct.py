"""Share of the window that the serving thread WORKED under no leaf span:
under no span at all (`(no span)` on its line of a profile: the event loop's
own turns, coroutine code between two awaits), or in the OWN time of a span
that holds others (`commit_group` outside `prepare`, `stage_h2d`, ...;
`general_commit` between its children).  The window less the selector's
`loop_wait` less the serving thread's self time in every LEAF span.

Which spans are leaves is read from the run, not listed here: a name whose
self times (over all roles) add up to its durations (`txtrace.stage.<name>`)
never had a child; one whose do not, held some.  A section that gains a child
span moves from named to holder by itself."""

from benchmarks.harness import snapshots
from benchmarks.layer_metrics.serving_work_pct import SELF, window_us

ALL_ROLES = "txtrace.self_us."


def leaves(before, after):
    """The span names with no child in the window: the duration less the
    self time is within the counters' rounding (half a microsecond a span)."""
    by_name = {}
    for counter in after["counters"]:
        if counter.startswith(ALL_ROLES):
            name = counter[len(ALL_ROLES):].split(".", 1)[1]
            by_name[name] = by_name.get(name, 0) + snapshots.counter(
                before, after, counter)
    out = set()
    for name, own in by_name.items():
        h1 = after["histograms"].get("txtrace.stage." + name)
        if h1 is None:
            continue
        h0 = before["histograms"].get(
            "txtrace.stage." + name, {"sum": 0, "count": 0})
        if (h1["sum"] - h0["sum"]) - own <= h1["count"] - h0["count"]:
            out.add(name)
    return out


def read(run):
    us = window_us(run)
    if us is None:
        return None
    before, after = run["snapshots"]["open"], run["snapshots"]["close"]
    named = sum(snapshots.counter(before, after, SELF + name)
                for name in leaves(before, after))   # `loop_wait` is one
    return 100.0 * (us - named) / us
