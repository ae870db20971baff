"""Where the traced run's one profiler window lies: cued by the measured
window's own progress (requests answered), not by the wall clock, so that it
lies inside the measured window at any rate.

A mix caps its window at a number of requests (`README.md`, sizing
invariants 1 and 2), and a faster program reaches the cap sooner: a profiler
opened at a fixed second would then find the window over and hold no device
operation.  So the profiler

- OPENS once half of the cap has been answered, or `OPEN_SHARE` of the run's
  seconds have passed (the arm of a window that never reaches its cap: an
  open loop), whichever comes first, and never before the first reply;
- CLOSES `keep_s` seconds later, or once all but the last cycle of the cap
  (one request a session) has been answered, whichever comes first.

`TraceCue` holds the two decisions as plain functions of (seconds since the
window opened, requests answered); `place` waits on a `drive.Progress` for
them and sends the server its cues.
"""

from __future__ import annotations

import dataclasses
import time

OPEN_SHARE = 0.4            # of the run's seconds: the clock's arm to open
KEEP_S, KEEP_SHARE = 5.0, 0.25


class WindowOver(Exception):
    """The measured window ended before the profiler could be opened."""


@dataclasses.dataclass(frozen=True)
class TraceCue:
    open_answered: int      # open once so many requests are answered ...
    open_after_s: float     # ... or so long after the window opened
    close_answered: int     # close once so many are answered ...
    keep_s: float           # ... or so long after the profiler opened

    @classmethod
    def for_window(cls, cap: int, sessions: int, seconds: float) -> "TraceCue":
        """`cap`: the requests the plan's window holds; `sessions`: its
        queues (the last cycle is one request of each)."""
        return cls(open_answered=max(1, cap // 2),
                   open_after_s=OPEN_SHARE * seconds,
                   close_answered=cap - sessions,
                   keep_s=min(KEEP_S, KEEP_SHARE * seconds))

    def opens(self, at_s: float, answered: int) -> bool:
        return answered >= 1 and (answered >= self.open_answered
                                  or at_s >= self.open_after_s)

    def closes(self, at_s: float, answered: int, opened_at_s: float) -> bool:
        return (at_s - opened_at_s >= self.keep_s
                or answered >= self.close_answered)


def place(server, progress, cue: TraceCue, trace_dir: str) -> dict:
    """One profiler window inside the run `progress` follows.  Returns when
    each cue was sent (and the first one answered), on `time.monotonic()`'s
    scale, and how many requests had been answered by then.  Nothing else is
    asked of the server at these two moments: what the readers count of the
    profiler's window they count in the trace itself."""
    _at_s, answered = progress.wait(cue.opens, cue.open_after_s)
    if progress.finished:
        raise WindowOver(
            f"the window ended with {answered} requests answered before the "
            f"profiler opened (it opens at {cue.open_answered} answered or "
            f"after {cue.open_after_s:.1f} s)")
    opened, opened_answered = time.monotonic(), progress.answered
    server.cue("trace_start", dir=trace_dir)
    started = time.monotonic()
    opened_at_s = started - progress.began
    _at_s, _n = progress.wait(
        lambda at_s, n: cue.closes(at_s, n, opened_at_s),
        opened_at_s + cue.keep_s)
    closed, closed_answered = time.monotonic(), progress.answered
    server.cue("trace_stop", timeout_s=300.0)
    return {"opened": opened, "started": started, "closed": closed,
            "answered": [opened_answered, closed_answered]}
