"""The device's timeline of a kept traced run, commit program by commit program.

    python3 tools/trace_timeline.py <dir>/run.json

on a run kept with `benchmarks/run.py --trace 1 --artifacts <dir>` (the
reduced trace's `executions`: device 0's program executions in time order).
One row a commit program (a lone `fast_probed`, a grouped loop with its
trips, a general execution): its own milliseconds, then what the device ran
BEHIND it until the next commit program (the index's `build_runs` and
`_merge`, and every eager operation of the host: a slice, a mask, a
`jnp.full` are each a program of a few microseconds): how many programs,
their milliseconds, the idle in gaps of at most `SHORT_GAP_MS` between them
(the device waiting for the host's next enqueue, ~0.2 ms each on a TPU v5
lite: PERF.md, PR 34) and the idle in the longer gaps, the last of which
ends at the next commit program (no closure was enqueued).  The last line
sums both kinds over the trace.  `tools/trace_ops.py` reads one execution
operation by operation; this reads the order of all of them.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import commit_programs  # noqa: E402

SHORT_GAP_MS = 1.0


def timeline(executions: list) -> list:
    """One dict a commit program, in time order (module docstring)."""
    rows, end_ns = [], None
    for name, start_ns, dur_ns, trips in sorted(
            executions, key=lambda e: e[1]):
        if rows:  # the gap before a program: the device is behind rows[-1]
            gap_ms = max(0.0, start_ns - end_ns) / 1e6
            rows[-1]["short_gaps_ms" if gap_ms <= SHORT_GAP_MS
                     else "long_gaps_ms"] += gap_ms
        end_ns = max(end_ns or 0.0, start_ns + dur_ns)
        if commit_programs.commits([name, start_ns, dur_ns, trips]):
            rows.append({
                "program": name, "trips": trips, "ms": dur_ns / 1e6,
                "behind": 0, "behind_ms": 0.0, "short_gaps_ms": 0.0,
                "long_gaps_ms": 0.0})
        elif rows:
            rows[-1]["behind"] += 1
            rows[-1]["behind_ms"] += dur_ns / 1e6
    return rows


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[1]) as f:
        rows = timeline(json.load(f)["trace"]["executions"])
    print("      ms trips  behind       ms  short gaps  long gaps  program")
    for r in rows:
        print(f"{r['ms']:8.2f} {r['trips']:5d} {r['behind']:7d} "
              f"{r['behind_ms']:8.2f} {r['short_gaps_ms']:11.2f} "
              f"{r['long_gaps_ms']:10.2f}  {r['program']}")
    requests = sum(commit_programs.requests_of([r["program"], 0, 0, r["trips"]])
                   for r in rows)
    print(f"{len(rows)} commit programs, {requests} requests, "
          f"{sum(r['behind'] for r in rows)} programs behind them; idle "
          f"{sum(r['short_gaps_ms'] for r in rows):.1f} ms in gaps <= "
          f"{SHORT_GAP_MS} ms, {sum(r['long_gaps_ms'] for r in rows):.1f} ms "
          "in longer ones")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
