"""Who was doing what in each idle gap of a kept device trace, each host
thread named by its ROLE.

    python3 tools/trace_roles.py <file.xplane.pb> [<out.json>]

`benchmarks/harness/host_spans.py`'s two tables (the device's longest idle
gaps with the `tb.*` span every thread was in; device self time by `tb/`
scope) with every thread's line renamed `<role>/<line>`: `serving`, `lane`,
`io`, `checkpoint`, from the `role` stat that `txtrace.stage` gives each
span (obs/txtrace.py).  On the serving thread `loop_wait` is the selector,
so `(no span)` there is work that no span names, never sleep.  A trace of
a program older than the stat keeps its lines' names.  And a third table,
(c): each thread's SELF time by span with the share of it in which the
device was busy, which tells a thread that works beside an idle device from
one that is held behind a busy one (an enqueue the runtime does not take
yet reads as work in `serving_work_pct`).

A fork of `host_spans` for the time being: it goes when the `benchmark`
issue that wires `host_spans` into `breakdown` (ROADMAP B-II.0) makes
`host_spans._stats` follow reference values and carries table (c) over.
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from typing import Dict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import host_spans as hs  # noqa: E402
from benchmarks.harness.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, _union)


def _role(stat_bufs, stat_names: Dict[int, str]):
    """The `role` stat of one event: TraceMe hands a string argument over
    as a reference into the plane's stat names (XStat field 7), which
    `host_spans._stats` does not follow."""
    for buf in stat_bufs:
        name = value = None
        for number, v in hs._fields(buf):
            if number == 1:
                name = stat_names.get(v)
            elif number == 7:
                value = stat_names.get(v)
            elif number == 5:
                value = hs._text(v)
        if name == "role" and value:
            return value
    return None


def thread_roles(path: str) -> Dict[str, str]:
    """{"<line name>#<n>" (as `host_spans.read_events` names a thread):
    role} for the host lines whose first `tb.*` event carries one."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    roles: Dict[str, str] = {}
    for number, plane_buf in hs._fields(data):
        if number != 1:
            continue
        plane = hs._plane(plane_buf)
        if DEVICE_PLANE.match(plane["name"]):
            continue
        meta_of = hs._meta_reader(plane)
        for at, line_buf in enumerate(plane["lines"]):
            line_name, t0_ns, events = hs._line(line_buf)
            for event_buf in events:
                meta, _start, _dur, stats = hs._event(event_buf, t0_ns)
                if meta_of(meta)[0].startswith(hs.SPAN_PREFIX):
                    role = _role(stats, plane["stat_names"])
                    if role:
                        roles[f"{line_name}#{at}"] = role
                    break
    return roles


def read_events(path: str) -> dict:
    """`host_spans.read_events`, its threads renamed `<role>/<line>`."""
    events, roles = hs.read_events(path), thread_roles(path)
    events["threads"] = {
        (f"{roles[line]}/{line}" if line in roles else line): spans
        for line, spans in events["threads"].items()}
    return events


def busy_under_spans(events: dict) -> Dict[str, Dict[str, list]]:
    """{thread: {span: [self seconds, of them with the device busy]}}; a
    span's self time is where it is the innermost one open on its thread,
    and `(no span)` the rest of the stretch from the thread's first recorded
    span to its last (a span open at either edge of the profile is not
    recorded, so the window's own edges would read as unnamed)."""
    device = events["device"]
    busy = _union([(e[1], e[1] + e[2]) for e in (
        device.get(OPS_LINE) or device.get(MODULES_LINE) or [])])
    starts = [b[0] for b in busy]

    def busy_inside(lo: float, hi: float) -> float:
        total, at = 0.0, max(bisect.bisect_right(starts, lo) - 1, 0)
        while at < len(busy) and busy[at][0] < hi:
            total += max(0.0, min(busy[at][1], hi) - max(busy[at][0], lo))
            at += 1
        return total

    out: Dict[str, Dict[str, list]] = {}
    for line, spans in sorted(events["threads"].items()):
        per = out.setdefault(line, {})
        spanned = spanned_busy = 0.0
        first = min(s[1] for s in spans)
        last = max(s[1] + s[2] for s in spans)
        for lo, hi, label in hs._innermost(spans):
            slot = per.setdefault(label.rsplit(">", 1)[-1], [0.0, 0.0])
            inside = busy_inside(lo, hi)
            slot[0] += (hi - lo) / 1e9
            slot[1] += inside / 1e9
            spanned += hi - lo
            spanned_busy += inside
        per[hs.NO_SPAN] = [(last - first - spanned) / 1e9,
                           (busy_inside(first, last) - spanned_busy) / 1e9]
    return out


def print_busy_under_spans(table, out=sys.stdout) -> None:
    print("\n(c) each thread's self time by span, and the share of it with "
          "the device busy", file=out)
    for line, per in table.items():
        print(f"  {line}", file=out)
        for span, (seconds, busy) in sorted(
                per.items(), key=lambda kv: -kv[1][0]):
            share = 100 * busy / seconds if seconds > 0 else 0.0
            print(f"      {span:16s} {seconds * 1e3:10.1f} ms  "
                  f"device busy {share:5.1f} %", file=out)


def main(argv) -> int:
    if len(argv) not in (1, 2):
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    events = read_events(argv[0])
    reduced = hs.reduce(events)
    reduced["busy_under_spans"] = busy_under_spans(events)
    hs.print_tables(reduced)
    print_busy_under_spans(reduced["busy_under_spans"])
    if len(argv) == 2:
        with open(argv[1], "w") as f:
            json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
