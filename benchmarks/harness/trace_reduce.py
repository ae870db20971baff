"""From the profiler's trace (`*.xplane.pb`) to numbers.

Two stages, so that the arithmetic can be checked on a small recorded
fixture: `read_events` turns the file into plain lists with nothing but JAX
(`jax.profiler.ProfileData`; it initialises no backend), and `reduce` turns
those into busy time, per-program and per-operation sums and idle gaps.

A device plane is named `/device:TPU:<n>`.  Its line `XLA Modules` has one
event per program execution, `XLA Ops` one per operation inside it; the
operations nest (a `while` holds its body's operations), so an operation's
time here is its SELF time, without what is nested in it.  Busy is the union
of the operations' intervals (of the programs', where a trace has no
operation line), averaged over the device planes.  The traced window is the
span from the first event's start to the last event's end over ALL planes:
the host's threads log throughout, so that is the window the profiler was on.
Names are cut to what identifies them: a program without its fingerprint
(`jit_f(123)` -> `jit_f`), an operation without its HLO text (`%fusion.7`).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str) -> dict:
    """{"span_ns": [first start, last end] over all planes,
        "devices": {plane name: {line name: [[name, start_ns, dur_ns]...]}}}
    Only the device planes' events are kept; the host's give the span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    first, last = None, None
    devices: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        keep = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            events = [] if keep else None
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                if first is None or start < first:
                    first = start
                if last is None or end > last:
                    last = end
                if keep:
                    events.append([_short(e.name), start, e.duration_ns])
            if keep and line.name in (MODULES_LINE, OPS_LINE):
                devices.setdefault(plane.name, {})[line.name] = events
    return {"span_ns": [first, last], "devices": devices}


def _short(name: str) -> str:
    name = name.split(" = ", 1)[0]
    return re.sub(r"\(\d+\)$", "", name)[:96]


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _sums(events: list) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for name, _start, dur in events:
        slot = out.setdefault(name, [0.0, 0])
        slot[0] += dur / 1e9
        slot[1] += 1
    return out


def _self_time_by_op(ops: list, modules: list) -> Dict[str, List[float]]:
    """{`program:operation`: [self seconds, count]}: each operation's time
    without the operations nested in it, under the program it ran in."""
    starts = [s for _n, s, _d in modules]
    out: Dict[str, List[float]] = {}
    stack: List[list] = []            # [label, end, self_ns]

    def close(entry) -> None:
        slot = out.setdefault(entry[0], [0.0, 0])
        slot[0] += entry[2] / 1e9
        slot[1] += 1

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        at = bisect.bisect_right(starts, start) - 1
        program = modules[at][0] if at >= 0 else "?"
        stack.append([f"{program}:{name}", start + dur, dur])
    while stack:
        close(stack.pop())
    return out


def _loop_trips(ops: list, modules: list) -> List[int]:
    """For each program execution of `modules` (in time order): the trips of
    the longest `while` directly under it, 0 where it holds none.  A loop's
    body runs each of its operations once a trip, so the trips are the count
    most of the operations directly under the `while` share (its condition's
    run once more)."""
    starts = [s for _n, s, _d in modules]
    loops: List[Dict[int, list]] = [{} for _ in modules]  # start -> [dur, {}]
    stack: List[list] = []            # [end, the loop's slot or None]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        slot = None
        if not stack:
            at = bisect.bisect_right(starts, start) - 1
            if at >= 0 and name.lstrip("%").startswith("while"):
                slot = loops[at].setdefault(start, [dur, {}])
        elif len(stack) == 1 and stack[0][1] is not None:
            counts = stack[0][1][1]
            counts[name] = counts.get(name, 0) + 1
        stack.append([start + dur, slot])
    trips = []
    for found in loops:
        counts = max(found.values(), key=lambda v: v[0])[1] if found else {}
        tally: Dict[int, int] = {}
        for n in counts.values():
            tally[n] = tally.get(n, 0) + 1
        trips.append(max(tally, key=lambda n: (tally[n], -n)) if tally else 0)
    return trips


def _top(sums: Dict[str, List[float]]) -> List[list]:
    ranked = sorted(sums.items(), key=lambda kv: -kv[1][0])[:TOP]
    return [[name, seconds] for name, (seconds, _n) in ranked]


def reduce(events: dict) -> dict:
    """busy_s, window_s, program_s (all program executions, summed, averaged
    over devices), programs / ops ({name: [seconds, count]}, device 0's; an
    operation's seconds are its self time), executions (device 0's program
    executions in time order: [name, start_ns, dur_ns, trips of its longest
    loop]) inside device_span_ns (device 0's first event's start, its last
    one's end), device_ops and idle_gaps for the result line's `breakdown`."""
    first, last = events["span_ns"]
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace has no device plane: nothing ran on a "
                         "device inside the traced window")
    busy, program, unions = [], [], {}
    for name, lines in devices.items():
        source = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        unions[name] = _union([(s, s + d) for _n, s, d in source])
        busy.append(sum(e - s for s, e in unions[name]) / 1e9)
        program.append(sum(d for _n, _s, d in lines.get(MODULES_LINE, []))
                       / 1e9)
    name0 = sorted(devices)[0]
    lines0, merged = devices[name0], unions[name0]
    modules = sorted(lines0.get(MODULES_LINE, []), key=lambda e: e[1])
    # An idle gap is named by the program that ends it: what the host did in
    # it is not in the trace until the program has spans on this clock.
    starts = [s for _n, s, _d in modules]
    gaps = []
    edges = [[first, first]] + merged + [[last, last]]
    for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
        if s1 > e0:
            at = bisect.bisect_left(starts, s1)
            nxt = modules[at][0] if at < len(modules) else "end_of_trace"
            gaps.append([f"before:{nxt}", (s1 - e0) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    programs = _sums(modules)
    ops0 = lines0.get(OPS_LINE) or []
    op_sums = _self_time_by_op(ops0, modules)
    events0 = ops0 + modules
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (last - first) / 1e9,
        "program_s": sum(program) / len(program),
        "devices": len(devices),
        "programs": programs,
        "executions": [[name, start, dur, trips] for (name, start, dur), trips
                       in zip(modules, _loop_trips(ops0, modules))],
        "device_span_ns": [min(e[1] for e in events0),
                           max(e[1] + e[2] for e in events0)],
        "ops": op_sums,
        "device_ops": _top(op_sums),
        "idle_gaps": gaps[:TOP],
    }
