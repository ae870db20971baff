"""From the profiler's trace (`*.xplane.pb`) to WHO was doing WHAT.

    python3 -m benchmarks.harness.host_spans <file.xplane.pb>

on a run kept with `run.py --artifacts <dir>`.  Two tables:

(a) each of the device's longest idle gaps (`trace_reduce.reduce`'s own
    gaps) with, for every host thread that carries the program's spans, the
    `tb.*` span it was in (`obs/txtrace.py`'s `stage`: a TraceMe annotation
    on the profiler's clock), innermost first where spans nest;
(b) the device's self time by `tb/` scope (`jax.named_scope` in the
    program's kernels).  The scope is in an operation's METADATA (its
    `tf_op` stat: the HLO `op_name`), which `jax.profiler.ProfileData` does
    not surface; so this module reads the file itself.

Two stages, as in `trace_reduce`, so that the arithmetic can be checked on a
small recorded fixture: `read_events` turns the file into plain lists (a
schema-less walk of the protobuf by the field numbers of `xplane.proto`;
nothing is imported but the standard library), `reduce` turns those into the
two tables.  Nothing calls this yet: the `benchmark` issue that follows wires
it into the result line's `breakdown`.  A trace of a program without the
spans or the scopes gives gaps with no thread and one scope, `(none)`.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks.harness.trace_reduce import (
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, TOP, _short, _union)

SPAN_PREFIX = "tb."
SCOPE = re.compile(r"tb/([A-Za-z0-9_]+)")
NO_SCOPE, NO_SPAN = "(none)", "(no span)"


# -- stage 1: the file ----------------------------------------------------------


def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode(errors="replace")


def _map_entry(buf) -> Tuple[int, object]:
    key, value = 0, b""
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stats(buf_list, stat_names: Dict[int, str]) -> Dict[str, object]:
    """XStat messages -> {stat name: value} (ints and strings only)."""
    out = {}
    for buf in buf_list:
        name, value = None, None
        for number, v in _fields(buf):
            if number == 1:
                name = stat_names.get(v)
            elif number in (3, 4):          # uint64 / int64
                value = v
            elif number == 5:               # str
                value = _text(v)
        if name is not None and value is not None:
            out[name] = value
    return out


def _plane(buf) -> dict:
    """One XPlane: its name, its lines (still encoded), and its two
    metadata tables (event metadata still encoded, stat names decoded)."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            for n, sv in _fields(value):
                if n == 2:
                    stat_names[key] = _text(sv)
    return {"name": name, "lines": lines, "event_meta": event_meta,
            "stat_names": stat_names}


def _line(buf) -> Tuple[str, int, list]:
    name, display, t0_ns, events = "", "", 0, []
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 11:
            display = _text(v)
        elif number == 3:
            t0_ns = v
        elif number == 4:
            events.append(v)
    return display or name, t0_ns, events


def _event(buf, t0_ns: int) -> Tuple[int, float, float, list]:
    meta = offset_ps = duration_ps = 0
    stats = []
    for number, v in _fields(buf):
        if number == 1:
            meta = v
        elif number == 2:
            offset_ps = v
        elif number == 3:
            duration_ps = v
        elif number == 4:
            stats.append(v)
    return meta, t0_ns + offset_ps / 1e3, duration_ps / 1e3, stats


def _event_meta(buf, stat_names) -> Tuple[str, Dict[str, object]]:
    name, stats = "", []
    for number, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 5:
            stats.append(v)
    return name, _stats(stats, stat_names)


def _meta_reader(plane: dict):
    """key -> (event name, its metadata's stats), each decoded once."""
    seen: Dict[int, Tuple[str, Dict[str, object]]] = {}

    def meta_of(key: int) -> Tuple[str, Dict[str, object]]:
        got = seen.get(key)
        if got is None:
            buf = plane["event_meta"].get(key)
            got = seen[key] = (("?", {}) if buf is None else
                               _event_meta(buf, plane["stat_names"]))
        return got

    return meta_of


def _scope_of(meta_stats: Dict[str, object]) -> str:
    """The innermost `tb/<scope>` of an operation's `op_name` (the `tf_op`
    stat of its metadata)."""
    found = SCOPE.findall(str(meta_stats.get("tf_op", "")))
    return found[-1] if found else NO_SCOPE


def read_events(path: str) -> dict:
    """{"span_ns": [first start, last end] over all planes,
        "device": {"XLA Modules": [[name, start_ns, dur_ns]...],
                   "XLA Ops": [[name, start_ns, dur_ns, scope]...]}
                  (the first device plane's),
        "threads": {"<line name>#<n>": [[span, start_ns, dur_ns, seq]...]}
                  (the host lines that carry `tb.*` events)}"""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    first = last = None
    device: Optional[dict] = None
    device_name = None
    threads: Dict[str, list] = {}
    for number, plane_buf in _fields(data):
        if number != 1:
            continue
        plane = _plane(plane_buf)
        is_device = DEVICE_PLANE.match(plane["name"]) is not None
        keep_device = is_device and (
            device_name is None or plane["name"] < device_name)
        meta_of = _meta_reader(plane)
        lines_out: Dict[str, list] = {}
        for at, line_buf in enumerate(plane["lines"]):
            line_name, t0_ns, events = _line(line_buf)
            rows = []
            for event_buf in events:
                meta, start, dur, stats = _event(event_buf, t0_ns)
                end = start + dur
                if first is None or start < first:
                    first = start
                if last is None or end > last:
                    last = end
                if keep_device and line_name in (MODULES_LINE, OPS_LINE):
                    name, meta_stats = meta_of(meta)
                    row = [_short(name), start, dur]
                    if line_name == OPS_LINE:
                        row.append(_scope_of(meta_stats))
                    rows.append(row)
                elif not is_device:
                    name, _ = meta_of(meta)
                    if name.startswith(SPAN_PREFIX):
                        seq = _stats(stats, plane["stat_names"]).get("seq", 0)
                        rows.append([name[len(SPAN_PREFIX):], start, dur,
                                     seq])
            if not rows:
                continue
            if keep_device:
                lines_out[line_name] = rows
            else:
                # Threads nobody named share the process's name: a line is
                # known by its place in the plane.
                threads[f"{line_name}#{at}"] = rows
        if keep_device:
            device, device_name = lines_out, plane["name"]
    return {"span_ns": [first, last], "device": device or {},
            "threads": threads}


# -- stage 2: the arithmetic ----------------------------------------------------


def _innermost(spans: list) -> List[Tuple[float, float, str]]:
    """Nested spans of one thread -> consecutive (start, end, label) pieces,
    each labelled by the spans open in it, outermost first
    (`commit_group>stage_h2d`)."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (end, name)
    cursor = 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        if stack and until > cursor:
            pieces.append((cursor, until, ">".join(n for _e, n in stack)))
        cursor = max(cursor, until)

    for name, start, dur, _seq in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        cursor = max(cursor, start)
        stack.append((start + dur, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return pieces


def _overlaps(pieces, starts, lo: float, hi: float) -> Dict[str, float]:
    """{label: seconds} of one thread's pieces inside [lo, hi]."""
    out: Dict[str, float] = {}
    at = max(bisect.bisect_right(starts, lo) - 1, 0)
    while at < len(pieces) and pieces[at][0] < hi:
        start, end, label = pieces[at]
        inside = min(end, hi) - max(start, lo)
        if inside > 0:
            out[label] = out.get(label, 0.0) + inside / 1e9
        at += 1
    return out


def _self_time_by_scope(ops: list) -> Dict[str, List[float]]:
    """{scope: [self seconds, operations]}: an operation's time without the
    operations nested in it (a `while` holds its body's)."""
    out: Dict[str, List[float]] = {}
    stack: List[list] = []                   # [scope, end, self_ns]

    def close(entry) -> None:
        slot = out.setdefault(entry[0], [0.0, 0])
        slot[0] += entry[2] / 1e9
        slot[1] += 1

    for _name, start, dur, scope in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            # Only what lies inside the holder: an asynchronous operation
            # may outlast the one it started in.
            stack[-1][2] -= min(start + dur, stack[-1][1]) - start
        stack.append([scope, start + dur, dur])
    while stack:
        close(stack.pop())
    return out


def reduce(events: dict) -> dict:
    """idle_gaps: the TOP longest, each {"seconds", "at_s" (from the trace's
    start), "before" (the program that ends it, as `trace_reduce` names it),
    "threads": {line: {label: seconds inside the gap}, longest first, with
    `(no span)` for the rest}}; scope_self_s: {scope: [seconds, operations]}
    and scoped_pct, the share of busy self time under some `tb/` scope;
    thread_spans: {line: {span: [count, seconds]}}."""
    first, last = events["span_ns"]
    device = events["device"]
    ops = device.get(OPS_LINE) or []
    modules = sorted(device.get(MODULES_LINE) or [], key=lambda e: e[1])
    source = ops or modules
    if not source:
        raise ValueError("the trace has no device plane: nothing ran on a "
                         "device inside the traced window")
    merged = _union([(e[1], e[1] + e[2]) for e in source])
    starts = [m[1] for m in modules]
    threads = {line: _innermost(spans)
               for line, spans in sorted(events["threads"].items())}
    piece_starts = {line: [p[0] for p in pieces]
                    for line, pieces in threads.items()}
    gaps = []
    edges = [[first, first]] + merged + [[last, last]]
    for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
        if s1 > e0:
            gaps.append((s1 - e0, e0, s1))
    gaps.sort(key=lambda g: -g[0])    # ties in time order, as trace_reduce
    idle = []
    for length, lo, hi in gaps[:TOP]:
        at = bisect.bisect_left(starts, hi)
        per_thread = {}
        for line, pieces in threads.items():
            inside = _overlaps(pieces, piece_starts[line], lo, hi)
            rest = length / 1e9 - sum(inside.values())
            if rest > 1e-9:
                inside[NO_SPAN] = rest
            per_thread[line] = dict(
                sorted(inside.items(), key=lambda kv: -kv[1]))
        idle.append({
            "seconds": length / 1e9, "at_s": (lo - first) / 1e9,
            "before": modules[at][0] if at < len(modules) else "end_of_trace",
            "threads": per_thread})
    by_scope = _self_time_by_scope(ops)
    total = sum(v[0] for v in by_scope.values())
    scoped = total - by_scope.get(NO_SCOPE, [0.0, 0])[0]
    thread_spans: Dict[str, Dict[str, list]] = {}
    for line, spans in sorted(events["threads"].items()):
        per = thread_spans.setdefault(line, {})
        for name, _start, dur, _seq in spans:
            slot = per.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += dur / 1e9
    return {
        "window_s": (last - first) / 1e9,
        "busy_s": sum(e - s for s, e in merged) / 1e9,
        "idle_gaps": idle,
        "scope_self_s": dict(sorted(by_scope.items(),
                                    key=lambda kv: -kv[1][0])),
        "scoped_pct": 100.0 * scoped / total if total > 0 else None,
        "thread_spans": thread_spans,
    }


def print_tables(reduced: dict, out=sys.stdout) -> None:
    busy, window = reduced["busy_s"], reduced["window_s"]
    print(f"device busy {busy:.3f} s of {window:.3f} s traced "
          f"(idle {100 * (1 - busy / window):.1f} %)", file=out)
    print(f"\n(a) the {len(reduced['idle_gaps'])} longest idle gaps, and "
          "where each thread was", file=out)
    for gap in reduced["idle_gaps"]:
        print(f"  {gap['seconds'] * 1e3:9.3f} ms at {gap['at_s']:8.3f} s, "
              f"before {gap['before']}", file=out)
        for line, inside in gap["threads"].items():
            parts = ", ".join(f"{label} {s * 1e3:.3f}"
                              for label, s in list(inside.items())[:4])
            print(f"      {line:24s} {parts}", file=out)
    print("\n(b) device self time by tb/ scope", file=out)
    total = sum(v[0] for v in reduced["scope_self_s"].values())
    for scope, (seconds, count) in reduced["scope_self_s"].items():
        share = 100 * seconds / total if total else 0.0
        print(f"  {scope:16s} {seconds * 1e3:10.1f} ms {share:5.1f} %  "
              f"{count} operations", file=out)
    if reduced["scoped_pct"] is not None:
        print(f"  under some tb/ scope: {reduced['scoped_pct']:.1f} % of "
              "busy self time", file=out)
    print("\nthread spans (count x mean ms)", file=out)
    for line, spans in reduced["thread_spans"].items():
        parts = ", ".join(f"{name} {n} x {s * 1e3 / n:.3f}"
                          for name, (n, s) in sorted(spans.items()))
        print(f"  {line:24s} {parts}", file=out)


def main(argv) -> int:
    if len(argv) not in (1, 2):
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    reduced = reduce(read_events(argv[0]))
    print_tables(reduced)
    if len(argv) == 2:                      # the tables as JSON too
        with open(argv[1], "w") as f:
            json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
