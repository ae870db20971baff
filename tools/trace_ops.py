"""One program execution of a kept device trace, operation by operation.

    python3 tools/trace_ops.py <file.xplane.pb[.gz]> [<program> [<rows>]]

on the trace of a run kept with `benchmarks/run.py --trace 1 --artifacts
<dir>`.  Without <program>: the device's programs, with executions and
milliseconds.  With it (a substring of the program's name, say
`group_fast` or `transfers_full`): the execution of median length, its
operations' SELF time (a `while` does not count its body's) grouped by
`tb/` scope, primitive (the last part of the operation's `tf_op`, the HLO
`op_name`), category and result shape (`shape_with_layout`, layouts cut),
longest first, with the operation's `bytes_accessed`.  This is how PERF.md
section 5's per-operation tables are read (PR 32): a scatter over
`(u32[N], u32[N])` is the two-operand form a 64-bit scatter compiles to.

The file is read by `benchmarks/harness/host_spans.py`'s schema-less
protobuf walk (the operations' metadata is not in `jax.profiler`'s view).
"""

from __future__ import annotations

import collections
import gzip
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import host_spans as hs  # noqa: E402
from benchmarks.harness.trace_reduce import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE)


def device_rows(path: str) -> dict:
    """{line name: [(event name, start_ns, dur_ns, metadata stats)...]} of
    the first device plane's program and operation lines."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = memoryview(f.read())
    for number, plane_buf in hs._fields(data):
        if number != 1:
            continue
        plane = hs._plane(plane_buf)
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        meta_of = hs._meta_reader(plane)
        out = {}
        for line_buf in plane["lines"]:
            name, t0_ns, events = hs._line(line_buf)
            if name in (MODULES_LINE, OPS_LINE):
                out[name] = [
                    (*meta_of(meta), start, dur)
                    for meta, start, dur, _stats in (
                        hs._event(e, t0_ns) for e in events)
                ]
        return out
    raise ValueError("the trace has no device plane")


def self_times(ops: list) -> list:
    """[(operation, self_ns)]: its time without the operations inside it."""
    done, stack = [], []
    for op in sorted(ops, key=lambda o: (o[2], -o[3])):
        start, dur = op[2], op[3]
        while stack and stack[-1][0] <= start:
            done.append(tuple(stack.pop()[1:]))
        if stack:
            stack[-1][2] -= min(start + dur, stack[-1][0]) - start
        stack.append([start + dur, op, dur])
    done.extend(tuple(entry[1:]) for entry in stack)
    return done


def group_key(stats: dict) -> tuple:
    tf_op = str(stats.get("tf_op", ""))
    primitive = tf_op.rsplit("/", 1)[-1].split(":")[0] if tf_op else "?"
    shape = re.sub(r"\{[^}]*\}", "", str(stats.get("shape_with_layout", "")))
    return (hs._scope_of(stats), primitive,
            str(stats.get("hlo_category", "")), shape[:72])


def main(argv) -> int:
    if not 2 <= len(argv) <= 4:
        sys.stderr.write(__doc__)
        return 2
    rows = device_rows(argv[1])
    programs = collections.defaultdict(list)
    for name, _stats, start, dur in rows.get(MODULES_LINE, []):
        programs[hs._short(name)].append((dur, start))
    if len(argv) == 2:
        for name, runs in sorted(programs.items(),
                                 key=lambda kv: -sum(d for d, _ in kv[1])):
            print(f"{sum(d for d, _ in runs) / 1e6:10.3f} ms  x{len(runs):<5d}"
                  f" {name}")
        return 0
    runs = sorted(r for name, rs in programs.items() if argv[2] in name
                  for r in rs)
    if not runs:
        sys.stderr.write(f"no program named like {argv[2]!r}\n")
        return 1
    dur, start = runs[len(runs) // 2]
    print(f"{argv[2]}: {len(runs)} executions, {runs[0][0] / 1e6:.3f}-"
          f"{runs[-1][0] / 1e6:.3f} ms; the median one, {dur / 1e6:.3f} ms:")
    inside = [op for op in rows[OPS_LINE] if start <= op[2] < start + dur]
    groups = collections.defaultdict(lambda: [0, 0.0, set()])
    for (_name, stats, _start, _dur), self_ns in self_times(inside):
        group = groups[group_key(stats)]
        group[0] += 1
        group[1] += self_ns
        group[2].add(stats.get("bytes_accessed"))
    total = sum(g[1] for g in groups.values())
    top = int(argv[3]) if len(argv) == 4 else 40
    print(f"{'ms':>9s} {'%':>5s} {'runs':>6s} {'ms each':>9s}  scope, "
          "primitive, category, result shape, bytes_accessed")
    for key, (count, ns, nbytes) in sorted(
            groups.items(), key=lambda kv: -kv[1][1])[:top]:
        print(f"{ns / 1e6:9.3f} {100 * ns / total:5.1f} {count:6d} "
              f"{ns / count / 1e6:9.4f}  {key[0]:<12s} {key[1]:<22s} "
              f"{key[2]:<16s} {key[3]}  "
              f"{sorted(b for b in nbytes if b is not None)[:2]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
