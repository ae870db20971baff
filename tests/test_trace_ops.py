"""tools/trace_ops.py: the arithmetic behind PERF.md's per-operation tables
(self time under a `while`, the grouping key) on synthetic rows, and the
whole tool on a trace file encoded here: the file walk is
`benchmarks/harness/host_spans.py`'s private readers, so a rename there has
to fail here."""

import gzip

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def trace_ops():
    spec = importlib.util.spec_from_file_location(
        "trace_ops", os.path.join(ROOT, "tools", "trace_ops.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_timeline_bills_each_gap_to_the_commit_program_before_it(
        tmp_path, capsys):
    """tools/trace_timeline.py: what the device ran behind each commit
    program, and the idle in short gaps (the device waiting for the host's
    next enqueue) apart from the long ones (no closure enqueued)."""
    ms = 1e6
    executions = [                                   # name, start, dur, trips
        ["jit_convert_element_type", 0, 0.002 * ms, 0],   # before any commit
        ["jit_create_transfers_fast_probed_impl", 1 * ms, 30 * ms, 21],
        ["jit_build_runs", 31.2 * ms, 2 * ms, 0],         # 0.2 ms: short
        ["jit__group_fast_dispatch_impl", 50 * ms, 100 * ms, 6],  # 16.8: long
        ["jit_dynamic_slice", 150.2 * ms, 0.002 * ms, 0],
        ["jit_build_runs", 150.5 * ms, 2 * ms, 0],
        ["jit__merge", 154.5 * ms, 4 * ms, 0],            # 2.0 ms: long
    ]
    timeline = _tool("trace_timeline")
    rows = timeline.timeline(list(reversed(executions)))  # any order in
    assert [(r["program"], r["trips"], r["behind"]) for r in rows] == [
        ("jit_create_transfers_fast_probed_impl", 21, 1),
        ("jit__group_fast_dispatch_impl", 6, 3)]
    lone, loop = rows
    assert lone["ms"] == 30 and lone["behind_ms"] == 2
    assert lone["short_gaps_ms"] == pytest.approx(0.2)
    assert lone["long_gaps_ms"] == pytest.approx(16.8)
    assert loop["behind_ms"] == pytest.approx(6.002)
    assert loop["short_gaps_ms"] == pytest.approx(0.2 + 0.298)
    assert loop["long_gaps_ms"] == pytest.approx(2.0)

    path = tmp_path / "run.json"
    path.write_text(json.dumps({"trace": {"executions": executions}}))
    assert timeline.main(["trace_timeline.py", str(path)]) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    assert last.startswith("2 commit programs, 7 requests, 4 programs behind")
    assert timeline.main(["trace_timeline.py"]) == 2


def test_a_while_does_not_count_its_body(trace_ops):
    ops = [
        ("while", {}, 0.0, 100.0),
        ("scatter.1", {}, 10.0, 30.0),
        ("gather.2", {}, 50.0, 20.0),
        ("after", {}, 100.0, 5.0),
    ]
    got = {op[0]: self_ns for op, self_ns in trace_ops.self_times(ops)}
    assert got == {"while": 50.0, "scatter.1": 30.0, "gather.2": 20.0,
                   "after": 5.0}
    assert sum(got.values()) == 105.0


@pytest.mark.parametrize("stats,want", [
    ({"tf_op": "jit(f)/while/body/tb/group_step/tb/insert/jit(insert)/scatter",
      "hlo_category": "custom fusion",
      "shape_with_layout":
          "(u32[8388608]{0:T(1024)}, u32[8388608]{0:T(1024)S(1)})"},
     ("insert", "scatter", "custom fusion", "(u32[8388608], u32[8388608])")),
    ({"tf_op": "jit(f)/tb/balance/scatter-add:",
      "shape_with_layout": "u32[16385,16]{1,0:T(8,128)S(1)}"},
     ("balance", "scatter-add", "", "u32[16385,16]")),
    ({}, ("(none)", "?", "", "")),
])
def test_group_key_is_scope_primitive_category_shape(trace_ops, stats, want):
    assert trace_ops.group_key(stats) == want


def test_usage_without_a_file(trace_ops, capsys):
    assert trace_ops.main(["trace_ops.py"]) == 2
    assert "xplane" in capsys.readouterr().err


# -- a device plane of xplane.proto, by field number --------------------------


def _varint(n):
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _msg(*fields):
    """(number, int | bytes | str)... -> an encoded message."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


STATS = {1: "tf_op", 2: "shape_with_layout", 3: "bytes_accessed",
         4: "hlo_category"}
# event metadata: id -> (name, tf_op, shape, bytes_accessed)
OPS = {
    1: ("while.7", "jit(group)/while", "()", 0),
    2: ("scatter.3", "jit(group)/while/body/tb/insert/scatter",
        "u32[8388608]{0:T(1024)}", 229376),
    3: ("fusion.9", "jit(group)/while/body/tb/probe/gather",
        "u64[8192]{0}", 65536),
    10: ("jit__group_fast_dispatch_impl(123)", None, None, None),
    11: ("jit_build_runs(5)", None, None, None),
}


def _xspace():
    def event(meta, offset_ns, dur_ns):
        return (4, _msg((1, meta), (2, offset_ns * 1000), (3, dur_ns * 1000)))

    def meta(key, name, tf_op, shape, nbytes):
        stats = [] if tf_op is None else [
            (5, _msg((1, 1), (5, tf_op))), (5, _msg((1, 2), (5, shape))),
            (5, _msg((1, 3), (3, nbytes))),
            (5, _msg((1, 4), (5, "data formatting")))]
        return (4, _msg((1, key), (2, _msg((1, key), (2, name), *stats))))

    modules = _msg((2, "XLA Modules"), (3, 1000), event(10, 0, 1000),
                   event(11, 1000, 50), event(10, 2000, 1200))
    # The first (median of two, the upper) execution: a while of 900 ns
    # holding two scatters and a gather; the second holds one scatter.
    ops = _msg((2, "XLA Ops"), (3, 1000), event(1, 2000, 900),
               event(2, 2100, 100), event(2, 2300, 100), event(3, 2500, 300),
               event(2, 100, 100))
    plane = _msg(
        (2, "/device:TPU:0"), (3, modules), (3, ops),
        *(meta(key, *row) for key, row in OPS.items()),
        *((5, _msg((1, key), (2, _msg((1, key), (2, name)))))
          for key, name in STATS.items()))
    host = _msg((2, "/host:CPU"), (3, _msg((2, "python"), (3, 0))))
    return _msg((1, host), (1, plane))


@pytest.mark.parametrize("suffix", [".xplane.pb", ".xplane.pb.gz"])
def test_the_tool_reads_a_trace_file_end_to_end(trace_ops, tmp_path, capsys,
                                                suffix):
    path = tmp_path / ("t" + suffix)
    data = _xspace()
    path.write_bytes(gzip.compress(data) if suffix.endswith(".gz") else data)

    assert trace_ops.main(["trace_ops.py", str(path)]) == 0
    programs = capsys.readouterr().out.splitlines()
    assert [line.split()[-1] for line in programs] == [
        "jit__group_fast_dispatch_impl", "jit_build_runs"]
    assert programs[0].split()[:3] == ["0.002", "ms", "x2"]

    assert trace_ops.main(["trace_ops.py", str(path), "group_fast"]) == 0
    out = capsys.readouterr().out
    assert "2 executions" in out and "the median one, 0.001 ms" in out
    rows = {tuple(line.split()[4:6]): line.split()
            for line in out.splitlines()[2:]}
    # ms, %, runs, ms each | scope, primitive | ... bytes_accessed
    scatter = rows[("insert", "scatter")]
    assert scatter[2] == "2" and scatter[-1] == "[229376]"
    assert "u32[8388608]" in scatter and float(scatter[1]) == pytest.approx(
        100 * 200 / 900, abs=0.06)
    assert rows[("probe", "gather")][2] == "1"
    assert float(rows[("(none)", "while")][1]) == pytest.approx(
        100 * 400 / 900, abs=0.06)            # 900 - 200 - 300 of self time

    assert trace_ops.main(["trace_ops.py", str(path), "no_such"]) == 1
