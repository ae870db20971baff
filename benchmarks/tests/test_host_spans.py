"""`harness/host_spans.py`: its arithmetic on hand-made events, on a piece of a
chip's own trace kept as a fixture, and `read_events` (its own walk of the
protobuf) on a trace recorded here beside `jax.profiler.ProfileData`'s
reading of the same file."""

import glob
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness import host_spans, trace_reduce  # noqa: E402

MS = 1_000_000


def _events():
    # The device: a scan whose body's operations nest in its `while`, a
    # 20 ms gap, then an index merge.  Two threads: the serving thread in
    # a group's call (staging inside it), the lane in its closure.
    device = {
        "XLA Modules": [["jit_commit", 10 * MS, 30 * MS],
                        ["jit_merge", 60 * MS, 20 * MS]],
        "XLA Ops": [["%while.9", 10 * MS, 30 * MS, "(none)"],
                    ["%fusion.1", 10 * MS, 20 * MS, "insert"],
                    ["%scatter.2", 32 * MS, 8 * MS, "balance"],
                    ["%sort.3", 60 * MS, 20 * MS, "index_merge"]],
    }
    threads = {
        "python#3": [["commit_group", 35 * MS, 20 * MS, 7],
                     ["prepare", 36 * MS, 2 * MS, 7],
                     ["stage_h2d", 42 * MS, 10 * MS, 7],
                     ["reply_release", 58 * MS, 1 * MS, 6]],
        "python#5": [["device_execute", 55 * MS, 30 * MS, 7],
                     ["dispatch", 56 * MS, 3 * MS, 7]],
    }
    return {"span_ns": [0, 100 * MS], "device": device, "threads": threads}


def test_spans_are_cut_into_innermost_pieces():
    pieces = host_spans._innermost(_events()["threads"]["python#3"])
    assert pieces == [
        (35 * MS, 36 * MS, "commit_group"),
        (36 * MS, 38 * MS, "commit_group>prepare"),
        (38 * MS, 42 * MS, "commit_group"),
        (42 * MS, 52 * MS, "commit_group>stage_h2d"),
        (52 * MS, 55 * MS, "commit_group"),
        (58 * MS, 59 * MS, "reply_release"),
    ]


def test_gaps_name_where_every_thread_was():
    r = host_spans.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)
    gaps = r["idle_gaps"]
    # [40, 60], then the trace's edges: [80, 100] and [0, 10].
    assert [(g["seconds"], g["before"]) for g in gaps] == [
        (pytest.approx(0.020), "jit_merge"),
        (pytest.approx(0.020), "end_of_trace"),
        (pytest.approx(0.010), "jit_commit")]
    mid = next(g for g in gaps if g["before"] == "jit_merge")
    assert mid["at_s"] == pytest.approx(0.040)
    assert mid["threads"]["python#3"] == {
        "commit_group>stage_h2d": pytest.approx(0.010),
        "commit_group": pytest.approx(0.005),
        "(no span)": pytest.approx(0.004),
        "reply_release": pytest.approx(0.001)}
    assert list(mid["threads"]["python#3"])[0] == "commit_group>stage_h2d"
    assert mid["threads"]["python#5"] == {
        "(no span)": pytest.approx(0.015),
        "device_execute>dispatch": pytest.approx(0.003),
        "device_execute": pytest.approx(0.002)}
    # Every thread's pieces of a gap add up to the gap.
    for gap in gaps:
        for inside in gap["threads"].values():
            assert sum(inside.values()) == pytest.approx(gap["seconds"])
    assert r["thread_spans"]["python#5"] == {
        "device_execute": [1, pytest.approx(0.030)],
        "dispatch": [1, pytest.approx(0.003)]}


def test_device_self_time_by_scope():
    r = host_spans.reduce(_events())
    assert r["scope_self_s"] == {
        "insert": [pytest.approx(0.020), 1],
        "index_merge": [pytest.approx(0.020), 1],
        "balance": [pytest.approx(0.008), 1],
        "(none)": [pytest.approx(0.002), 1]}       # the while's own 2 ms
    assert list(r["scope_self_s"])[-1] == "(none)"
    assert r["scoped_pct"] == pytest.approx(96.0)


def test_an_operation_that_outlasts_its_holder_costs_it_only_the_overlap():
    # An asynchronous copy starts inside a fusion and ends after it.
    ops = [["%fusion.1", 0, 10 * MS, "insert"],
           ["%copy-start.2", 6 * MS, 8 * MS, "(none)"]]
    assert host_spans._self_time_by_scope(ops) == {
        "insert": [pytest.approx(0.006), 1],
        "(none)": [pytest.approx(0.008), 1]}


def test_a_program_without_spans_or_scopes_still_reduces():
    """The parent of the PR that brought them: gaps with no thread, one
    scope."""
    events = _events()
    events["threads"] = {}
    for op in events["device"]["XLA Ops"]:
        op[3] = host_spans.NO_SCOPE
    r = host_spans.reduce(events)
    assert all(g["threads"] == {} for g in r["idle_gaps"])
    assert list(r["scope_self_s"]) == ["(none)"] and r["scoped_pct"] == 0.0
    with pytest.raises(ValueError, match="no device plane"):
        host_spans.reduce({"span_ns": [0, 1], "device": {}, "threads": {}})


def test_chip_fixture_reduces_to_what_was_read_by_hand():
    """A cut-down piece of a chip trace of `default-plain-s8` (the fixture's
    `from`): the device plane's two lines and the three threads' spans over
    a short span that holds index appends and two long idle gaps."""
    with open(os.path.join(HERE, "fixtures",
                           "chip_host_spans_events.json")) as f:
        fixture = json.load(f)
    r = host_spans.reduce(fixture["events"])
    want = fixture["by_hand"]
    for key in ("busy_s", "window_s", "scoped_pct"):
        assert r[key] == pytest.approx(want[key], rel=1e-9), key
    longest = r["idle_gaps"][0]
    assert longest["seconds"] == pytest.approx(want["longest_gap_s"])
    assert len(longest["threads"]) == 3
    for line, inside in want["longest_gap_threads"].items():
        assert longest["threads"][line] == pytest.approx(inside)
    assert {k: v[0] for k, v in r["scope_self_s"].items()} == pytest.approx(
        {k: v[0] for k, v in want["scope_self_s"].items()})
    # The same gaps as the benchmark's own reduction names by the program.
    same = trace_reduce.reduce({
        "span_ns": fixture["events"]["span_ns"],
        "devices": {"/device:TPU:0": {
            line: [e[:3] for e in rows]
            for line, rows in fixture["events"]["device"].items()}}})
    assert [g["seconds"] for g in r["idle_gaps"]] == pytest.approx(
        [g[1] for g in same["idle_gaps"]])
    assert r["busy_s"] == pytest.approx(same["busy_s"])
    out = io.StringIO()
    host_spans.print_tables(r, out)
    assert "(a) the" in out.getvalue() and "(b) device" in out.getvalue()


def test_read_events_reads_the_spans_of_a_trace_recorded_here(tmp_path):
    """A CPU trace has no device plane; its host plane holds the spans, on
    their threads' lines, with their `seq`, as ProfileData reads them."""
    import threading

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from tigerbeetle_tpu.obs.txtrace import txtrace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    def lane():
        with txtrace.stage("device_execute", seq=5):
            with txtrace.stage("dispatch", seq=5, n=2):
                jnp.arange(8).sum().block_until_ready()

    with txtrace.attribution_scope():
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with txtrace.stage("commit_group", seq=5, n=2):
                with txtrace.stage("stage_h2d", seq=5):
                    worker = threading.Thread(target=lane)
                    worker.start()
                    worker.join(60)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = host_spans.read_events(path)
    assert events["device"] == {}
    by_spans = {tuple(sorted(s[0] for s in spans)): spans
                for spans in events["threads"].values()}
    assert set(by_spans) == {("commit_group", "stage_h2d"),
                             ("device_execute", "dispatch")}
    assert all(s[3] == 5 for spans in by_spans.values() for s in spans)
    want = {e.name: (e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("tb.")}
    for spans in by_spans.values():
        for name, start, dur, _seq in spans:
            assert (start, dur) == pytest.approx(want["tb." + name])
    first, last = events["span_ns"]
    assert first <= min(s[1] for v in by_spans.values() for s in v)
    assert last >= max(s[1] + s[2] for v in by_spans.values() for s in v)
