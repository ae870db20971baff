"""What a traced profiler window holds WHOLE (`harness/commit_programs.py`,
`trace_reduce`'s `executions`): on hand-made events with a program cut at
each edge, and on a recorded chip trace's own edges."""

import importlib
import json
import os

import pytest

from benchmarks.harness import commit_programs, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000
FAST = "jit_create_transfers_fast_probed_impl"
GROUP = "jit__group_fast_dispatch_impl"
FULL = "jit_create_transfers_full_impl"


def _loop(at, trips, step=40):
    """A grouped dispatch's operations: one `while` holding `trips` runs of a
    three-operation body (one of them a loop of its own) and `trips + 1` of
    its condition, after one operation outside it."""
    ops = [["%copy.1", at, 1 * MS],
           ["%while.7", at + 1 * MS, (trips * step + 1) * MS]]
    for k in range(trips):
        t = at + (1 + k * step) * MS
        ops += [["%compare.2", t, MS // 10],
                ["%fusion.843", t + MS, 10 * MS],
                ["%while.3", t + 11 * MS, 20 * MS],      # a probe loop
                ["%fusion.9", t + 11 * MS, 5 * MS],      # ... its body, 3 x
                ["%fusion.9", t + 16 * MS, 5 * MS],
                ["%fusion.9", t + 21 * MS, 5 * MS],
                ["%scatter.4", t + 31 * MS, 8 * MS]]
    ops.append(["%compare.2", at + (1 + trips * step) * MS, MS // 10])
    return ops


def _events():
    """The profiler opened 30 ms before a general execution ended and closed
    60 ms into a grouped dispatch: between them a lone fast request, a group
    of 3, two general requests, each with its index program."""
    modules = [
        [FULL, 0, 30 * MS],                       # cut by the open
        ["jit_build_runs", 35 * MS, 10 * MS],     # ... its index: no request
        [FAST, 100 * MS, 50 * MS],
        ["jit_build_runs", 155 * MS, 10 * MS],
        [GROUP, 200 * MS, 125 * MS],
        ["jit_build_runs", 330 * MS, 30 * MS],
        [FULL, 400 * MS, 100 * MS],
        ["jit_build_runs", 505 * MS, 10 * MS],
        [FULL, 600 * MS, 110 * MS],
        ["jit_build_runs", 715 * MS, 10 * MS],
        ["jit__merge", 730 * MS, 20 * MS],
        [GROUP, 800 * MS, 60 * MS],               # cut by the close
    ]
    ops = _loop(200 * MS, 3) + [o for o in _loop(800 * MS, 7)
                                if o[1] + o[2] <= 860 * MS]
    ops += [["%fusion.1", s, d] for n, s, d in modules if GROUP not in n]
    return {"span_ns": [-5 * MS, 870 * MS],
            "devices": {"/device:TPU:0": {"XLA Modules": modules,
                                          "XLA Ops": ops}}}


def test_a_loops_trips_are_read_from_its_bodys_operations():
    reduced = trace_reduce.reduce(_events())
    trips = {(n, s // MS): t for n, s, _d, t in reduced["executions"]}
    assert trips[(GROUP, 200)] == 3
    assert trips[(FAST, 100)] == trips[(FULL, 400)] == 0
    assert reduced["device_span_ns"] == [0, 860 * MS]


def test_programs_cut_by_either_edge_leave_both_terms():
    reduced = trace_reduce.reduce(_events())
    whole = commit_programs.whole_requests(reduced)
    # From the lone fast request's start to the cut group's: 1 + 3 fast, 2
    # general requests, and every program begun in between.
    assert whole["fast"] == 4 and whole["general"] == 2
    assert whole["span_s"] == pytest.approx(0.700)
    assert whole["program_s"] == pytest.approx(
        (50 + 10 + 125 + 30 + 100 + 10 + 110 + 10 + 20) / 1e3)
    general = commit_programs.whole_executions(reduced, commit_programs.GENERAL)
    assert [e[2] // MS for e in general] == [100, 110]


def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


def test_the_readers_count_what_lies_whole_in_the_trace():
    from benchmarks.harness import bytes_model

    mix = {"batch": 8190, "resolve": {"post_pct": 80, "void_pct": 15}}
    run = {"trace": trace_reduce.reduce(_events()), "mix": mix,
           "peaks": {"hbm_bytes_per_s": 819e9}, "snapshots": {}}
    assert _read("kernel_ms_per_batch", run) == pytest.approx(465.0 / 6)
    assert _read("general_kernel_ms", run) == pytest.approx(105.0)
    least_s = (4 * 8190 * bytes_model.fast_lane_bytes()
               + 2 * 7780 * bytes_model.resolve_lane_bytes()) / 819e9
    assert _read("commit_roofline", run) == pytest.approx(
        100.0 * least_s / 0.465)
    assert _read("general_roofline", run) == pytest.approx(
        100.0 * 2 * 7780 * bytes_model.resolve_lane_bytes() / 819e9 / 0.210)


def test_nothing_is_read_from_a_trace_with_one_commit_program():
    events = _events()
    lines = events["devices"]["/device:TPU:0"]
    lines["XLA Modules"] = lines["XLA Modules"][:4]       # cut FULL, FAST
    lines["XLA Ops"] = [o for o in lines["XLA Ops"] if o[1] < 170 * MS]
    run = {"trace": trace_reduce.reduce(events), "mix": {"batch": 8190},
           "peaks": {"hbm_bytes_per_s": 819e9}, "snapshots": {}}
    assert commit_programs.whole_requests(run["trace"]) is None
    assert _read("kernel_ms_per_batch", run) is None
    assert _read("commit_roofline", run) is None


def test_a_group_whose_loop_the_trace_does_not_show_is_not_guessed():
    events = _events()
    events["devices"]["/device:TPU:0"].pop("XLA Ops")
    assert commit_programs.whole_requests(trace_reduce.reduce(events)) is None


# -- the chip's own edges --------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures",
                           "chip_cut_programs_events.json")) as f:
        return json.load(f)


def test_recorded_trace_whose_open_cut_a_program(recorded):
    piece = recorded["opened_on_a_program"]
    reduced = trace_reduce.reduce(piece["events"])
    by_hand = piece["by_hand"]
    name, start, dur = by_hand["cut_at_open"]
    # The cut program is the device's first event and shorter than any whole
    # one of its name.
    assert reduced["executions"][0][:3] == [name, start, dur]
    assert start == reduced["device_span_ns"][0]
    assert dur < min(e[2] for e in reduced["executions"][1:] if e[0] == name)
    groups = [e for e in reduced["executions"]
              if commit_programs.GROUPED in e[0]]
    assert [e[3] for e in groups] == by_hand["group_trips"]
    whole = commit_programs.whole_requests(reduced)
    lo, hi = by_hand["whole_span_ns"]
    assert whole["span_s"] == pytest.approx((hi - lo) / 1e9)
    assert (whole["fast"], whole["general"]) == (by_hand["requests"], 0)
    assert whole["program_s"] == pytest.approx(by_hand["program_ns"] / 1e9)
    run = {"trace": reduced, "mix": {"batch": 8190}, "peaks": None}
    assert _read("kernel_ms_per_batch", run) == pytest.approx(
        by_hand["program_ns"] / 1e6 / by_hand["requests"])


def test_recorded_trace_whose_close_cut_a_general_execution(recorded):
    piece = recorded["closed_on_a_program"]
    reduced = trace_reduce.reduce(piece["events"])
    by_hand = piece["by_hand"]
    cut = by_hand["cut_at_close"]
    assert reduced["executions"][-1][:3] == cut
    assert cut[1] + cut[2] == reduced["device_span_ns"][1]
    whole_ns = by_hand["general_whole_ns"]
    assert cut[2] < min(whole_ns) / 2
    run = {"trace": reduced, "peaks": None,
           "mix": {"batch": 8190, "resolve": {"post_pct": 80, "void_pct": 15}}}
    got = _read("general_kernel_ms", run)
    assert got == pytest.approx(sum(whole_ns) / len(whole_ns) / 1e6)
    assert min(whole_ns) / 1e6 <= got <= max(whole_ns) / 1e6
    # What the reader gave while it counted the cut one: below every whole one.
    assert by_hand["general_counting_the_cut_one_ms"] < min(whole_ns) / 1e6
    whole = commit_programs.whole_requests(reduced)
    assert {"fast": whole["fast"], "general": whole["general"]} == (
        by_hand["requests"])
    assert whole["program_s"] == pytest.approx(by_hand["program_ns"] / 1e9)
