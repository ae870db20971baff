"""The six per-layer readers of the serving thread's split (PR 39): their
arithmetic on recorded snapshots (a traced run of `twophase-resolve-s8` on
the chip, trimmed to the series they and the two metrics they stand beside
read), None where a series is absent, as in a program without the self
times; and `tb.loop_wait` with its `role` in a profile recorded here, as
`host_spans.read_events` and `tools/trace_roles.py` read it."""

import copy
import glob
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import host_spans  # noqa: E402
from benchmarks.harness.drive import Sent  # noqa: E402
from tools import trace_roles  # noqa: E402

NEW_METRICS = ("serving_work_pct", "serving_unnamed_pct",
               "stage_ms_per_request", "enqueue_ms_per_request",
               "lane_closure_ms", "blocking_commit_ms")


@pytest.fixture
def recorded():
    """The fixture's snapshots and a window as long as the recorded run's."""
    with open(os.path.join(HERE, "fixtures",
                           "serving_split_snapshots.json")) as f:
        kept = json.load(f)
    seconds = kept["window_seconds"]
    window = [Sent(0, 0, "create_transfers", 10, 100.0, 100.0 + seconds, [])]
    return {"snapshots": {k: kept[k] for k in ("open", "close")},
            "window": window}


def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


def _deltas(recorded):
    opened, closed = (recorded["snapshots"][k] for k in ("open", "close"))

    def counter(name):
        return closed["counters"].get(name, 0) - opened["counters"].get(
            name, 0)

    def histogram(name):
        a, b = (s["histograms"].get(name, {"sum": 0, "count": 0})
                for s in (opened, closed))
        return b["sum"] - a["sum"], b["count"] - a["count"]

    return counter, histogram


SERVING = "txtrace.self_us.serving."


def test_the_three_states_sum_to_the_window(recorded):
    from benchmarks.layer_metrics import serving_work_pct

    counter, _ = _deltas(recorded)
    window_us = (recorded["window"][0].t_reply
                 - recorded["window"][0].t_send) * 1e6
    shares = serving_work_pct.shares(recorded)
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["socket_wait"] == pytest.approx(
        100.0 * counter(SERVING + "loop_wait") / window_us)
    # Device wait is self time of the three waits on role `serving`.
    assert shares["device_wait"] == pytest.approx(100.0 * sum(
        counter(SERVING + name)
        for name in ("dispatch_wait", "readback", "full_sync")) / window_us)
    assert _read("serving_work_pct", recorded) == shares["work"]
    # The chip's run: the thread slept on the device most of the window,
    # worked a share of it, and hardly waited for a socket.
    assert shares["device_wait"] > 50 > shares["work"] > 5
    assert 0 < shares["socket_wait"] < 5


def test_unnamed_is_what_no_leaf_span_of_the_serving_thread_names(recorded):
    from benchmarks.layer_metrics import serving_unnamed_pct

    counter, histogram = _deltas(recorded)
    window_us = (recorded["window"][0].t_reply
                 - recorded["window"][0].t_send) * 1e6
    got = _read("serving_unnamed_pct", recorded)
    # The holders are found in the run: the spans whose self time is not
    # their duration.  Every other name of the recorded run is a leaf.
    spans = {name.rsplit(".", 1)[1]
             for name in recorded["snapshots"]["close"]["counters"]
             if name.startswith("txtrace.self_us.")}
    holders = {"commit_group", "pipeline_flush", "device_execute",
               "general_commit"}
    opened, closed = (recorded["snapshots"][k] for k in ("open", "close"))
    assert serving_unnamed_pct.leaves(opened, closed) == spans - holders
    # Outside every section and the selector, less the spans that stand
    # there (`socket_read`), plus the own time of the holders.
    busy = sum(histogram("txtrace.stage." + name)[0] for name in (
        "ingress_verify", "commit_group", "pipeline_flush", "reply_release"))
    assert busy == pytest.approx(counter("serve.busy_us"), rel=1e-4)
    by_hand = (window_us - busy - counter(SERVING + "loop_wait")
               - histogram("txtrace.stage.socket_read")[0]
               + sum(counter(SERVING + name) for name in holders))
    assert got == pytest.approx(100.0 * by_hand / window_us, abs=0.01)
    assert 0.5 < got < 2.5
    # On that thread the self times sum to the top-level durations.
    selfs = sum(counter(name) for name in closed["counters"]
                if name.startswith(SERVING))
    top = (busy + histogram("txtrace.stage.loop_wait")[0]
           + histogram("txtrace.stage.socket_read")[0])
    assert selfs == pytest.approx(top, rel=1e-3)
    # A section that gains a child stops naming its own time by itself:
    # `reply_release` with a millisecond of its time under a new span.
    grown = copy.deepcopy(recorded)
    counters = grown["snapshots"]["close"]["counters"]
    counters[SERVING + "reply_release"] -= 1000
    counters[SERVING + "a_new_child"] = 1000
    grown["snapshots"]["close"]["histograms"]["txtrace.stage.a_new_child"] = {
        "count": 1, "sum": 1000.0, "unit": "us"}
    assert _read("serving_unnamed_pct", grown) == pytest.approx(
        got + 100.0 * (counter(SERVING + "reply_release") - 1000)
        / window_us, abs=1e-6)


def test_host_work_a_request_and_the_two_parts_of_lane_execute_ms(recorded):
    counter, histogram = _deltas(recorded)
    requests = histogram("txtrace.request.total")[1]
    assert requests == 248
    for metric, span in (("stage_ms_per_request", "stage_h2d"),
                         ("enqueue_ms_per_request", "dispatch")):
        us = sum(counter(f"txtrace.self_us.{role}.{span}")
                 for role in ("serving", "lane"))
        assert us > 0
        assert _read(metric, recorded) == pytest.approx(us / requests / 1e3)
        # Neither span holds another: self time is its duration.
        assert us == pytest.approx(
            histogram(f"txtrace.stage.{span}")[0], rel=1e-3)
    lane_us, lane_n = histogram("txtrace.stage.device_execute.lane")
    serving_us, serving_n = histogram("txtrace.stage.device_execute.serving")
    closure = _read("lane_closure_ms", recorded)
    blocking = _read("blocking_commit_ms", recorded)
    assert closure == pytest.approx(lane_us / lane_n / 1e3)
    assert blocking == pytest.approx(serving_us / serving_n / 1e3)
    # The split is of the same spans: their count-weighted mean is the
    # mixture `lane_execute_ms` reads (33 closures, 120 blocking commits).
    assert (lane_n, serving_n) == (33, 120)
    assert _read("lane_execute_ms", recorded) == pytest.approx(
        (closure * lane_n + blocking * serving_n) / (lane_n + serving_n),
        rel=1e-9)


def test_readers_return_none_without_the_series(recorded):
    """The parent of the PR that brought them (the request timeline's
    fixture is such a program's): nothing to read, nothing raised."""
    with open(os.path.join(HERE, "fixtures",
                           "request_timeline_snapshots.json")) as f:
        older = {"snapshots": json.load(f), "window": recorded["window"]}
    for name in NEW_METRICS:
        assert _read(name, older) is None, name
    no_window = dict(recorded, window=[])
    assert _read("serving_work_pct", no_window) is None
    assert _read("serving_unnamed_pct", no_window) is None
    for name, series in (
            ("lane_closure_ms", "txtrace.stage.device_execute.lane"),
            ("blocking_commit_ms", "txtrace.stage.device_execute.serving"),
            ("stage_ms_per_request", "txtrace.request.total")):
        cut = copy.deepcopy(recorded)
        for snap in cut["snapshots"].values():
            snap["histograms"].pop(series, None)
        assert _read(name, cut) is None, name
    # A cell with no blocking commit (both plain cells): the lane's closures
    # are all of `lane_execute_ms`, and the blocking part reads nothing.
    frozen = copy.deepcopy(recorded)
    frozen["snapshots"]["close"]["histograms"][
        "txtrace.stage.device_execute.serving"] = frozen["snapshots"]["open"][
        "histograms"]["txtrace.stage.device_execute.serving"]
    assert _read("blocking_commit_ms", frozen) is None
    assert _read("lane_closure_ms", frozen) is not None


def test_device_busy_under_a_threads_self_time():
    """`trace_roles` table (c) on events made by hand: a holder's self time
    leaves its child out, the device's busy time is split over the spans it
    falls under, and `(no span)` is the rest of the thread's own stretch,
    first span to last, not of the profile's (0 to 130 here)."""
    events = {
        "span_ns": [0.0, 130.0],
        "device": {"XLA Ops": [["fusion", 10.0, 20.0, "(none)"],
                               ["fusion", 50.0, 30.0, "(none)"]]},
        "threads": {"serving/python3#1": [
            ["commit_group", 0.0, 60.0, 1],        # holds the next one
            ["index_append", 20.0, 40.0, 1],       # busy 20-30 and 50-60
            ["loop_wait", 70.0, 20.0, 0]]},        # busy 70-80
    }
    (per,) = trace_roles.busy_under_spans(events).values()
    assert {k: [round(v * 1e9, 6) for v in pair]
            for k, pair in per.items()} == {
        "commit_group": [20.0, 10.0], "index_append": [40.0, 20.0],
        "loop_wait": [20.0, 10.0], "(no span)": [10.0, 10.0]}


def test_loop_wait_and_the_role_in_a_profile_recorded_here(tmp_path):
    """The selector's span lands on the loop thread's line with the other
    spans of that thread; every `tb.*` event carries its thread's role, a
    reference into the plane's stat names, which `trace_roles` follows."""
    import threading

    import jax

    from tigerbeetle_tpu.net.bus import ServingLoop
    from tigerbeetle_tpu.obs.txtrace import txtrace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    def lane():
        with txtrace.stage("device_execute", seq=5):
            with txtrace.stage("dispatch", seq=5):
                pass

    loop = ServingLoop()
    with txtrace.attribution_scope():
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with txtrace.stage("commit_group", seq=5):
                worker = threading.Thread(target=lane, name="tb-dispatch_0")
                worker.start()
                worker.join(60)
            loop.call_later(0.02, loop.stop)
            loop.run_forever()       # nothing ready: one real wait
        finally:
            jax.profiler.stop_trace()
            loop.close()
        totals = txtrace.stage_totals()
    assert totals["loop_wait"]["us"] >= 15_000
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    threads = host_spans.read_events(path)["threads"]
    by_spans = {tuple(sorted({s[0] for s in spans})): line
                for line, spans in threads.items()}
    assert set(by_spans) == {("commit_group", "loop_wait"),
                             ("device_execute", "dispatch")}
    waits = [s for s in threads[by_spans["commit_group", "loop_wait"]]
             if s[0] == "loop_wait"]
    assert sum(s[2] for s in waits) >= 15e6          # ns, the trace's clock
    roles = trace_roles.thread_roles(path)
    assert roles == {by_spans["commit_group", "loop_wait"]: "serving",
                     by_spans["device_execute", "dispatch"]: "lane"}
    renamed = trace_roles.read_events(path)["threads"]
    assert sorted(renamed) == sorted(
        f"{role}/{line}" for line, role in roles.items())
