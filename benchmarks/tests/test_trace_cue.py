"""The traced run's profiler window follows the measured window
(`harness/trace_cue.py`): the two decisions alone on sequences of (seconds,
answered); `place` against a stand-in server and real sessions' progress, on a
window that never reaches its cap (the clock's arm, as before) and on one
that is over before the profiler could open; and two CPU rehearsals through
`cpu_cell.py`: a capped window that ends long before `0.4 * seconds` still
reads every per-layer metric, and the untraced run hands `run_queues` no
progress hook."""

import json
import os
import threading
import time

import pytest

from benchmarks.harness import drive
from benchmarks.harness.trace_cue import TraceCue, WindowOver, place

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _walk(cue, pairs):
    """(opened, closed): the first pair at which each decision holds."""
    opened = closed = None
    for at_s, answered in pairs:
        if opened is None:
            if cue.opens(at_s, answered):
                opened = (at_s, answered)
        elif cue.closes(at_s, answered, opened[0]):
            closed = (at_s, answered)
            break
    return opened, closed


def _steady(rate, cap, step_s=0.01, until_s=60.0):
    """A window answering `rate` requests a second up to its cap."""
    n = int(until_s / step_s)
    return [(k * step_s, min(cap, int(k * step_s * rate))) for k in range(n)]


# The two cells' cap (8 sessions x 31) and length; requests a second: the
# control's 13.4 and the two-phase cell's 10.1 (ledger, PR 30), x2 and x4.
@pytest.mark.parametrize("rate,opened_s,closed_s,closed_answered", [
    (13.4, 124 / 13.4, 124 / 13.4 + 5.0, 191),      # the 5 s arm closes
    (10.1, 124 / 10.1, 124 / 10.1 + 5.0, 174),
    (26.8, 124 / 26.8, 240 / 26.8, 240),            # the last cycle closes
    (53.6, 124 / 53.6, 240 / 53.6, 240),
    (100.0, 1.24, 2.40, 240),
])
def test_a_capped_window_is_cued_by_its_answered_requests(
        rate, opened_s, closed_s, closed_answered):
    cue = TraceCue.for_window(cap=248, sessions=8, seconds=40.0)
    assert (cue.open_answered, cue.close_answered) == (124, 240)
    opened, closed = _walk(cue, _steady(rate, 248))
    assert opened == (pytest.approx(opened_s, abs=0.011), 124)
    assert closed[0] == pytest.approx(closed_s, abs=0.021)
    assert closed[1] == closed_answered
    # At any rate: after the first reply, before the last.
    assert 1 <= opened[1] < closed[1] < 248


def test_a_window_short_of_its_cap_is_cued_by_the_clock_as_before():
    cue = TraceCue.for_window(cap=10_000, sessions=8, seconds=40.0)
    opened, closed = _walk(cue, _steady(13.4, 10_000))
    assert opened[0] == pytest.approx(16.0, abs=0.011)
    assert closed[0] == pytest.approx(21.0, abs=0.021)
    short = TraceCue.for_window(cap=10_000, sessions=8, seconds=4.0)
    assert (short.open_after_s, short.keep_s) == (pytest.approx(1.6), 1.0)


def test_nothing_opens_before_the_first_reply():
    cue = TraceCue.for_window(cap=248, sessions=8, seconds=40.0)
    assert not cue.opens(39.0, 0)          # the clock's arm alone: no
    assert cue.opens(39.0, 1)
    opened, _closed = _walk(cue, [(0.0, 0), (17.0, 0), (18.0, 3), (19.0, 9)])
    assert opened == (18.0, 3)


class _Server:
    """Stands in for `harness.server.Server`: notes each cue and when."""

    def __init__(self):
        self.cues = []

    def cue(self, cmd, **_args):
        self.cues.append((cmd, time.monotonic()))
        return {"cmd": cmd}


class _Client:
    def __init__(self, reply_s):
        self.reply_s = reply_s

    def create_transfers(self, rows):
        time.sleep(self.reply_s)
        return []


def _placed_beside(queues, seconds, reply_s, cue):
    """`place` on its thread beside a real `run_queues` over slow clients."""
    server, progress, got = _Server(), drive.Progress(), {}

    def tracer():
        try:
            got["placed"] = place(server, progress, cue, "unused")
        except WindowOver as err:
            got["error"] = err

    thread = threading.Thread(target=tracer, daemon=True)
    thread.start()
    clients = [_Client(reply_s) for _ in queues]
    window = drive.run_queues(clients, queues, seconds=seconds,
                              progress=progress)
    thread.join(10.0)
    assert not thread.is_alive()
    return server, progress, got, window


def test_place_on_a_window_that_is_not_capped_opens_at_the_old_second():
    """Far from its cap the window is cued as it was before: opened at
    0.4 x seconds, kept min(5, 0.25 x seconds)."""
    seconds = 2.0
    queues = [[("create_transfers", [0])] * 1000 for _ in range(2)]
    cue = TraceCue.for_window(2000, 2, seconds)
    server, progress, got, window = _placed_beside(
        queues, seconds, 0.02, cue)
    assert [c for c, _t in server.cues] == ["trace_start", "trace_stop"]
    placed = got["placed"]
    assert placed["opened"] - progress.began == pytest.approx(0.8, abs=0.25)
    assert placed["closed"] - placed["started"] == pytest.approx(0.5, abs=0.25)
    first, last = placed["answered"]
    assert 1 <= first < last < len(window) < 2000
    assert placed["closed"] < max(r.t_reply for r in window)


def test_place_on_a_capped_window_lies_between_first_and_last_reply():
    queues = [[("create_transfers", [0])] * 10 for _ in range(4)]
    cue = TraceCue.for_window(40, 4, 40.0)
    server, _progress, got, window = _placed_beside(
        queues, 40.0, 0.02, cue)
    assert len(window) == 40
    placed = got["placed"]
    assert placed["answered"][0] >= 20 and placed["answered"][1] >= 36
    sent_at = dict(server.cues)
    assert min(r.t_reply for r in window) < sent_at["trace_start"]
    assert sent_at["trace_stop"] < max(r.t_reply for r in window)


def test_place_says_so_when_the_window_is_over_before_it_opens():
    """A session that stops at an error never reaches half the cap: the run
    fails by name, it does not trace an idle server."""
    class Broken(_Client):
        def create_transfers(self, rows):
            raise ConnectionError("gone")

    server, progress, got = _Server(), drive.Progress(), {}
    cue = TraceCue.for_window(8, 1, 40.0)
    thread = threading.Thread(target=lambda: got.update(
        error=pytest.raises(WindowOver, place, server, progress, cue, "")))
    thread.start()
    window = drive.run_queues([Broken(0)], [[("create_transfers", [0])] * 8],
                              seconds=40.0, progress=progress)
    thread.join(10.0)
    assert not thread.is_alive()
    assert len(window) == 1 and window[0].error
    assert "before the profiler opened" in str(got["error"].value)
    assert server.cues == []


# -- CPU rehearsals ---------------------------------------------------------------


@pytest.fixture(scope="module")
def capped_traced(cpu_cell):
    """24 requests at most, 40 s allowed: the window is over long before
    0.4 x 40 = 16 s."""
    return cpu_cell("tiny-plain", 3100000031, 40, 1)


def test_a_capped_window_that_ends_early_still_reads_every_layer(
        capped_traced, tiny_copy):
    rc, out, err = capped_traced
    assert rc == 0, err[-3000:]
    assert out["correct"] is True and out["attempted"] == 24
    seen = out["observations"]
    assert seen["window_hit_its_cap"] is True
    assert seen["window_ended_at_s"] < 0.4 * 40
    assert 0 < seen["trace_opened_at_s"] < seen["trace_closed_at_s"]
    assert seen["trace_closed_at_s"] < seen["window_ended_at_s"]
    first, last = seen["trace_requests_answered"]
    assert 12 <= first <= last < 24
    with open(os.path.join(tiny_copy, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if "tiny-plain" in m["workloads"]}
    # The general route's five read nothing in a plain cell on any platform;
    # a CPU's trace names no program, so the kernels' layer is not read here.
    silent = {n for n in listed if n.startswith("general_")} | {
        "commit_roofline", "kernel_ms_per_batch"}
    assert listed - silent <= set(out["per_layer"]), (
        listed - silent - set(out["per_layer"]))
    assert out["run_queues_progress"] == [False, False, True]


def test_a_trace_without_a_device_operation_fails_and_says_where_it_lay(
        cpu_cell):
    """A CPU's trace has no device plane: the run gives no result, and the
    failure names the profiler's place in the window."""
    rc, out, err = cpu_cell("tiny-plain", 23, 40, 1, "--no-device-plane")
    assert rc != 0 and out is None
    assert "the trace has no device plane" in err
    assert "the profiler's place in the window" in err
    for key in ("trace_opened_at_s", "trace_closed_at_s",
                "trace_requests_answered", "window_ended_at_s"):
        assert key in err, key


def test_the_untraced_run_hands_run_queues_no_progress(cpu_cell):
    rc, out, err = cpu_cell("tiny-plain", 7, 3, 0)
    assert rc == 0, err[-3000:]
    assert out["run_queues_progress"] == [False, False, False]
    assert "trace_opened_at_s" not in out["observations"]
    slowest = out["observations"]["batch_max_request"]
    assert slowest["operation"] == "create_transfers"
    assert 0 <= slowest["session"] < 4 and 0 <= slowest["index"] < 6
    assert slowest["sent_at_s"] >= 0
