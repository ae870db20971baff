"""The nine per-layer readers of the request timeline: on recorded snapshots
(a traced `tiny-plain` CPU rehearsal's, trimmed to the series they read), a
number where the series exist and None where they do not, as in a program
without the spans; and the CPU rehearsal reporting all of them."""

import copy
import importlib
import json
import os

import pytest

from benchmarks.harness.drive import Sent

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "request_timeline_snapshots.json")
NEW_METRICS = (
    "server_request_ms", "unseen_by_server_ms", "admission_wait_ms",
    "pickup_arriving", "commit_host_ms", "results_wait_ms",
    "serving_thread_busy_pct", "lane_execute_ms", "lane_join_ms",
)
INTERVALS = ("ingress", "admission_wait", "commit_host", "results_wait",
             "barrier_wait", "reply_release")


def _window():
    # Four answered requests of 1.5, 2.5, 3.5, 4.5 s (mean 3 s) inside a
    # window of 10 s, and one that failed.
    sent = [Sent(s, 0, "create_transfers", 10, 100.0 + s, 101.5 + 2 * s, [])
            for s in range(4)]
    sent.append(Sent(4, 0, "create_transfers", 10, 100.0, 110.0, None,
                     "TimeoutError: no reply"))
    return sent


@pytest.fixture
def recorded():
    with open(FIXTURE) as f:
        return {"snapshots": json.load(f), "window": _window()}


def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


def test_readers_on_recorded_snapshots(recorded):
    got = {name: _read(name, recorded) for name in NEW_METRICS}
    opened, closed = (recorded["snapshots"][k] for k in ("open", "close"))

    def mean_ms(series):
        a, b = opened["histograms"][series], closed["histograms"][series]
        return (b["sum"] - a["sum"]) / (b["count"] - a["count"]) / 1e3

    assert got["server_request_ms"] == mean_ms("txtrace.request.total")
    assert got["admission_wait_ms"] == mean_ms(
        "txtrace.request.admission_wait")
    assert got["commit_host_ms"] == mean_ms("txtrace.request.commit_host")
    assert got["results_wait_ms"] == mean_ms("txtrace.request.results_wait")
    assert got["lane_execute_ms"] == mean_ms("txtrace.stage.device_execute")
    assert got["lane_join_ms"] == mean_ms("txtrace.stage.dispatch_wait")
    # By definition: what the client saw less what the server saw.
    assert got["unseen_by_server_ms"] == pytest.approx(
        3000.0 - got["server_request_ms"])
    assert got["pickup_arriving"] == 0.0

    def delta(counter):
        return closed["counters"][counter] - opened["counters"][counter]

    busy = delta("serve.busy_us")
    assert got["serving_thread_busy_pct"] == pytest.approx(
        100.0 * busy / 10e6)
    # The recorded run's six interval means sum to its total's mean.
    assert sum(mean_ms(f"txtrace.request.{name}") for name in INTERVALS
               ) == pytest.approx(got["server_request_ms"], abs=1e-9)


def test_readers_return_none_without_the_series(recorded):
    """A program that lacks the spans and counters (the parent of the PR
    that brought them): nothing to read, nothing raised."""
    bare = copy.deepcopy(recorded)
    for snap in bare["snapshots"].values():
        snap["counters"] = {"replica.commits": 1}
        snap["histograms"] = {}
    for name in NEW_METRICS:
        assert _read(name, bare) is None, name


def test_readers_return_none_on_an_empty_window(recorded):
    same = copy.deepcopy(recorded)
    same["snapshots"]["close"] = same["snapshots"]["open"]
    same["window"] = []
    for name in NEW_METRICS:
        assert _read(name, same) is None, name


@pytest.fixture(scope="module")
def plain_traced(cpu_cell):
    return cpu_cell("tiny-plain", 3000000017, 4, 1)


def test_cpu_rehearsal_reports_all_nine(plain_traced):
    rc, out, err = plain_traced
    assert rc == 0, err[-3000:]
    assert out["correct"] is True
    layer = out["per_layer"]
    for name in NEW_METRICS:
        assert isinstance(layer.get(name), float), (name, layer.get(name))
    assert layer["server_request_ms"] > 0
    assert layer["unseen_by_server_ms"] > -1.0
    assert 0 < layer["serving_thread_busy_pct"] <= 101
    # A mean of the window's requests cannot pass their longest.
    assert (layer["server_request_ms"] + layer["unseen_by_server_ms"]
            <= out["observations"]["batch_max_ms"])
