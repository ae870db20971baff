"""The reduction from a profiler trace to numbers: its arithmetic on
hand-made events, on a piece of a chip's own trace kept as a fixture, and
`read_events` on a trace recorded here (a CPU trace has no device plane)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness import trace_reduce  # noqa: E402

MS = 1_000_000


def _events():
    # Device 0: two programs; operations nest inside the first.
    dev0 = {
        "XLA Modules": [["jit_commit", 10 * MS, 30 * MS],
                        ["jit_merge", 60 * MS, 20 * MS],
                        ["jit_commit", 90 * MS, 5 * MS]],
        "XLA Ops": [["while.9", 10 * MS, 30 * MS],        # holds the next two
                    ["fusion.1", 10 * MS, 20 * MS],
                    ["scatter.2", 32 * MS, 8 * MS],
                    ["sort.3", 60 * MS, 20 * MS],
                    ["fusion.1", 90 * MS, 5 * MS]],
    }
    dev1 = {"XLA Modules": [["jit_commit", 0, 50 * MS]]}  # no operation line
    return {"span_ns": [0, 100 * MS],
            "devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}}


def test_busy_is_the_union_and_sums_are_per_name():
    r = trace_reduce.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    # device 0: [10,40] + [60,80] + [90,95] = 55 ms; device 1: 50 ms
    assert r["busy_s"] == pytest.approx((0.055 + 0.050) / 2)
    assert r["program_s"] == pytest.approx((0.055 + 0.050) / 2)
    assert r["programs"]["jit_commit"] == [pytest.approx(0.035), 2]
    assert r["programs"]["jit_merge"] == [pytest.approx(0.020), 1]
    # an operation's seconds are its self time, under its program's name
    assert r["device_ops"][0] == ["jit_commit:fusion.1", pytest.approx(0.025)]
    assert r["ops"]["jit_commit:scatter.2"] == [pytest.approx(0.008), 1]
    assert r["ops"]["jit_commit:while.9"] == [pytest.approx(0.002), 1]
    assert r["ops"]["jit_merge:sort.3"] == [pytest.approx(0.020), 1]
    assert sum(v[0] for v in r["ops"].values()) == pytest.approx(0.055)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["before:jit_merge", pytest.approx(0.020)]
    assert ["before:jit_commit", pytest.approx(0.010)] in gaps
    assert ["before:end_of_trace", pytest.approx(0.005)] in gaps
    assert sum(g[1] for g in gaps) + 0.055 == pytest.approx(0.100)


def test_names_are_cut_to_what_identifies_them():
    assert trace_reduce._short("jit__merge(16895332855397057666)") == (
        "jit__merge")
    assert trace_reduce._short(
        "%fusion.7 = (u32[8]{0}) fusion(%p), kind=kLoop") == "%fusion.7"


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce({"span_ns": [0, 1], "devices": {}})


def test_chip_fixture_reduces_to_what_was_read_by_hand():
    """A cut-down piece of the first chip trace of `default-plain-s8`
    (PR 24): the device plane's two lines over a short span."""
    with open(os.path.join(HERE, "fixtures", "chip_trace_events.json")) as f:
        fixture = json.load(f)
    r = trace_reduce.reduce(fixture["events"])
    for key, want in fixture["by_hand"].items():
        assert r[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]


def test_read_events_on_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    events = trace_reduce.read_events(trace_reduce.find_xplane(str(tmp_path)))
    first, last = events["span_ns"]
    assert last > first
    assert events["devices"] == {}      # a CPU has no device plane
