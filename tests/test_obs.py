"""Observability stack: metrics registry, trace merge, VOPR visualization,
tracer thread-safety (tigerbeetle_tpu/obs/ + utils/tracer.py)."""

import gzip
import json
import os
import socket
import threading
import time

import pytest

from tigerbeetle_tpu.obs import profile as obs_profile
from tigerbeetle_tpu.obs import vopr_viz
from tigerbeetle_tpu.obs.metrics import HIST_BUCKETS, Histogram, Registry
from tigerbeetle_tpu.utils.statsd import StatsD
from tigerbeetle_tpu.utils.tracer import Tracer


# -- histogram ----------------------------------------------------------------

def test_histogram_bucket_layout_is_deterministic():
    h = Histogram("t", "us")
    for v in (0, 1, 2, 3, 4, 1023, 1024):
        h.observe(v)
    # bucket b holds values with bit_length b: 0->0, 1->1, {2,3}->2, 4->3,
    # 1023->10, 1024->11.
    assert h.buckets[0] == 1
    assert h.buckets[1] == 1
    assert h.buckets[2] == 2
    assert h.buckets[3] == 1
    assert h.buckets[10] == 1
    assert h.buckets[11] == 1
    assert h.count == 7 and h.min == 0 and h.max == 1024
    assert h.total == sum((0, 1, 2, 3, 4, 1023, 1024))


def test_histogram_percentiles_clamped_exact():
    h = Histogram("t")
    for _ in range(10):
        h.observe(7)
    # All samples share one value: every percentile is exactly it (bucket
    # midpoints clamp to [min, max]).
    assert h.percentile(50) == 7 and h.percentile(99) == 7
    assert h.percentile(100) == 7
    h2 = Histogram("t2")
    assert h2.percentile(50) is None  # empty


def test_histogram_huge_values_saturate_last_bucket():
    h = Histogram("t")
    h.observe(1 << 80)
    assert h.buckets[HIST_BUCKETS - 1] == 1
    assert h.max == 1 << 80


def test_histogram_snapshot_shape():
    h = Histogram("t", "ms")
    h.observe(100)
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["unit"] == "ms"
    assert snap["buckets"] == {"7": 1}
    assert snap["p50"] == 100  # midpoint of [64,127] is 95.5 -> clamps up


# -- registry -----------------------------------------------------------------

def test_registry_series_and_snapshot(tmp_path):
    reg = Registry(enabled=True)
    reg.counter("a.b").inc()
    reg.counter("a.b").inc(4)
    reg.gauge("g").set(2.5)
    reg.histogram("h", "us").observe(10)
    snap = reg.snapshot()
    assert snap["counters"] == {"a.b": 5}
    assert snap["gauges"] == {"g": 2.5}
    assert snap["histograms"]["h"]["count"] == 1
    path = str(tmp_path / "m.json")
    reg.dump(path)
    assert json.load(open(path)) == snap


def test_registry_handles_are_shared():
    reg = Registry(enabled=True)
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("y") is reg.histogram("y")


def test_registry_disabled_records_nothing_via_guarded_sites():
    """The instrumentation contract: call sites guard on registry.enabled,
    so a disabled registry's snapshot stays empty."""
    reg = Registry(enabled=False)
    # Mimic an instrumented site.
    if reg.enabled:
        reg.counter("never").inc()
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


def test_registry_statsd_bridge_deltas():
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.settimeout(2.0)
    port = recv.getsockname()[1]
    statsd = StatsD("127.0.0.1", port, prefix="tb")

    reg = Registry(enabled=True)
    reg.counter("reqs").inc(3)
    reg.gauge("depth").set(7)
    reg.histogram("lat_us").observe(100)
    reg.flush_statsd(statsd)
    got = {recv.recv(1024).decode() for _ in range(5)}
    assert "tb.reqs:3|c" in got
    assert any(s.startswith("tb.depth:7") and s.endswith("|g") for s in got)
    assert any(s.startswith("tb.lat_us.p50:") for s in got)
    # Second flush: counters emit DELTAS only (no change -> no sample).
    reg.counter("reqs").inc(2)
    reg.flush_statsd(statsd)
    got2 = set()
    try:
        for _ in range(5):
            got2.add(recv.recv(1024).decode())
    except socket.timeout:
        pass
    assert "tb.reqs:2|c" in got2
    assert not any(s.startswith("tb.reqs:5") for s in got2)
    statsd.close()
    recv.close()


# -- tracer thread-safety (satellite: start/stop race) ------------------------

def test_tracer_same_name_spans_across_threads_do_not_collide():
    t = Tracer("json")
    barrier = threading.Barrier(2)

    def worker(sleep_s):
        barrier.wait()
        t.start("checkpoint")
        time.sleep(sleep_s)
        t.stop("checkpoint")

    a = threading.Thread(target=worker, args=(0.01,))
    b = threading.Thread(target=worker, args=(0.05,))
    a.start(), b.start()
    a.join(), b.join()
    events = t.drain()
    assert len(events) == 2, "one thread's stop consumed the other's start"
    durs = sorted(e["dur"] for e in events)  # us
    assert durs[0] >= 8_000 and durs[1] >= 40_000, durs
    assert not t._open  # nothing leaked


def test_tracer_stop_without_start_is_noop():
    t = Tracer("json")
    t.stop("never_started")
    assert t.drain() == []


# -- profile merge ------------------------------------------------------------

def _host_event(name, ts, dur=10.0):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": 2, "args": {}}


def test_merge_rebases_device_onto_host_clock(tmp_path):
    out = str(tmp_path / "merged.json")
    host = [_host_event("commit", 5000.0)]
    device = [
        {"name": "xla_op", "ph": "X", "ts": 900.0, "dur": 3.0, "pid": 4},
        {"name": "process_name", "ph": "M", "pid": 4,
         "args": {"name": "device"}},
    ]
    stats = obs_profile.merge(host, device, out, host_t0_us=5000.0)
    assert stats["host_events"] == 1 and stats["device_events"] == 2
    merged = json.load(open(out))["traceEvents"]
    dev = next(e for e in merged if e["name"] == "xla_op")
    assert dev["ts"] == 5000.0  # min device ts rebased to capture start
    assert dev["pid"] == 4 + obs_profile.DEVICE_PID_BASE
    host_ev = next(e for e in merged if e["name"] == "commit")
    assert host_ev["ts"] == 5000.0 and host_ev["pid"] == 1


def test_merge_caps_device_events_longest_survive(tmp_path):
    out = str(tmp_path / "merged.json")
    device = [
        {"name": f"op{i}", "ph": "X", "ts": float(i), "dur": float(i),
         "pid": 1}
        for i in range(10)
    ]
    stats = obs_profile.merge([], device, out, host_t0_us=0.0,
                              device_events_max=3)
    assert stats["device_events_dropped"] == 7
    merged = json.load(open(out))["traceEvents"]
    names = [e["name"] for e in merged if e["name"] != "process_name"]
    assert names == ["op7", "op8", "op9"]  # longest, re-sorted by ts


def test_load_device_events_reads_gzipped_chrome_traces(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    payload = {"traceEvents": [{"name": "op", "ph": "X", "ts": 1.0}]}
    with gzip.open(str(d / "host.trace.json.gz"), "wt") as f:
        json.dump(payload, f)
    # A corrupt sibling must not break the load.
    (d / "bad.trace.json.gz").write_bytes(b"not gzip")
    events = obs_profile.load_device_events(str(tmp_path))
    assert events == payload["traceEvents"]


def test_device_capture_disabled_is_noop(tmp_path):
    with obs_profile.DeviceCapture(str(tmp_path / "p"), enabled=False) as c:
        pass
    assert c.events() == [] and c.host_t0_us is None


# -- vopr viz -----------------------------------------------------------------

class _FakeReplica:
    def __init__(self, status="normal", view=1, commit_min=3, op=4,
                 primary=False, suspect=False):
        self.status = status
        self.view = view
        self.commit_min = commit_min
        self.op = op
        self.is_primary = primary
        self._log_suspect = suspect


class _FakeCluster:
    def __init__(self):
        self.t = 0
        self.n = 2
        self.total = 3
        self.alive = [True, True, True]
        self.replicas = [
            _FakeReplica(primary=True),
            _FakeReplica(),
            _FakeReplica(),  # standby index
        ]


def test_viz_symbols():
    assert vopr_viz.status_symbol(None, False, False) == "x"
    assert vopr_viz.status_symbol(_FakeReplica(primary=True), True, False) == "*"
    assert vopr_viz.status_symbol(_FakeReplica(), True, False) == "."
    assert vopr_viz.status_symbol(
        _FakeReplica(status="view_change"), True, False
    ) == "v"
    assert vopr_viz.status_symbol(
        _FakeReplica(status="recovering"), True, False
    ) == "r"
    assert vopr_viz.status_symbol(_FakeReplica(suspect=True), True, False) == "!"
    assert vopr_viz.status_symbol(_FakeReplica(), True, True) == "s"


def test_viz_records_only_changes_and_renders():
    viz = vopr_viz.ClusterViz()
    cluster = _FakeCluster()
    viz.sample(cluster)
    cluster.t = 1
    viz.sample(cluster)  # no state change: no new line
    assert len(viz.lines) == 1
    cluster.t = 2
    cluster.replicas[0].commit_min = 5
    viz.sample(cluster)
    assert len(viz.lines) == 2
    text = viz.render()
    assert text.startswith("legend:")
    assert "r0" in text and "s2" in text
    assert "*1:5/4" in text


def test_viz_bounded_buffer_drops_oldest():
    viz = vopr_viz.ClusterViz(max_lines=2)
    cluster = _FakeCluster()
    for i in range(4):
        cluster.t = i
        cluster.replicas[0].commit_min = i  # force a change each tick
        viz.sample(cluster)
    assert len(viz.lines) == 2 and viz.dropped == 2
    assert "older lines dropped" in viz.render()


def test_run_seed_viz_smoke(tmp_path):
    """run_seed(viz=True) records a grid without disturbing the schedule:
    the result (exit/commits/faults) is bit-identical to a viz-less run."""
    from tigerbeetle_tpu.sim.vopr import run_seed

    bare = run_seed(3, workdir=str(tmp_path / "a"), ticks=300,
                    settle_ticks=20_000, viz=False)
    rich = run_seed(3, workdir=str(tmp_path / "b"), ticks=300,
                    settle_ticks=20_000, viz=True)
    assert bare.viz is None and rich.viz is not None
    assert (bare.exit_code, bare.commits, bare.faults, bare.ticks) == (
        rich.exit_code, rich.commits, rich.faults, rich.ticks
    )
    lines = rich.viz.splitlines()
    assert lines[0].startswith("legend:") and len(lines) > 3


# -- instrumented serving path (registry populated end to end) ----------------

def test_replica_commit_series_recorded(tmp_path):
    """A solo replica's request flow populates the commit-pipeline series
    when (and only when) the global registry is enabled."""
    import numpy as np

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import LEDGER_TEST, TEST_MIN
    from tigerbeetle_tpu.obs.metrics import registry
    from tigerbeetle_tpu.vsr import wire
    from tigerbeetle_tpu.vsr.replica import Replica

    def request(client, request_n, session, operation, body):
        h = wire.new_header(
            wire.Command.request, cluster=1, client=client,
            request=request_n, session=session, operation=int(operation),
        )
        return wire.decode(wire.encode(h, body))[0], body

    def drive(path):
        Replica.format(path, cluster=1, cluster_config=TEST_MIN)
        r = Replica(path, cluster_config=TEST_MIN,
                    ledger_config=LEDGER_TEST, batch_lanes=64)
        r.open()
        h, b = request(5, 0, 0, wire.Operation.register, b"")
        r.on_request(h, b)
        accounts = types.accounts_array(
            [types.account(id=i + 1, ledger=1, code=10) for i in range(4)]
        )
        h, b = request(5, 1, r.sessions[5].session,
                       wire.Operation.create_accounts, accounts.tobytes())
        r.on_request(h, b)
        r.close()

    registry.reset()
    registry.disable()
    drive(str(tmp_path / "off.tb"))
    snap = registry.snapshot()
    assert "replica.commit_us" not in snap["histograms"], (
        "disabled registry must record nothing"
    )

    registry.enable()
    try:
        drive(str(tmp_path / "on.tb"))
        snap = registry.snapshot()
        assert snap["counters"]["replica.commits"] >= 1
        assert snap["histograms"]["replica.commit_us"]["count"] >= 1
        assert snap["histograms"]["replica.prefetch_us"]["count"] >= 2
        assert snap["histograms"]["replica.batch_events"]["min"] == 4
        assert snap["histograms"]["ops.batch_fill_pct"]["count"] >= 1
    finally:
        registry.disable()
        registry.reset()
