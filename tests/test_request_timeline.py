"""The request timeline and the commit-path spans (docs/tracing.md ATTRIBUTE).

Through the real bus (`net/bus.py`) with real clients over TCP: the six
`txtrace.request.*` intervals of every released request sum to its total,
the new counters move as a scripted sequence of arrivals says, everything
costs nothing when off, and nothing the instruments do changes a reply or
the ledger.  Through a `jax.profiler` session on the CPU: the `tb.*` thread
spans land in the profile's host plane, nested and ordered as the code runs
them.
"""

import asyncio
import glob
import hashlib
import socket
import threading
import time

import jax
import pytest

from test_pipeline import ReplicaHarness, _mixed_stream, accounts_batch, batch
from tigerbeetle_tpu.client import Client
from tigerbeetle_tpu.config import ClusterConfig, LedgerConfig
from tigerbeetle_tpu.net import bus as bus_mod
from tigerbeetle_tpu.net.bus import ReplicaServer
from tigerbeetle_tpu.obs import txtrace as txtrace_mod
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import (
    REQUEST_INTERVALS,
    SERVING_SECTIONS,
    txtrace,
)
from tigerbeetle_tpu.vsr import wire
from tigerbeetle_tpu.vsr.replica import Replica

CONFIG = ClusterConfig(message_size_max=8192, journal_slot_count=256)
LEDGER = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10, max_probe=1 << 10,
)
CLUSTER = 0xC7
SESSIONS = 4
ROUNDS = 3
REQUEST_SERIES = tuple(
    f"txtrace.request.{name}" for name in REQUEST_INTERVALS + ("total",)
)


class Served:
    """A replica behind a ReplicaServer on a loop thread of its own, with
    the server object in reach (run_server keeps it to itself)."""

    def __init__(self, tmp_path, name="served"):
        path = str(tmp_path / f"{name}.tb")
        Replica.format(path, cluster=CLUSTER, cluster_config=CONFIG)
        self.replica = Replica(path, cluster_config=CONFIG,
                               ledger_config=LEDGER, batch_lanes=64,
                               time_ns=lambda: 0)
        self.replica.open()
        self.replica.async_checkpoint = True  # as run_server does
        self.server = None
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        assert self._ready.wait(30)
        self.address = [("127.0.0.1", self.port)]

    def _run(self):
        async def main():
            self.server = ReplicaServer(self.replica, "127.0.0.1", 0)
            self.port = await self.server.start()
            self._loop = asyncio.get_running_loop()
            self._stop = asyncio.Event()
            self._ready.set()
            await self._stop.wait()
            await self.server.close()

        asyncio.run(main(), loop_factory=bus_mod.ServingLoop)  # run_server's

    def client(self, k: int) -> Client:
        return Client(self.address, cluster=CLUSTER, config=CONFIG,
                      timeout_s=30, client_id=0x500 + 2 * k + 1)

    def close(self):
        self._loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(30)
        assert not self._thread.is_alive()
        self.replica.close()


@pytest.fixture
def served(tmp_path):
    s = Served(tmp_path)
    yield s
    s.close()


def drive(served, sessions=SESSIONS, rounds=ROUNDS):
    """`sessions` clients, one request in flight each, `rounds` requests
    each, every round sent together.  Returns every reply's codes, by
    session then round."""
    clients = [served.client(k) for k in range(sessions)]
    assert clients[0].create_accounts(accounts_batch()) == []
    for c in clients[1:]:
        c.lookup_accounts([1])  # registers the session
    barrier = threading.Barrier(sessions)
    codes = [[None] * rounds for _ in range(sessions)]

    def session(s):
        for r in range(rounds):
            barrier.wait(30)
            codes[s][r] = clients[s].create_transfers(
                batch(100_000 * (s + 1) + 1000 * r, 20 + s)
            )

    threads = [threading.Thread(target=session, args=(s,), daemon=True)
               for s in range(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    for c in clients:
        c.close()
    return codes


# -- (a) the intervals sum to the request ------------------------------------


def test_request_intervals_sum_to_total_for_every_request(served,
                                                          monkeypatch):
    seen = []
    observe = txtrace.request_observe

    def spy(timeline, t_header, t_enqueued):
        seen.append((timeline, t_header, t_enqueued,
                     timeline.intervals(t_header, t_enqueued)))
        observe(timeline, t_header, t_enqueued)

    monkeypatch.setattr(txtrace, "request_observe", spy)
    with registry.enabled_scope():
        codes = drive(served)
        snap = registry.snapshot()
    assert all(c == [] for per in codes for c in per)
    # Accounts + one lookup per other session + the transfers.
    requests = 1 + (SESSIONS - 1) + SESSIONS * ROUNDS
    assert len(seen) >= requests  # >=: register requests are timed too
    for timeline, t_header, t_enqueued, values in seen:
        *six, total = values
        assert sum(six) == total == timeline.t_released - t_header
        assert all(v >= 0 for v in values), values
        assert (t_header <= t_enqueued <= timeline.t_pickup
                <= timeline.t_returned <= timeline.t_results
                <= timeline.t_released)
        assert timeline.t_durable <= timeline.t_released
    hists = snap["histograms"]
    assert {hists[name]["count"] for name in REQUEST_SERIES} == {len(seen)}
    assert sum(
        hists[name]["sum"] for name in REQUEST_SERIES[:-1]
    ) == hists["txtrace.request.total"]["sum"]
    # One meaning, one site: the stage series of that name is gone.
    assert "txtrace.stage.admission_wait" not in hists
    seqs = [timeline.seq for timeline, *_ in seen]
    assert seqs == sorted(seqs) and seqs[0] >= 1


# -- (b) off: no clock, no record, no observation -----------------------------


class CountingClock:
    """Stands in for the `time` module inside obs/txtrace.py: every clock
    the timeline and the spans read goes through here."""

    def __init__(self):
        self.calls = 0

    def __getattr__(self, name):
        self.calls += 1
        return getattr(time, name)


def test_everything_off_costs_no_clock_no_record_no_observation(
        served, monkeypatch):
    assert not txtrace.active and not registry.enabled
    clock = CountingClock()
    monkeypatch.setattr(txtrace_mod, "time", clock)
    monkeypatch.setattr(bus_mod, "time", clock)  # the bus's own reads
    made = []
    monkeypatch.setattr(
        txtrace_mod, "GroupTimeline",
        lambda seq: made.append(seq) or pytest.fail("a timeline record"))
    monkeypatch.setattr(
        txtrace_mod, "_StageSpan",
        lambda *a: made.append(a) or pytest.fail("a stage span"))
    txtrace.reset_stages()
    codes = drive(served)
    assert all(c == [] for per in codes for c in per)
    assert clock.calls == 0 and made == []
    assert txtrace.stage_totals() == {}
    assert served.server._arriving == 0
    snap = registry.snapshot()
    assert not any(
        name.startswith(("txtrace.", "serve.", "net.pickup", "ops.group"))
        for group in snap.values() for name in group
    )
    # Groups are numbered only while picked up active.
    assert served.server._group_seq == 0


# -- (b2) the serving thread's three states -----------------------------------


SOCKET_READ_LOST = (
    "no `socket_read` span for a readable socket: net/bus.py's ServingLoop "
    "overrides asyncio's PRIVATE BaseSelectorEventLoop._add_reader (CPython "
    "3.12); if this Python registers its readers another way, wrap that")


def _serving_states(snapshot):
    """(busy, socket wait, device wait, top-level spans outside the four
    sections) of the serving thread, in microseconds."""
    counters, histograms = snapshot["counters"], snapshot["histograms"]

    def self_us(*names):
        return sum(counters.get("txtrace.self_us.serving." + name, 0)
                   for name in names)

    return (counters.get("serve.busy_us", 0),
            self_us("loop_wait"),
            self_us("dispatch_wait", "readback", "full_sync"),
            histograms.get("txtrace.stage.socket_read", {"sum": 0})["sum"])


def test_the_loop_threads_time_is_sections_selector_and_socket_reads(served):
    """Every instant of the bus's loop thread is in a section, in the
    selector (`loop_wait`) or in a readable socket's callback: together
    within 10 % of the elapsed time under load, and an idle server's time
    is almost all `loop_wait`."""
    with registry.enabled_scope():
        before, t0 = registry.snapshot(), time.perf_counter()
        codes = drive(served, rounds=24)
        elapsed_us = (time.perf_counter() - t0) * 1e6
        loaded = registry.snapshot()
        assert all(c == [] for per in codes for c in per)
        busy, loop_wait, device_wait, reads = (
            b - a for a, b in zip(_serving_states(before),
                                  _serving_states(loaded)))
        assert busy > 0 and loop_wait > 0
        assert reads > 0, SOCKET_READ_LOST
        assert busy + loop_wait + reads == pytest.approx(elapsed_us, rel=0.10)
        assert 0 < device_wait <= busy
        # On one thread the self times sum to the top-level durations.
        selfs = sum(
            loaded["counters"][name] - before["counters"].get(name, 0)
            for name in loaded["counters"]
            if name.startswith("txtrace.self_us.serving."))
        assert selfs == pytest.approx(busy + loop_wait + reads, rel=0.005)

        t0 = time.perf_counter()
        time.sleep(0.3)             # nobody connected: the loop sleeps
        # A span is observed when it closes: end the selector's one wait.
        woken = threading.Event()
        served._loop.call_soon_threadsafe(woken.set)
        assert woken.wait(10)
        idle_us = (time.perf_counter() - t0) * 1e6
        idle = registry.snapshot()
        busy, loop_wait, device_wait, reads = (
            b - a for a, b in zip(_serving_states(loaded),
                                  _serving_states(idle)))
        # (the closing connections' last callbacks may still fall in here,
        # and the one wait began before the snapshot that opens this phase)
        assert loop_wait == pytest.approx(idle_us, rel=0.10)
        assert busy + reads <= 0.05 * idle_us and device_wait == 0


def test_off_the_timed_selector_reads_no_clock_and_opens_no_span(monkeypatch):
    """Off, `select` is the plain call (one branch) and a readable socket's
    callback the plain callback; on, a poll opens no span and a wait one."""
    assert not txtrace.active
    clock = CountingClock()
    monkeypatch.setattr(txtrace_mod, "time", clock)
    opened = []
    stage = txtrace.stage
    monkeypatch.setattr(
        txtrace, "stage", lambda name, **kw: opened.append(name) or stage(
            name, **kw))
    loop = bus_mod.ServingLoop()
    try:
        a, b = socket.socketpair()
        got = []
        loop.add_reader(a, lambda: got.append(a.recv(16)))
        b.send(b"x")
        loop.call_later(0.02, loop.stop)
        loop.run_forever()          # a read, then one real wait
        assert got == [b"x"] and opened == [] and clock.calls == 0
        with registry.enabled_scope():
            b.send(b"y")
            loop.call_soon(loop.stop)
            loop.run_forever()      # callbacks ready: polls only
            assert got == [b"x", b"y"]
            assert opened == ["socket_read"], SOCKET_READ_LOST
            loop.call_later(0.02, loop.stop)
            loop.run_forever()
            assert opened[1:] and set(opened[1:]) == {"loop_wait"}
            counters = registry.snapshot()["counters"]
            assert counters["txtrace.self_us.serving.loop_wait"] >= 15_000
            assert "serve.busy_us" not in counters
        loop.remove_reader(a)
        a.close()
        b.close()
    finally:
        loop.close()


# -- (c) served results are byte-identical on, traced and off ------------------


def _served_fingerprint(tmp_path, name):
    h = ReplicaHarness(str(tmp_path), name, depth=2, group=True)
    try:
        bodies, _batches, _kinds = _mixed_stream(h)
        digest = h.r.machine.digest()
    finally:
        h.close()
    return hashlib.sha256(b"\x00".join(bodies)).hexdigest(), digest


def test_results_byte_identical_on_traced_and_off(tmp_path):
    off = _served_fingerprint(tmp_path, "off")
    with registry.enabled_scope():
        on = _served_fingerprint(tmp_path, "on")
        assert registry.snapshot()["histograms"][
            "txtrace.stage.device_execute"]["count"] > 0
    with registry.enabled_scope():
        jax.profiler.start_trace(str(tmp_path / "profile"))
        try:
            traced = _served_fingerprint(tmp_path, "traced")
        finally:
            jax.profiler.stop_trace()
    assert off == on == traced


# -- (d) the spans in a profile ---------------------------------------------------


def _tb_events(profile_dir):
    """{line: [(name, start_ns, end_ns, stats)]} of the tb.* events,
    and the names of the planes that hold them and the executions."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    by_line, planes_tb, planes_exec = {}, set(), set()
    data = ProfileData.from_file(path)
    for plane in data.planes:
        # Lines are threads; unnamed threads share the process's name, so a
        # line is known by its place in the plane.
        for at, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("tb."):
                    planes_tb.add(plane.name)
                    by_line.setdefault(f"{line.name}#{at}", []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
                elif "XlaModule" in e.name or e.name.startswith(
                        ("PjitFunction", "TfrtCpuExecutable", "jit_")):
                    planes_exec.add(plane.name)
    return by_line, planes_tb, planes_exec


def test_profile_holds_nested_ordered_spans_on_their_threads(tmp_path):
    h = ReplicaHarness(str(tmp_path), "prof", depth=2, group=True)
    try:
        clients = [0x700 + i for i in range(3)]
        for c in clients:
            h.register(c)
        h.setup_accounts(clients[0])

        def group(request_n, first_id):
            return [h.request(c, request_n, wire.Operation.create_transfers,
                              batch(first_id + 1000 * k, 8).tobytes())
                    for k, c in enumerate(clients)]

        h.serve(group(2, 10_000))[1].result()  # warm: compiles stay out
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with registry.enabled_scope():
            jax.profiler.start_trace(str(tmp_path / "profile"),
                                     profiler_options=options)
            try:
                txtrace.group_seq = 41
                replies, fsync = h.serve(group(3, 20_000),
                                         deferred_replies=True)
                txtrace.group_seq = 42
                h.r.pipeline_flush()
                fsync.result()
                assert all(replies.result())
            finally:
                txtrace.group_seq = 0
                jax.profiler.stop_trace()
    finally:
        h.close()
    by_line, planes_tb, planes_exec = _tb_events(tmp_path / "profile")
    # One file, one clock: the spans sit in the host plane that also holds
    # the runtime's own execution events.
    assert planes_tb == {"/host:CPU"} and planes_tb <= planes_exec

    def only(line, name):
        (event,) = [e for e in by_line[line] if e[0] == name]
        return event

    # Three thread lines, told apart by the spans they carry (no OS thread
    # is named): the serving thread's, the lane's, the io pool's.
    def line_of(name):
        (line,) = [n for n, events in by_line.items()
                   if any(e[0] == name for e in events)]
        return line

    serving, lane, io = (line_of("tb.stage_h2d"),
                         line_of("tb.device_execute"),
                         line_of("tb.wal_fsync"))
    assert len({serving, lane, io}) == 3 == len(by_line)
    execute = only(lane, "tb.device_execute")
    # The closure's children, nested in it and in the order it runs them.
    children = [only(lane, f"tb.{c}")
                for c in ("grow", "dispatch", "index_append")]
    assert execute[1] <= children[0][1] and children[-1][2] <= execute[2]
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    # The serving thread: prepare, staging, journal, then (in the flush)
    # the join, the readback and phase B.
    order = [only(serving, f"tb.{s}") for s in (
        "prepare", "stage_h2d", "wal_write", "dispatch_wait", "readback",
        "phase_b")]
    assert all(a[2] <= b[1] for a, b in zip(order, order[1:]))
    # The closure is submitted after staging and joined before the readback.
    assert order[1][2] <= execute[1] and execute[2] <= order[4][1] + 1_000_000
    assert only(io, "tb.wal_fsync")[1] >= order[2][1]
    # Every span of the group carries ITS sequence number, also where it
    # runs on another thread or inside a later call.
    for line in by_line.values():
        for name, _s, _e, stats in line:
            assert stats["seq"] == 41, (name, stats)
    assert order[0][3]["n"] == 3
    # ... and its thread's role, so a line of the profile says who it is.
    for line, role in ((serving, "serving"), (lane, "lane"), (io, "io")):
        assert {e[3]["role"] for e in by_line[line]} == {role}


# -- (f) the counters follow a scripted sequence of arrivals -------------------


def _wait_for(predicate, seconds=20.0):
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


def test_counters_follow_scripted_arrivals(served):
    server = served.server
    with registry.enabled_scope():
        client = served.client(0)
        assert client.create_accounts(accounts_batch()) == []
        base = registry.snapshot()["counters"]
        assert base.get("pipeline.flush.idle", 0) == 0

        # A lone session: every transfer group stays pending until the
        # queue idles, so each costs exactly one idle flush.
        for r in range(3):
            assert client.create_transfers(batch(1000 * (r + 1), 10)) == []
        counters = registry.snapshot()["counters"]
        assert counters["pipeline.flush.idle"] == 3
        assert counters["pipeline.groups"] - base["pipeline.groups"] == 3
        # Lone requests ride the fast kernel, not the grouped scan.
        assert "ops.group.batches" not in counters

        # A frame whose header is in and whose body is not: the connection
        # counts as arriving at every pickup until the body comes.
        body = batch(9000, 4).tobytes()
        h = wire.new_header(
            wire.Command.request, cluster=CLUSTER, client=0x999, request=1,
            operation=int(wire.Operation.create_transfers),
        )
        h["size"] = wire.HEADER_SIZE + len(body)
        frame = wire.encode(wire.set_checksums(h, body), body)
        raw = socket.create_connection(served.address[0])
        raw.sendall(frame[:wire.HEADER_SIZE])
        assert _wait_for(lambda: server._arriving == 1)
        arriving = registry.histogram("net.pickup.arriving", "requests")
        seen0 = arriving.count
        assert client.create_transfers(batch(5000, 10)) == []
        assert arriving.count == seen0 + 1 and arriving.max == 1
        raw.sendall(frame[wire.HEADER_SIZE:])  # no session: an eviction
        assert _wait_for(lambda: server._arriving == 0)
        assert raw.recv(wire.HEADER_SIZE)
        # ... and a connection that ends inside a frame stops counting.
        raw.sendall(frame[:wire.HEADER_SIZE])
        assert _wait_for(lambda: server._arriving == 1)
        raw.close()
        assert _wait_for(lambda: server._arriving == 0)
        total0 = arriving.total
        assert client.create_transfers(batch(6000, 10)) == []
        assert arriving.total == total0  # observed 0 at that pickup
        client.close()

        # serve.busy_us is the sum of the serving thread's four sections.
        snap = registry.snapshot()
        sections = sum(
            snap["histograms"].get(f"txtrace.stage.{name}", {"sum": 0})["sum"]
            for name in SERVING_SECTIONS)
        assert snap["counters"]["serve.busy_us"] == sections > 0
        for name in ("commit_group", "reply_release", "ingress_verify",
                     "pipeline_flush"):
            assert snap["histograms"][f"txtrace.stage.{name}"]["count"] > 0


def test_group_scan_counter_counts_the_batches_held(tmp_path):
    h = ReplicaHarness(str(tmp_path), "scan", depth=2, group=True)
    try:
        clients = [0x800 + i for i in range(3)]
        for c in clients:
            h.register(c)
        h.setup_accounts(clients[0])
        with registry.enabled_scope():
            for n, (request_n, width) in enumerate(((2, 3), (3, 2), (4, 1))):
                reqs = [h.request(c, request_n,
                                  wire.Operation.create_transfers,
                                  batch(10_000 * (n + 1) + 100 * k,
                                        6).tobytes())
                        for k, c in enumerate(clients[:width])]
                h.serve(reqs)[1].result()
                counters = registry.snapshot()["counters"]
                # 3 for a group of 3, then 2 more for 2 more; a lone
                # request rides the fast kernel and does not move it.
                assert counters["ops.group.batches"] == (3, 5, 5)[n]
    finally:
        h.close()


# -- the annotation starts no backend -------------------------------------------


def test_active_stage_opens_its_annotation_without_starting_a_backend():
    """The client, the simulator and tbmc use txtrace with no backend."""
    import subprocess
    import sys

    code = (
        "from tigerbeetle_tpu import jaxenv\n"
        "from tigerbeetle_tpu.obs.txtrace import txtrace\n"
        "assert txtrace._trace_annotation is None\n"
        "with txtrace.stage('prepare'):\n"
        "    pass\n"
        "assert txtrace._trace_annotation is None  # off: nothing pulled\n"
        "with txtrace.attribution_scope():\n"
        "    with txtrace.stage('prepare', seq=7, n=2):\n"
        "        pass\n"
        "    assert txtrace.stage_totals()['prepare']['count'] == 1\n"
        "assert txtrace._trace_annotation is not None\n"
        "assert jaxenv.current_platform() is None\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
