"""Overload control: explicit backpressure, priority-aware admission,
flood-proof liveness (docs/fault_domains.md, overload domain).

Layers under test:

- wire: the retryable ``busy`` command and the eviction ``reason`` field
  (layout-pinned at the reference's absolute offsets);
- vsr/overload.py: command classification and the bounded AdmissionQueue
  (priority drain, per-client round-robin, shed order, FIFO negative mode);
- vsr/consensus.py: the primary's shed points reply busy (with reasons and
  retry hints) when overload control is on, and stay bit-identical silent
  drops when off;
- net/cluster_bus.py: class-aware send-queue thresholds + the
  bus.dropped_sends observability satellite;
- client.py: busy backoff (distinct from reconnect backoff) and
  capacity-eviction re-registration, both within the request deadline;
- vsr/replica.py: the clients_max LRU session eviction path (victim
  choice, reply-slot reuse);
- sim/vopr.py run_overload_seed: the pinned flood seed — priority
  scheduling on passes all oracles with a view change completing
  mid-flood; priority forced off demonstrably fails the liveness oracle
  (slow: the pass run commits a full flood's worth of requests).
"""

import random

import pytest

from tigerbeetle_tpu.vsr import overload, wire

CLUSTER = 0x0B5

# ---------------------------------------------------------------------------
# wire: busy command + eviction reason
# ---------------------------------------------------------------------------


class TestBusyWire:
    def test_busy_round_trip(self):
        h = wire.new_header(
            wire.Command.busy, cluster=CLUSTER, client=0xC1,
            request_checksum=0xABCDEF, request=9,
            retry_after_ticks=25, reason=wire.BUSY_WAL,
        )
        decoded, command, body = wire.decode(wire.encode(h))
        assert command == wire.Command.busy
        assert body == b""
        assert wire.u128(decoded, "request_checksum") == 0xABCDEF
        assert wire.u128(decoded, "client") == 0xC1
        assert int(decoded["request"]) == 9
        assert int(decoded["retry_after_ticks"]) == 25
        assert int(decoded["reason"]) == wire.BUSY_WAL

    def test_busy_field_offsets_pinned(self):
        """Absolute offsets are the wire contract (clients/typescript/src/
        wire.ts OFF_BUSY_*); a dtype reshuffle must fail loudly."""
        offs = {n: wire.BUSY_DTYPE.fields[n][1] for n in (
            "request_checksum_lo", "client_lo", "request",
            "retry_after_ticks", "reason",
        )}
        assert offs == {
            "request_checksum_lo": 128, "client_lo": 160,
            "request": 176, "retry_after_ticks": 180, "reason": 184,
        }

    def test_eviction_reason_offset_and_legacy_zero(self):
        assert wire.EVICTION_DTYPE.fields["reason"][1] == 144
        # Session echo (clients/typescript/src/wire.ts OFF_EVICT_SESSION,
        # native kOffEvictSession): which session the eviction is ABOUT.
        assert wire.EVICTION_DTYPE.fields["session"][1] == 145
        # A legacy frame (reason/session never set) decodes as zeros.
        h = wire.new_header(
            wire.Command.eviction, cluster=CLUSTER, client=0xC1
        )
        decoded, _ = wire.decode_header(wire.encode(h))
        assert int(decoded["reason"]) == 0
        assert int(decoded["session"]) == 0

    def test_busy_message_helper(self):
        req = wire.new_header(
            wire.Command.request, cluster=CLUSTER, client=0xC2,
            request=3, session=7,
            operation=int(wire.Operation.create_transfers),
        )
        req = wire.set_checksums(req, b"")
        msg = overload.busy_message(
            1, CLUSTER, 4, req, wire.BUSY_PIPELINE, 10
        )
        h, command, _ = wire.decode(msg)
        assert command == wire.Command.busy
        assert int(h["replica"]) == 1
        assert int(h["view"]) == 4
        assert wire.u128(h, "request_checksum") == (
            wire.header_checksum(req)
        )
        assert int(h["reason"]) == wire.BUSY_PIPELINE


# ---------------------------------------------------------------------------
# vsr/overload.py: classification + AdmissionQueue
# ---------------------------------------------------------------------------


class TestClassification:
    def test_every_command_classified(self):
        for command in wire.Command:
            cls = overload.classify(command)
            assert cls in overload.CLASS_NAMES

    def test_class_assignments(self):
        assert overload.classify(wire.Command.do_view_change) == (
            overload.CLASS_VIEW_CHANGE
        )
        assert overload.classify(wire.Command.ping) == (
            overload.CLASS_VIEW_CHANGE
        )
        assert overload.classify(wire.Command.request_prepare) == (
            overload.CLASS_REPAIR
        )
        assert overload.classify(wire.Command.sync_checkpoint) == (
            overload.CLASS_REPAIR
        )
        assert overload.classify(wire.Command.prepare) == (
            overload.CLASS_PREPARE
        )
        assert overload.classify(wire.Command.request) == (
            overload.CLASS_CLIENT
        )


class TestAdmissionQueue:
    def test_priority_drain_order(self):
        q = overload.AdmissionQueue(8)
        q.offer(overload.CLASS_CLIENT, 1, "c")
        q.offer(overload.CLASS_PREPARE, 0, "p")
        q.offer(overload.CLASS_REPAIR, 0, "r")
        q.offer(overload.CLASS_VIEW_CHANGE, 0, "v")
        assert [q.pop()[2] for _ in range(4)] == ["v", "r", "p", "c"]

    def test_client_round_robin(self):
        """One hot client cannot monopolize the drain: clients pop
        round-robin regardless of queue share."""
        q = overload.AdmissionQueue(16)
        for i in range(6):
            q.offer(overload.CLASS_CLIENT, 0xA, f"hot{i}")
        q.offer(overload.CLASS_CLIENT, 0xB, "cold0")
        q.offer(overload.CLASS_CLIENT, 0xC, "cold1")
        first_three = [q.pop() for _ in range(3)]
        assert {c for _, c, _ in first_three} == {0xA, 0xB, 0xC}

    def test_full_queue_evicts_lower_class_only(self):
        q = overload.AdmissionQueue(2)
        q.offer(overload.CLASS_CLIENT, 1, "c0")
        q.offer(overload.CLASS_CLIENT, 2, "c1")
        # Higher-priority arrival displaces a queued client...
        shed = q.offer(overload.CLASS_VIEW_CHANGE, 0, "svc")
        assert len(shed) == 1 and shed[0][0] == overload.CLASS_CLIENT
        # ...but a client arrival into a full queue with nothing lower
        # sheds itself.
        shed = q.offer(overload.CLASS_CLIENT, 3, "c2")
        assert shed == [(overload.CLASS_CLIENT, 3, "c2")]
        # And a view-change arrival never displaces another view-change.
        q2 = overload.AdmissionQueue(1)
        q2.offer(overload.CLASS_VIEW_CHANGE, 0, "v0")
        shed = q2.offer(overload.CLASS_VIEW_CHANGE, 0, "v1")
        assert shed == [(overload.CLASS_VIEW_CHANGE, 0, "v1")]

    def test_client_flood_cannot_lock_out_other_clients_at_admission(self):
        """Max-min fairness at ADMISSION, not just drain: a hot client
        that fills the queue pays for its own flood — a colder client's
        arrival displaces the flooder's tail.  Equal-share clients never
        churn each other out (the eviction requires the fattest backlog
        to exceed the arrival's own by more than one)."""
        q = overload.AdmissionQueue(8)
        for i in range(8):
            q.offer(overload.CLASS_CLIENT, 0xA, f"hot{i}")
        # Cold client B: the flooder's TAIL is shed, B is admitted.
        shed = q.offer(overload.CLASS_CLIENT, 0xB, "cold0")
        assert shed == [(overload.CLASS_CLIENT, 0xA, "hot7")]
        assert q.size == 8
        # The flooder itself cannot displace anyone (fattest is itself).
        shed = q.offer(overload.CLASS_CLIENT, 0xA, "hot8")
        assert shed == [(overload.CLASS_CLIENT, 0xA, "hot8")]
        # Near-equal shares: B (1 queued) vs A (7 queued) still displaces;
        # C arriving against A=6,B=2 displaces A, not B.
        shed = q.offer(overload.CLASS_CLIENT, 0xB, "cold1")
        assert shed == [(overload.CLASS_CLIENT, 0xA, "hot6")]
        shed = q.offer(overload.CLASS_CLIENT, 0xC, "new0")
        assert shed == [(overload.CLASS_CLIENT, 0xA, "hot5")]
        # Drain still round-robins across the admitted clients.
        first_three = [q.pop() for _ in range(3)]
        assert {c for _, c, _ in first_three} == {0xA, 0xB, 0xC}

    def test_fifo_mode_tail_drops_everything(self):
        q = overload.AdmissionQueue(2, priority=False)
        assert q.offer(overload.CLASS_CLIENT, 1, "a") == []
        assert q.offer(overload.CLASS_CLIENT, 1, "b") == []
        shed = q.offer(overload.CLASS_VIEW_CHANGE, 0, "svc")
        assert shed == [(overload.CLASS_VIEW_CHANGE, 0, "svc")]
        assert q.pop()[2] == "a"  # strict FIFO

    def test_bounded_at_cap(self):
        q = overload.AdmissionQueue(4)
        rng = random.Random(3)
        for i in range(200):
            cls = rng.choice(list(overload.CLASS_NAMES))
            q.offer(cls, rng.randrange(3), i)
            assert len(q) <= 4
            assert q.depth_peak <= 4
        drained = 0
        while q.pop() is not None:
            drained += 1
        assert drained <= 4


# ---------------------------------------------------------------------------
# consensus: the primary's shed points signal busy (gated)
# ---------------------------------------------------------------------------


def _primary_cluster(tmp_path, seed=5):
    """A converged 3-replica sim cluster; returns (cluster, primary)."""
    from tigerbeetle_tpu.sim.cluster import SimCluster

    cluster = SimCluster(
        str(tmp_path), n_replicas=3, n_clients=1, seed=seed,
        requests_per_client=2,
    )
    ok = cluster.run_until(
        lambda: cluster.clients_done() and cluster.converged(),
        max_ticks=20_000,
    )
    assert ok, "setup cluster failed to converge"
    primary = next(
        r for r, a in zip(cluster.replicas, cluster.alive)
        if a and r.is_primary
    )
    return cluster, primary


def _request_header(client=0xF00, request=1, session=1):
    h = wire.new_header(
        wire.Command.request, cluster=7, client=client,
        request=request, session=session,
        operation=int(wire.Operation.create_transfers),
    )
    return wire.set_checksums(h, b"")


class TestPrimaryShedSignals:
    def test_pipeline_full_sheds_busy_when_on(self, tmp_path):
        from tigerbeetle_tpu.obs.metrics import registry
        from tigerbeetle_tpu.vsr.consensus import PipelineEntry

        cluster, primary = _primary_cluster(tmp_path)
        cap = primary.config.pipeline_prepare_queue_max
        for k in range(cap):
            primary.pipeline[primary.op + 1 + k] = PipelineEntry(
                op=primary.op + 1 + k, checksum=k, client=0xD00 + k
            )
        # A register request reaches the shed checks without a session
        # (anything else would evict first); off -> silence, on -> busy.
        primary.overload_control = False
        out = primary.on_request_msg(
            wire.new_header(
                wire.Command.request, cluster=7, client=0xF00,
                request=0, session=0,
                operation=int(wire.Operation.register),
            ), b"",
        )
        # register lands in the (full) pipeline path too: off -> silence.
        assert out == []
        primary.overload_control = True
        with registry.enabled_scope():
            out = primary.on_request_msg(
                wire.new_header(
                    wire.Command.request, cluster=7, client=0xF00,
                    request=0, session=0,
                    operation=int(wire.Operation.register),
                ), b"",
            )
            counters = registry.snapshot()["counters"]
        assert len(out) == 1
        (kind, ident), message = out[0]
        assert (kind, ident) == ("client", 0xF00)
        bh, command, _ = wire.decode(message)
        assert command == wire.Command.busy
        assert int(bh["reason"]) == wire.BUSY_PIPELINE
        assert int(bh["retry_after_ticks"]) > 0
        # The shed accounting every sink reads, by name.
        assert counters["overload.shed.pipeline"] == 1
        assert counters["overload.busy_sent"] == 1

    def test_wal_full_sheds_busy_with_wal_reason(self, tmp_path):
        cluster, primary = _primary_cluster(tmp_path)
        primary.overload_control = True
        saved = primary.op_checkpoint
        try:
            # op_prepare_max derives from op_checkpoint: force the bound.
            primary.op_checkpoint = (
                primary.op - primary.config.journal_slot_count
            )
            out = primary.on_request_msg(
                wire.new_header(
                    wire.Command.request, cluster=7, client=0xF11,
                    request=0, session=0,
                    operation=int(wire.Operation.register),
                ), b"",
            )
            assert len(out) == 1
            bh, command, _ = wire.decode(out[0][1])
            assert command == wire.Command.busy
            assert int(bh["reason"]) == wire.BUSY_WAL
        finally:
            primary.op_checkpoint = saved

    def test_unsynchronized_clock_sheds_busy_clock(self, tmp_path):
        cluster, primary = _primary_cluster(tmp_path)
        primary.overload_control = True
        primary._init_clock()  # fresh clock: no Marzullo samples yet
        assert primary.clock.realtime_synchronized is None
        out = primary.on_request_msg(
            wire.new_header(
                wire.Command.request, cluster=7, client=0xF22,
                request=0, session=0,
                operation=int(wire.Operation.register),
            ), b"",
        )
        assert len(out) == 1
        bh, command, _ = wire.decode(out[0][1])
        assert command == wire.Command.busy
        assert int(bh["reason"]) == wire.BUSY_CLOCK

    def test_eviction_reasons_split(self, tmp_path):
        cluster, primary = _primary_cluster(tmp_path)
        # Unknown session -> no_session (retryable).
        out = primary.on_request_msg(
            _request_header(client=0xE01, request=1, session=99), b""
        )
        eh, command, _ = wire.decode(out[0][1])
        assert command == wire.Command.eviction
        assert int(eh["reason"]) == wire.EVICTION_NO_SESSION
        # Known session, wrong number -> session_mismatch (terminal).
        known = next(iter(primary.sessions.values()))
        out = primary.on_request_msg(
            _request_header(
                client=known.client, request=known.request + 1,
                session=known.session + 5,
            ), b"",
        )
        eh, command, _ = wire.decode(out[0][1])
        assert command == wire.Command.eviction
        assert int(eh["reason"]) == wire.EVICTION_SESSION_MISMATCH
        assert int(eh["session"]) == known.session + 5
        # Known session, STALE (lower) number -> mismatch TOO, but the
        # session echo lets the client tell "about my replaced session"
        # (discard: a pre-re-register duplicate must not poison the
        # recovered client) from "about my live session" (terminal).
        out = primary.on_request_msg(
            _request_header(
                client=known.client, request=known.request + 1,
                session=known.session - 1,
            ), b"",
        )
        eh, command, _ = wire.decode(out[0][1])
        assert command == wire.Command.eviction
        assert int(eh["reason"]) == wire.EVICTION_SESSION_MISMATCH
        assert int(eh["session"]) == known.session - 1


# ---------------------------------------------------------------------------
# cluster bus: class-aware send thresholds + dropped_sends observability
# ---------------------------------------------------------------------------


class TestBusClassShedding:
    def _server(self, buffer_size, overload_on):
        from tigerbeetle_tpu.net.cluster_bus import ClusterServer

        class FakeTransport:
            def __init__(self, n):
                self.n = n

            def get_write_buffer_size(self):
                return self.n

        class FakeWriter:
            def __init__(self, n):
                self.transport = FakeTransport(n)
                self.writes = []

            def write(self, data):
                self.writes.append(data)

        class FakeReplica:
            debugged = []

            def _debug(self, event, **kw):
                self.debugged.append((event, kw))

        server = ClusterServer.__new__(ClusterServer)
        w = FakeWriter(buffer_size)
        server.peer_writers = {1: w}
        server.client_writers = {}
        server.dropped_sends = 0
        server._last_drop_log = 0.0
        server._drop_logged = set()
        server.overload_control = overload_on
        server.replica = FakeReplica()
        return server, w

    @staticmethod
    def _msg(command, **fields):
        h = wire.new_header(command, cluster=CLUSTER, **fields)
        return wire.encode(h)

    def test_priority_classes_survive_client_sheds(self):
        import asyncio

        from tigerbeetle_tpu.net.cluster_bus import ClusterServer

        # Buffer sits between the client threshold (MAX/2) and the
        # replication threshold (MAX): client-class messages shed,
        # prepare/commit and view-change messages still send.
        size = ClusterServer.SEND_BUFFER_MAX - 1
        server, w = self._server(size, overload_on=True)
        envelopes = [
            (("replica", 1), self._msg(wire.Command.reply, client=1)),
            (("replica", 1), self._msg(wire.Command.commit)),
            (("replica", 1), self._msg(wire.Command.start_view_change)),
            (("replica", 1), self._msg(wire.Command.request_prepare)),
        ]
        asyncio.run(server._route(envelopes))
        # reply is CLASS_PREPARE (client-visible replication tail) — only
        # a request-class message sheds at MAX/2; craft one:
        asyncio.run(server._route([
            (("replica", 1), self._msg(wire.Command.request, client=2)),
        ]))
        assert server.dropped_sends == 1
        assert len(w.writes) == 4

    def test_view_change_reserve_beyond_base_threshold(self):
        import asyncio

        from tigerbeetle_tpu.net.cluster_bus import ClusterServer

        size = ClusterServer.SEND_BUFFER_MAX + 1
        server, w = self._server(size, overload_on=True)
        asyncio.run(server._route([
            (("replica", 1), self._msg(wire.Command.commit)),
            (("replica", 1), self._msg(wire.Command.do_view_change)),
            (("replica", 1), self._msg(wire.Command.request_prepare)),
        ]))
        # commit sheds at the base threshold; view-change + repair ride
        # the 2x reserve.
        assert server.dropped_sends == 1
        assert len(w.writes) == 2

    def test_overload_off_single_threshold_unchanged(self):
        import asyncio

        from tigerbeetle_tpu.net.cluster_bus import ClusterServer

        size = ClusterServer.SEND_BUFFER_MAX + 1
        server, w = self._server(size, overload_on=False)
        asyncio.run(server._route([
            (("replica", 1), self._msg(wire.Command.do_view_change)),
            (("replica", 1), self._msg(wire.Command.commit)),
        ]))
        assert server.dropped_sends == 2
        assert w.writes == []

    def test_first_drop_logged_once_per_connection(self):
        import asyncio

        from tigerbeetle_tpu.net.cluster_bus import ClusterServer

        size = ClusterServer.SEND_BUFFER_MAX + 1
        server, w = self._server(size, overload_on=False)
        asyncio.run(server._route(
            [(("replica", 1), self._msg(wire.Command.commit))] * 5
        ))
        first_drops = [
            e for e, _ in server.replica.debugged
            if e == "send_queue_drop_first"
        ]
        assert len(first_drops) == 1
        assert server.dropped_sends == 5

    def test_dropped_sends_metric_series(self):
        import asyncio

        from tigerbeetle_tpu.net.cluster_bus import ClusterServer
        from tigerbeetle_tpu.obs.metrics import registry

        size = ClusterServer.SEND_BUFFER_MAX + 1
        server, w = self._server(size, overload_on=True)
        registry.enable()
        try:
            before = registry.counter("bus.dropped_sends").value
            asyncio.run(server._route([
                (("replica", 1), self._msg(wire.Command.request, client=3)),
            ]))
            assert registry.counter("bus.dropped_sends").value == before + 1
            assert registry.counter("overload.drop.client").value >= 1
        finally:
            registry.disable()


# ---------------------------------------------------------------------------
# client: busy backoff + eviction re-registration (fake socket + fake clock)
# ---------------------------------------------------------------------------


class FakeServerSocket:
    """A scripted in-memory socket: each sendall() runs the script against
    the decoded request and queues the scripted response bytes for recv."""

    def __init__(self, script):
        self.script = script  # (h, command, body) -> [response bytes]
        self.buf = b""
        self.pending = b""

    # socket interface the client touches
    def setsockopt(self, *a):
        pass

    def settimeout(self, *a):
        pass

    def close(self):
        pass

    def sendall(self, data):
        self.pending += data
        while len(self.pending) >= wire.HEADER_SIZE:
            h, command = wire.decode_header(
                self.pending[: wire.HEADER_SIZE]
            )
            size = int(h["size"])
            if len(self.pending) < size:
                return
            body = self.pending[wire.HEADER_SIZE : size]
            self.pending = self.pending[size:]
            for response in self.script(h, command, body):
                self.buf += response

    def recv(self, n):
        if not self.buf:
            raise ConnectionError("script produced no response")
        chunk, self.buf = self.buf[:n], self.buf[n:]
        return chunk


def _fake_clock_client(monkeypatch, script, timeout_s=30.0):
    import tigerbeetle_tpu.client as client_mod

    sock = FakeServerSocket(script)
    monkeypatch.setattr(
        client_mod.socket, "create_connection",
        lambda addr, timeout=None: sock,
    )
    c = client_mod.Client(
        [("127.0.0.1", 1)], cluster=CLUSTER, client_id=0xC11E47,
        timeout_s=timeout_s,
    )
    clock = {"t": 0.0}
    sleeps = []

    def fake_sleep(s):
        sleeps.append(s)
        clock["t"] += s

    c._sleep = fake_sleep
    c._now = lambda: clock["t"]
    return c, clock, sleeps


class _ScriptServer:
    """Minimal session server for the fake-socket tests."""

    def __init__(self, evict_reason=None, busy_first=0,
                 busy_hint_ticks=20, stale_mismatch_once=False):
        self.sessions = {}
        self.next_session = 5
        self.evict_reason = evict_reason   # evict first non-register once
        self.evicted_once = False
        # Prepend ONE stale MISMATCH (echoing live session - 1) to the
        # first non-register reply: the race where a backup's forward of a
        # pre-re-register request lands just before the real reply.
        self.stale_mismatch_once = stale_mismatch_once
        self.busy_first = busy_first       # busy-reply the first N sends
        self.busy_hint_ticks = busy_hint_ticks
        self.busy_sent = 0
        self.requests_served = 0

    def __call__(self, h, command, body):
        request_checksum = wire.header_checksum(h)
        client = wire.u128(h, "client")
        op = wire.Operation(int(h["operation"]))
        if self.busy_sent < self.busy_first:
            self.busy_sent += 1
            busy = wire.new_header(
                wire.Command.busy, cluster=CLUSTER, client=client,
                request_checksum=request_checksum,
                request=int(h["request"]),
                retry_after_ticks=self.busy_hint_ticks,
                reason=wire.BUSY_PIPELINE,
            )
            return [wire.encode(busy)]
        if op == wire.Operation.register:
            self.next_session += 1
            self.sessions[client] = self.next_session
            reply = wire.new_header(
                wire.Command.reply, cluster=CLUSTER, client=client,
                request_checksum=request_checksum,
                op=self.next_session, request=0,
            )
            return [wire.encode(reply)]
        if self.evict_reason is not None and not self.evicted_once:
            self.evicted_once = True
            ev = wire.new_header(
                wire.Command.eviction, cluster=CLUSTER, client=client,
                reason=self.evict_reason,
            )
            return [wire.encode(ev)]
        self.requests_served += 1
        reply = wire.new_header(
            wire.Command.reply, cluster=CLUSTER, client=client,
            request_checksum=request_checksum,
            op=100 + self.requests_served, request=int(h["request"]),
        )
        out = [wire.encode(reply, b"")]
        if self.stale_mismatch_once:
            self.stale_mismatch_once = False
            stale = wire.new_header(
                wire.Command.eviction, cluster=CLUSTER, client=client,
                reason=wire.EVICTION_SESSION_MISMATCH,
                session=self.sessions[client] - 1,
            )
            out.insert(0, wire.encode(stale))
        return out


class TestClientBusyBackoff:
    def test_busy_backs_off_and_retries_to_success(self, monkeypatch):
        server = _ScriptServer(busy_first=3, busy_hint_ticks=20)
        c, clock, sleeps = _fake_clock_client(monkeypatch, server)
        c.request(wire.Operation.create_transfers, b"")
        assert c.busy_count == 3
        assert server.requests_served == 1
        # Every busy wait honors at least the server hint (20 consensus
        # ticks at HINT_TICK_S each — the server's unit, not the client's
        # 50 ms backoff tick).
        assert len(sleeps) >= 3
        assert all(s >= 20 * c.HINT_TICK_S - 1e-9 for s in sleeps[:3])
        # Distinct from the reconnect schedule: no failover happened.
        assert c.failover_count == 0

    def test_busy_honors_deadline(self, monkeypatch):
        server = _ScriptServer(busy_first=10_000, busy_hint_ticks=200)
        c, clock, sleeps = _fake_clock_client(
            monkeypatch, server, timeout_s=30.0
        )
        with pytest.raises(TimeoutError):
            c.request(wire.Operation.create_transfers, b"")
        assert clock["t"] <= 30.0 + 200 * c.RETRY_TICK_S  # bounded overrun
        assert c.busy_count > 1

    def test_busy_backoff_resets_on_progress(self, monkeypatch):
        server = _ScriptServer(busy_first=2, busy_hint_ticks=1)
        c, clock, sleeps = _fake_clock_client(monkeypatch, server)
        c.request(wire.Operation.create_transfers, b"")
        assert c._busy_backoff.attempts == 0  # reset by the reply


class TestClientEvictionReRegister:
    def test_capacity_eviction_reregisters_within_deadline(
        self, monkeypatch
    ):
        server = _ScriptServer(evict_reason=wire.EVICTION_NO_SESSION)
        c, clock, sleeps = _fake_clock_client(monkeypatch, server)
        first_session_holder = {}
        c.register()
        first_session_holder["s"] = c.session
        out = c.request(wire.Operation.create_transfers, b"")
        assert out == b""
        # A FRESH session was registered (two registers served).
        assert c.session != first_session_holder["s"]
        assert server.requests_served == 1
        assert clock["t"] <= c.timeout_s

    def test_session_mismatch_is_terminal(self, monkeypatch):
        # Legacy frame: session echo 0 (not session-specific) — terminal.
        from tigerbeetle_tpu.client import ClientEvicted

        server = _ScriptServer(
            evict_reason=wire.EVICTION_SESSION_MISMATCH
        )
        c, clock, sleeps = _fake_clock_client(monkeypatch, server)
        with pytest.raises(ClientEvicted) as err:
            c.request(wire.Operation.create_transfers, b"")
        assert err.value.reason == wire.EVICTION_SESSION_MISMATCH

    def test_stale_mismatch_about_replaced_session_is_discarded(
        self, monkeypatch
    ):
        """A MISMATCH echoing a session OTHER than the live one (the
        stale forward of a pre-re-register request) is discarded by the
        client, which keeps reading and takes the real reply — it
        neither dies nor re-registers."""
        server = _ScriptServer(stale_mismatch_once=True)
        c, clock, sleeps = _fake_clock_client(monkeypatch, server)
        c.register()
        live = c.session
        out = c.request(wire.Operation.create_transfers, b"")
        assert out == b""
        assert c.session == live          # no re-register happened
        assert server.requests_served == 1


# ---------------------------------------------------------------------------
# replica: clients_max LRU session eviction (satellite coverage)
# ---------------------------------------------------------------------------


class TestClientsMaxEviction:
    def _solo(self, tmp_path, clients_max=3):
        import dataclasses

        from tigerbeetle_tpu.config import LEDGER_TEST, TEST_MIN
        from tigerbeetle_tpu.vsr.replica import Replica

        config = dataclasses.replace(TEST_MIN, clients_max=clients_max)
        path = str(tmp_path / "evict.tb")
        Replica.format(path, cluster=CLUSTER, cluster_config=config)
        replica = Replica(
            path, cluster_config=config, ledger_config=LEDGER_TEST,
            batch_lanes=64,
        )
        replica.open()
        return replica

    @staticmethod
    def _register(replica, client):
        h = wire.new_header(
            wire.Command.request, cluster=CLUSTER, client=client,
            request=0, session=0,
            operation=int(wire.Operation.register),
        )
        h = wire.set_checksums(h, b"")
        out = replica.on_request(h, b"")
        assert len(out) == 1
        rh, command = wire.decode_header(out[0])
        assert command == wire.Command.reply
        return int(rh["op"])  # the session number

    def test_lru_victim_and_slot_reuse(self, tmp_path):
        replica = self._solo(tmp_path, clients_max=3)
        try:
            sessions = {}
            for client in (0xA1, 0xA2, 0xA3):
                sessions[client] = self._register(replica, client)
            slots_before = {
                c: s.slot for c, s in replica.sessions.items()
            }
            assert len(replica.sessions) == 3
            # A fourth register evicts the LOWEST session number (0xA1,
            # the oldest register commit) and reuses its reply slot.
            self._register(replica, 0xA4)
            assert 0xA1 not in replica.sessions
            assert set(replica.sessions) == {0xA2, 0xA3, 0xA4}
            assert replica.sessions[0xA4].slot == slots_before[0xA1]
            # Slots stay within [0, clients_max).
            assert all(
                0 <= s.slot < 3 for s in replica.sessions.values()
            )
        finally:
            replica.close()

    def test_evicted_client_gets_no_session_reason(self, tmp_path):
        replica = self._solo(tmp_path, clients_max=2)
        try:
            s1 = self._register(replica, 0xB1)
            self._register(replica, 0xB2)
            self._register(replica, 0xB3)  # evicts 0xB1
            h = wire.new_header(
                wire.Command.request, cluster=CLUSTER, client=0xB1,
                request=1, session=s1,
                operation=int(wire.Operation.create_transfers),
            )
            h = wire.set_checksums(h, b"")
            out = replica.on_request(h, b"")
            eh, command = wire.decode_header(out[0])
            assert command == wire.Command.eviction
            assert int(eh["reason"]) == wire.EVICTION_NO_SESSION
            # Re-registering works and serves the retried request.
            self._register(replica, 0xB1)
            session = replica.sessions[0xB1]
            h = wire.new_header(
                wire.Command.request, cluster=CLUSTER, client=0xB1,
                request=1, session=session.session,
                operation=int(wire.Operation.create_transfers),
            )
            h = wire.set_checksums(h, b"")
            out = replica.on_request(h, b"")
            rh, command = wire.decode_header(out[0])
            assert command == wire.Command.reply
        finally:
            replica.close()

    def test_session_mismatch_echoes_offending_session(self, tmp_path):
        """Any wrong session number gets a MISMATCH eviction that ECHOES
        the offending session, so the CLIENT discriminates: a stale frame
        about a session it already replaced is discarded client-side,
        while a live duplicate-id client (echo == its session) surfaces
        the violation terminally — no silent-drop timeout hang either
        way."""
        replica = self._solo(tmp_path, clients_max=2)
        try:
            session = self._register(replica, 0xB1)
            for wrong in (session - 1, session + 5):
                h = wire.new_header(
                    wire.Command.request, cluster=CLUSTER, client=0xB1,
                    request=1, session=wrong,
                    operation=int(wire.Operation.create_transfers),
                )
                h = wire.set_checksums(h, b"")
                out = replica.on_request(h, b"")
                rh, command = wire.decode_header(out[0])
                assert command == wire.Command.eviction
                assert int(rh["reason"]) == wire.EVICTION_SESSION_MISMATCH
                assert int(rh["session"]) == wrong
        finally:
            replica.close()

    def test_end_to_end_eviction_recovery_with_real_client(
        self, monkeypatch, tmp_path
    ):
        """The full loop against a REAL replica: capacity-evicted client
        re-registers with a fresh session and completes its retried
        request within its deadline (fake clock — no wall sleeps)."""
        import tigerbeetle_tpu.client as client_mod

        replica = self._solo(tmp_path, clients_max=2)
        try:
            def serve(h, command, body):
                return replica.on_request(h, body)

            sock = FakeServerSocket(serve)
            monkeypatch.setattr(
                client_mod.socket, "create_connection",
                lambda addr, timeout=None: sock,
            )
            c = client_mod.Client(
                [("127.0.0.1", 1)], cluster=CLUSTER, client_id=0xC1,
                timeout_s=30.0,
            )
            clock = {"t": 0.0}
            c._sleep = lambda s: clock.__setitem__("t", clock["t"] + s)
            c._now = lambda: clock["t"]
            c.register()
            old_session = c.session
            # Two other clients overflow clients_max -> 0xC1 evicted.
            for other in (0xC2, 0xC3):
                TestClientsMaxEviction._register(replica, other)
            assert 0xC1 not in replica.sessions
            out = c.request(wire.Operation.lookup_accounts, b"")
            assert out == b""
            assert c.session != old_session
            assert clock["t"] <= 30.0
        finally:
            replica.close()


# ---------------------------------------------------------------------------
# solo bus: busy-on-full-queue gate
# ---------------------------------------------------------------------------


class TestSoloBusGate:
    def test_overload_flag_follows_env(self, tmp_path, monkeypatch):
        from tigerbeetle_tpu.config import LEDGER_TEST, TEST_MIN
        from tigerbeetle_tpu.net.bus import ReplicaServer
        from tigerbeetle_tpu.vsr.replica import Replica

        path = str(tmp_path / "gate.tb")
        Replica.format(path, cluster=CLUSTER, cluster_config=TEST_MIN)
        replica = Replica(
            path, cluster_config=TEST_MIN, ledger_config=LEDGER_TEST,
            batch_lanes=64,
        )
        monkeypatch.delenv("TB_OVERLOAD", raising=False)
        assert ReplicaServer(replica).overload_control is False
        monkeypatch.setenv("TB_OVERLOAD", "1")
        assert ReplicaServer(replica).overload_control is True
        monkeypatch.setenv("TB_OVERLOAD", "0")
        assert ReplicaServer(replica).overload_control is False


# ---------------------------------------------------------------------------
# A polite flood against the real consensus cluster (deterministic sim time)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("multiple", [2, 4])
def test_polite_flood_is_signalled_and_completes(tmp_path, multiple):
    """A cohort that honors busy, offering ``multiple``x the pipeline's
    capacity with overload control on, gets explicit busy replies (not
    silence), backs off, and still completes EVERY request.  At 2x the
    admission queues absorb the flood; 4x forces queue-cap evictions, and
    those shed client-class traffic only."""
    import dataclasses

    from tigerbeetle_tpu.config import TEST_MIN
    from tigerbeetle_tpu.sim.cluster import SimCluster
    from tigerbeetle_tpu.sim.network import PacketSimulator

    pc = TEST_MIN.pipeline_prepare_queue_max
    flood_n, requests, seed = multiple * pc, 6, 11
    cluster = SimCluster(
        str(tmp_path), n_replicas=3, n_clients=1, seed=seed,
        requests_per_client=2,
        config=dataclasses.replace(TEST_MIN, clients_max=flood_n + 16),
        net=PacketSimulator(seed=seed + 1, delay_mean=1, delay_max=6),
        overload={"queue_cap": 4 * pc, "dispatch_budget": pc,
                  "priority": True, "signal": True},
    )
    ids = cluster.add_flood_clients(
        flood_n, seed, n_requests=requests, retry_ticks=40, start_tick=50,
        aggressive=False,
    )
    assert cluster.run_until(cluster.clients_done, max_ticks=120_000)
    assert sum(cluster.clients[c].busy_seen for c in ids) > 0
    assert sum(
        cluster.clients[c].requests_done for c in ids
    ) == flood_n * requests
    shed = cluster.overload_stats()["shed_by_class"]
    assert shed["view_change"] == 0 and shed["repair"] == 0
    assert (shed["client"] > 0) == (multiple == 4), shed


# ---------------------------------------------------------------------------
# VOPR: the overload fault kind (pinned seed; slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestGovernorCrashAccounting:
    def test_crash_retains_admission_counters(self, tmp_path):
        """crash() replaces the dead replica's AdmissionQueue (its items
        die with the kernel buffers) but must FOLD its counters into
        overload_stats() — the flood's heaviest window is usually exactly
        the crashed primary's."""
        from tigerbeetle_tpu.sim.cluster import SimCluster
        from tigerbeetle_tpu.vsr.overload import CLASS_CLIENT

        cluster = SimCluster(
            str(tmp_path), n_replicas=3, n_clients=1, seed=11,
            overload={"queue_cap": 4, "dispatch_budget": 2,
                      "priority": True, "signal": False},
        )
        q = cluster.admission[0]
        for i in range(6):  # 4 admitted, 2 shed at cap
            q.offer(CLASS_CLIENT, 0xA, i)
        before = cluster.overload_stats()
        assert before["shed"] == 2 and before["admitted"] == 4
        cluster.crash(0)
        after = cluster.overload_stats()
        assert after["shed"] == before["shed"]
        assert after["admitted"] == before["admitted"]
        assert after["depth_peak"] == before["depth_peak"] == 4
        assert after["shed_by_class"]["client"] == 2
        # And the replacement queue accumulates ON TOP.
        cluster.admission[0].offer(CLASS_CLIENT, 0xB, 99)
        assert cluster.overload_stats()["admitted"] == 5


@pytest.mark.slow
class TestVoprOverload:
    """Pinned seed 42 at the maximum flood factor: priority scheduling on
    passes every oracle with the election completing mid-flood; priority
    forced off (bounded FIFO) demonstrably fails the liveness oracle.

    Slow (the passing run commits a full flood's worth of requests):
    excluded from tier-1 and the ci consensus tier's "not slow" filter;
    runs by node id in the ci integration tier."""

    def test_pinned_seed_priority_on_passes_mid_flood_election(self):
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_overload_seed

        result = run_overload_seed(42, priority=True, flood_factor=8)
        assert result.exit_code == EXIT_PASSED, result.reason
        # The election completed while the flood was demonstrably live.
        assert result.view_change_tick is not None
        assert result.stats["flood_active_at_vc"] > 0
        # The governor actually shed (the flood was real)...
        assert result.stats["shed"] > 0
        # ...but only ever client-class traffic.
        by = result.stats["shed_by_class"]
        assert by["view_change"] == 0
        assert by["repair"] == 0
        assert by["client"] > 0
        # Signal, don't drop: busy replies flowed.
        assert result.stats["busy_replies"] > 0

    def test_pinned_seed_priority_off_fails_liveness(self):
        from tigerbeetle_tpu.sim.vopr import (
            EXIT_LIVENESS, run_overload_seed,
        )

        result = run_overload_seed(42, priority=False, flood_factor=8)
        assert result.exit_code == EXIT_LIVENESS, (
            "the FIFO negative control PASSED — priority scheduling is "
            f"not load-bearing: {result.reason}"
        )
        assert "view change" in result.reason
