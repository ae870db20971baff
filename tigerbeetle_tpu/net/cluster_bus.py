"""Cluster message bus: multi-replica VSR over real TCP.

The reference's replica-side MessageBus (src/message_bus.zig:24+): replicas
dial higher-indexed replicas (one connection per pair, traffic both ways),
clients dial any replica; connections carry 256-byte-header framed messages;
invalid frames drop the connection; reconnects use exponential backoff.

This asyncio implementation drives a ``VsrReplica`` (vsr/consensus.py): a
tick task fires every ``tick_interval`` (the reference's
``replica.tick(); io.run_for_ns()`` loop, main.zig:266-269) and every
inbound message dispatches through ``on_message``; outbound envelopes route
to peer or client connections.  Peer identity on accepted connections is
learned from the ``replica`` field of the first valid message (replica
messages), client identity from request/ping_client headers.
"""

from __future__ import annotations

import asyncio
import logging
import time
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs.metrics import registry as _obs
from ..obs.txtrace import txtrace
from ..vsr import overload, wire
from ..vsr.consensus import VsrReplica
from .bus import (
    STATSD_FLUSH_INTERVAL_S, FrameError, ServingLoop, _count_reject,
    read_message,
)

log = logging.getLogger("tigerbeetle_tpu.net.cluster")

CLIENT_COMMANDS = {
    wire.Command.request,
    wire.Command.ping_client,
}


class ClusterServer:
    def __init__(
        self,
        replica: VsrReplica,
        addresses: List[Tuple[str, int]],
        tick_interval: Optional[float] = None,
        statsd=None,
        process_config=None,
    ) -> None:
        # Addresses cover ALL nodes: voters [0, replica_count) followed by
        # standbys [replica_count, node_count) (cli.zig --addresses order).
        # Operator-reachable (start --addresses): a real error, not an
        # assert (stripped under -O; misrouting would surface later).
        if replica.node_count != len(addresses):
            raise ValueError(
                f"--addresses lists {len(addresses)} entries but the data "
                f"file's cluster has {replica.node_count} nodes "
                f"({replica.replica_count} voters + {replica.standby_count} "
                "standbys; standbys extend the address list)"
            )
        from ..config import PROCESS_DEFAULT

        self.process = process_config or getattr(
            replica, "process_config", None
        ) or PROCESS_DEFAULT
        self.statsd = statsd  # utils.statsd.StatsD; best-effort, optional
        self.replica = replica
        self.addresses = addresses
        self.index = replica.replica
        self.tick_interval = (
            tick_interval if tick_interval is not None
            else self.process.tick_ms / 1000.0
        )
        self.peer_writers: Dict[int, asyncio.StreamWriter] = {}
        self.client_writers: Dict[int, asyncio.StreamWriter] = {}
        self._server: Optional[asyncio.base_events.Server] = None
        self._tasks: List[asyncio.Task] = []
        self._accepted: set = set()  # live inbound transports (see close())
        self.port: Optional[int] = None
        self.dropped_sends = 0  # bounded-send-queue drops (backpressure)
        self.rejected_frames = 0  # malformed/impersonated ingress frames
        self._last_drop_log = 0.0
        # Connections whose first send-queue drop was already _debug-logged
        # (weak refs: entries die with the writer, so the set stays bounded
        # by LIVE connections and a recycled id can't suppress a fresh
        # connection's record) — silent backpressure drops must be
        # observable even with overload off.
        self._drop_logged: "weakref.WeakSet" = weakref.WeakSet()
        # Priority-aware shedding (vsr/overload.py): follows the replica's
        # one knob (TB_OVERLOAD / --overload-control / sim injection).
        self.overload_control = bool(
            getattr(replica, "overload_control", False)
        )
        self._statsd_flushed_at = 0.0  # registry->statsd bridge cadence
        # RTT-adaptive timeouts convert monotonic ns to consensus ticks;
        # keep the conversion in lockstep with the actual tick cadence.
        replica.tick_ns = int(self.tick_interval * 1e9)
        # Bounded commit execution per dispatch (replica.zig's async
        # commit_dispatch chain never monopolizes its IO loop): the
        # remainder drains through _commit_pump, which yields to the loop
        # between chunks so heartbeats/pongs/prepares interleave.
        replica.commit_budget = self.process.commit_budget_ops
        self._pump_task: Optional[asyncio.Task] = None
        self._pump_backoff_until = 0.0
        # Overlap checkpoints with serving (replica.zig:3153-3169).  Safe
        # under view changes: all superblock writes funnel through the
        # replica's _superblock_install merge-point, so the background
        # checkpoint and _persist_view serialize and never regress each
        # other.  Without this, a checkpoint writes the full (growing)
        # ledger snapshot inside one dispatch — measured 57→913 ms stalls
        # doubling with table capacity, each one a cluster-wide
        # primary-liveness probe and a client latency spike.
        replica.async_checkpoint = True

    # -- lifecycle ------------------------------------------------------------

    async def start(self) -> int:
        host, port = self.addresses[self.index]
        self._server = await asyncio.start_server(
            self._on_accept, host, port, backlog=self.process.tcp_backlog
        )
        self.port = self._server.sockets[0].getsockname()[1]
        log.info("replica %d listening on %s:%d", self.index, host, self.port)
        # Dial higher-indexed nodes (message_bus.zig connection rule).
        for j in range(self.index + 1, self.replica.node_count):
            self._tasks.append(asyncio.ensure_future(self._dial_loop(j)))
        self._tasks.append(asyncio.ensure_future(self._tick_loop()))
        return self.port

    async def serve_forever(self) -> None:
        assert self._server is not None
        async with self._server:
            await self._server.serve_forever()

    async def close(self) -> None:
        for t in self._tasks:
            t.cancel()
        if self._pump_task is not None:
            # A pump left running would keep committing against a replica
            # mid-teardown (storage closing under it) and die noisily.
            self._pump_task.cancel()
            self._pump_task = None
        if self._server is not None:
            self._server.close()
        # Close every transport we know of — outbound writers AND accepted
        # inbound connections.  Do NOT await Server.wait_closed(): since
        # Python 3.12 it waits for all connection handlers to finish, and a
        # live peer's inbound connection never ends on its own — a hard
        # stop of a busy replica would hang forever.
        for w in (
            list(self.peer_writers.values())
            + list(self.client_writers.values())
            + list(self._accepted)
        ):
            try:
                w.close()
            except (OSError, RuntimeError):
                pass  # already-closed transport / closed event loop
        self._accepted.clear()

    def _set_tcp_options(self, writer: asyncio.StreamWriter) -> None:
        """Apply ProcessConfig TCP knobs (config.zig tcp_nodelay et al.)."""
        import socket as _socket

        sock = writer.get_extra_info("socket")
        if sock is None:
            return
        try:
            if self.process.tcp_nodelay:
                sock.setsockopt(
                    _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1
                )
        except OSError:
            pass

    # -- peer connections -----------------------------------------------------

    async def _dial_loop(self, j: int) -> None:
        """Keep one outbound connection to replica j alive, with
        exponential backoff (message_bus.zig reconnect discipline)."""
        delay_min = self.process.connection_delay_min_ms / 1000.0
        delay_max = self.process.connection_delay_max_ms / 1000.0
        backoff = delay_min
        loop = asyncio.get_running_loop()
        while True:
            host, port = self.addresses[j]
            try:
                reader, writer = await asyncio.open_connection(host, port)
            except OSError:
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, delay_max)
                continue
            self._set_tcp_options(writer)
            self.peer_writers[j] = writer
            connected_at = loop.time()
            try:
                await self._read_loop(reader, writer, peer=j)
            finally:
                if self.peer_writers.get(j) is writer:
                    del self.peer_writers[j]
                writer.close()
            # Reset backoff only after a connection that actually lived —
            # an accept-then-drop listener must still back off exponentially.
            if loop.time() - connected_at > 1.0:
                backoff = delay_min
            else:
                await asyncio.sleep(backoff)
                backoff = min(backoff * 2, delay_max)

    async def _on_accept(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        """Accepted connection: replica j<i, or a client — identified by
        the first valid message."""
        self._set_tcp_options(writer)
        self._accepted.add(writer)
        try:
            await self._read_loop(reader, writer, peer=None)
        finally:
            self._accepted.discard(writer)
            for table in (self.peer_writers, self.client_writers):
                for key, w in list(table.items()):
                    if w is writer:
                        del table[key]
            writer.close()

    async def _read_loop(
        self,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        peer: Optional[int],
    ) -> None:
        # Connection kind: a dialed connection is a peer by construction; an
        # accepted one is classified by its FIRST valid message (a client
        # request forwarded over a replica link must NOT register the peer
        # writer as that client — the reply would be misrouted).
        is_peer = peer is not None
        is_client = False
        # Pinned peer identity (the byzantine fault domain's source
        # authentication, docs/fault_domains.md): a dialed connection's
        # identity is its address index; an accepted one pins to the first
        # replica-classifying message's sender.  Frames whose header
        # asserts a DIFFERENT voter identity for a source-authenticated
        # command are forged votes/heartbeats: drop-and-count, keep the
        # connection (one bad frame must not sever an honest link).
        pinned = peer
        rejected = {"n": 0}

        def on_reject(reason: str) -> None:
            self.rejected_frames += 1
            rejected["n"] += 1
            if rejected["n"] == 1:
                self.replica._debug(
                    "frame_reject_first", reason=reason,
                    peer=-1 if pinned is None else pinned,
                    rejected_total=self.rejected_frames,
                )
                log.warning(
                    "rejected malformed frame (peer %s): %s "
                    "(connection kept)", pinned, reason,
                )

        try:
            while True:
                msg = await read_message(
                    reader, self.replica.config.message_size_max,
                    on_reject=on_reject,
                )
                if msg is None:
                    return
                h, command, body = msg
                if wire.u128(h, "cluster") != self.replica.cluster:
                    log.warning("wrong cluster %x", wire.u128(h, "cluster"))
                    return
                if not is_peer:
                    if command in CLIENT_COMMANDS:
                        # Tentative: a replica link whose FIRST message is a
                        # forwarded client request must not freeze as a
                        # client connection — any replica-only command later
                        # upgrades it (ADVICE round-1).
                        is_client = True
                    else:
                        sender = int(h["replica"])
                        if not (0 <= sender < self.replica.node_count):
                            # A replica-classifying frame with an
                            # out-of-range identity must not classify the
                            # connection UNPINNED — that would disable the
                            # impersonation guard for its whole lifetime.
                            # Drop-and-count; the next frame re-attempts.
                            _count_reject("impersonation", on_reject)
                            continue
                        is_peer = True
                        if is_client:
                            # Upgrade: purge client registrations made during
                            # the tentative window or their replies would
                            # keep routing down this replica link.
                            for key in [
                                k for k, w in self.client_writers.items()
                                if w is writer
                            ]:
                                del self.client_writers[key]
                        is_client = False
                        self.peer_writers.setdefault(sender, writer)
                        if pinned is None:
                            pinned = sender  # accepted link: pin now
                if (
                    is_peer and pinned is not None
                    and command in wire.SOURCE_AUTHENTICATED_COMMANDS
                    and int(h["replica"]) != pinned
                ):
                    # A vote/heartbeat/repair frame asserting a different
                    # voter identity than this connection's: forged.
                    _count_reject("impersonation", on_reject)
                    continue
                if is_client and command in CLIENT_COMMANDS:
                    client = wire.u128(h, "client")
                    if client:
                        self.client_writers[client] = writer
                if command == wire.Command.ping_client:
                    pong = wire.new_header(
                        wire.Command.pong_client,
                        cluster=self.replica.cluster,
                        view=self.replica.view,
                    )
                    pong["replica"] = self.index
                    writer.write(wire.encode(pong))
                    await writer.drain()
                    continue
                if command == wire.Command.request and (
                    self.statsd is not None or _obs.enabled
                ):
                    events = 0
                    try:
                        op = wire.Operation(int(h["operation"]))
                        if op in (wire.Operation.create_accounts,
                                  wire.Operation.create_transfers):
                            events = len(body) // 128
                    except ValueError:
                        pass
                    if self.statsd is not None:
                        self.statsd.count("requests")
                        if events:
                            self.statsd.count("events", events)
                    if _obs.enabled:
                        _obs.counter("net.cluster.requests").inc()
                        _obs.counter("net.cluster.events").inc(events)
                        if events:
                            _obs.histogram(
                                "net.cluster.batch_events", "events"
                            ).observe(events)
                if command == wire.Command.request:
                    # A traced request crossing this replica's TCP ingress
                    # (no-op when untraced or the tracer is off).
                    txtrace.hop(int(h["trace"]), "cluster_bus.ingress",
                                replica=self.index)
                t0 = time.monotonic()
                out = self.replica.on_message(h, command, body)
                dt = time.monotonic() - t0
                if _obs.enabled:
                    _obs.histogram("net.cluster.dispatch_us", "us").observe(
                        dt * 1e6
                    )
                if dt > 0.05:
                    # Loop-stall forensics: a synchronous dispatch that
                    # blocks the IO loop starves heartbeats AND pongs, and
                    # shows up cluster-wide as a primary-liveness probe.
                    self.replica._debug(
                        "slow_dispatch", cmd=command.name,
                        ms=round(dt * 1e3, 1),
                    )
                await self._route(out)
                self._ensure_pump()
                await writer.drain()
        except FrameError as err:
            log.warning("dropping connection: %s", err)
        except (ConnectionResetError, BrokenPipeError):
            pass
        except asyncio.CancelledError:
            raise
        except Exception:
            log.exception("dispatch error, dropping connection")

    # -- outbound routing -----------------------------------------------------

    # Bounded send queue per connection (message_pool.zig's static budget):
    # messages to a peer that stops reading are DROPPED (adaptive retry
    # timeouts re-send); the connection itself stays up.
    SEND_BUFFER_MAX = 8 * (1 << 20)
    # Priority-aware thresholds (overload control ON): the client plane
    # sheds FIRST (half budget), the replication stream at the base budget,
    # and view-change/repair traffic — what would actually END an overload
    # — gets a hard reserve up to 2x.  Memory stays bounded either way.
    SEND_SHED_AT = {
        overload.CLASS_VIEW_CHANGE: 2 * SEND_BUFFER_MAX,
        overload.CLASS_REPAIR: 2 * SEND_BUFFER_MAX,
        overload.CLASS_PREPARE: SEND_BUFFER_MAX,
        overload.CLASS_CLIENT: SEND_BUFFER_MAX // 2,
    }

    def _send_threshold(self, message: bytes) -> Tuple[int, int]:
        """Per-message (drop threshold, class) for the bounded send queue.
        The command byte sits at a fixed frame offset
        (message_header.zig:17); an undecodable command sheds with the
        client class.  The class rides along so the drop path does not
        re-classify the same frame."""
        if not self.overload_control:
            return self.SEND_BUFFER_MAX, overload.CLASS_CLIENT
        try:
            cls = overload.classify(wire.Command(message[110]))
        except ValueError:
            cls = overload.CLASS_CLIENT
        return self.SEND_SHED_AT[cls], cls

    def _count_drop(self, w, cls: int) -> None:
        """Backpressure-drop accounting (satellite: silent drops must be
        observable even with overload control off): the bus.dropped_sends
        series, per-class overload.drop.* when shedding by class, a
        rate-limited warning, and a one-time _debug record per
        connection."""
        self.dropped_sends += 1
        if _obs.enabled:
            _obs.counter("bus.dropped_sends").inc()
            if self.overload_control:
                _obs.counter(
                    f"overload.drop.{overload.CLASS_NAMES[cls]}"
                ).inc()
        if w not in self._drop_logged:
            self._drop_logged.add(w)
            self.replica._debug(
                "send_queue_drop_first",
                buffered=w.transport.get_write_buffer_size(),
                dropped_total=self.dropped_sends,
            )
        now = asyncio.get_running_loop().time()
        if now - self._last_drop_log > 1.0:  # throttled visibility
            self._last_drop_log = now
            log.warning(
                "send queue full: dropped %d messages so far",
                self.dropped_sends,
            )

    async def _route(self, envelopes) -> None:
        keychain = getattr(self.replica, "auth", None)
        for (kind, ident), message in envelopes:
            if (
                keychain is not None
                and len(message) >= wire.HEADER_SIZE
                and message[111] == self.index
                and message[110] in wire.SOURCE_AUTHENTICATED_BYTES
            ):
                # MAC-stamp our OWN source-authenticated frames at egress
                # (vsr/auth.py; the sim transport does the same in
                # SimCluster._route).  Relayed frames — prepares, re-served
                # replies — keep their creator's stamp (or zero, legacy).
                message = keychain.stamp(message)
            if kind == "replica":
                w = self.peer_writers.get(ident)
            else:
                w = self.client_writers.get(ident)
            if w is None:
                continue  # not connected: timeouts re-send
            # Bounded send queue (message_bus.zig / message_pool.zig:17-58
            # discipline): a clogged peer's messages DROP — the adaptive
            # retry timeouts re-send — so a slow consumer can never grow
            # replica memory unboundedly.  The connection stays up.  With
            # overload control on, the threshold is CLASS-AWARE: a client
            # flood saturating the buffer sheds its own replies first while
            # view-change/repair messages still get through (the old single
            # threshold dropped whatever overflowed, repair included).
            threshold, cls = self._send_threshold(message)
            if w.transport.get_write_buffer_size() > threshold:
                self._count_drop(w, cls)
                continue
            w.write(message)

    async def _tick_loop(self) -> None:
        while True:
            await asyncio.sleep(self.tick_interval)
            try:
                await self._route(self.replica.tick())
                self._ensure_pump()
                # Adopt any landed background checkpoint.  checkpoint() only
                # runs at due boundaries (measured from the last capture),
                # so the tick loop is the cluster's poll path — without it
                # the finished write is never adopted, op_checkpoint never
                # advances, and the WAL fills permanently at
                # op_checkpoint + journal_slot_count.
                self.replica._checkpoint_poll()
                if _obs.enabled:
                    # Queue-depth sampling (overload.* forensics): the
                    # deepest outbound buffer, once per tick — cheap, and
                    # enough to see backpressure building before drops.
                    writers = list(self.peer_writers.values()) + list(
                        self.client_writers.values()
                    )
                    depth = max(
                        (w.transport.get_write_buffer_size()
                         for w in writers), default=0,
                    )
                    _obs.gauge("bus.send_buffer_max_bytes").set(depth)
                if self.statsd is not None and _obs.enabled:
                    now = time.monotonic()
                    if now - self._statsd_flushed_at >= (
                        STATSD_FLUSH_INTERVAL_S
                    ):
                        self._statsd_flushed_at = now
                        _obs.flush_statsd(self.statsd)
            except Exception:
                log.exception("tick failure")

    # -- bounded commit pump --------------------------------------------------

    def _ensure_pump(self) -> None:
        """Schedule the commit pump if a dispatch stopped on its commit
        budget with backlog remaining."""
        if self._pump_task is not None or not (
            self.replica.commit_budget_stopped
            and self.replica.commit_backlog
        ):
            return
        if asyncio.get_running_loop().time() < self._pump_backoff_until:
            return  # last pump crashed; don't respawn into a retry storm
        self._pump_task = asyncio.ensure_future(self._commit_pump())

    async def _commit_pump(self) -> None:
        try:
            while True:
                out: List = []
                more = self.replica._commit_journal(out)
                await self._route(out)
                if not more:
                    return
                # The yield that justifies the budget: pings, pongs, and
                # prepares get the loop between commit chunks.
                await asyncio.sleep(0)
        except Exception:
            # A persistent failure (e.g. checkpoint write on a full disk)
            # would otherwise respawn from the 2 ms tick loop into a
            # traceback-per-tick storm; back off instead — commits stay
            # wedged either way, but the replica remains diagnosable.
            self._pump_backoff_until = (
                asyncio.get_running_loop().time() + 5.0
            )
            log.exception("commit pump failure (backing off 5s)")
        finally:
            self._pump_task = None


def run_cluster_server(
    replica: VsrReplica,
    addresses: List[Tuple[str, int]],
    ready_callback=None,
    statsd=None,
) -> None:
    """Blocking entry point: serve one cluster replica until cancelled."""

    async def main():
        server = ClusterServer(replica, addresses, statsd=statsd)
        port = await server.start()
        if ready_callback is not None:
            ready_callback(port)
        await server.serve_forever()

    try:
        asyncio.run(main(), loop_factory=ServingLoop)
    except KeyboardInterrupt:
        pass
