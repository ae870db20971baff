"""Message bus: TCP framing + the replica server event loop.

The reference's MessageBus (src/message_bus.zig) is a TCP mesh over an
io_uring event loop with per-connection receive buffers and bounded send
queues; messages are framed as a 256-byte checksummed header + body.  This is
the same wire discipline on asyncio: the frame codec is shared by server and
client, bad frames drop the connection (checksum failure means corruption or
a protocol mismatch — message_bus.zig terminates on invalid headers), and the
replica executes on the loop thread (the reference replica is likewise
single-threaded; SURVEY §2.8.5).

Peer-to-peer replica connections (prepare/prepare_ok/commit flow) layer on
the same framing; see vsr/cluster.py for the multi-replica message flow.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import os
import selectors
import signal
import sys
import time
from typing import Optional

import numpy as np

from ..obs.metrics import registry as _obs
from ..obs.txtrace import now_us, txtrace
from ..vsr import overload, wire
from ..vsr.replica import Replica

log = logging.getLogger("tigerbeetle_tpu.net")

# Seconds between registry->StatsD bridge flushes when both are active
# (the registry's replica/ops series ride the same UDP path as the bus's
# direct counters; see obs/metrics.Registry.flush_statsd).
STATSD_FLUSH_INTERVAL_S = 1.0


class FrameError(Exception):
    pass


class TimedSelector(selectors.DefaultSelector):
    """The serving loop's selector: while ``txtrace.active`` a ``select``
    that may sleep is inside span ``loop_wait`` (obs/txtrace.py), so every
    instant of the loop thread outside a span is work; off it is the plain
    call (one branch a loop iteration).  A poll (``timeout`` 0: the loop
    has callbacks ready) waits for nothing and opens no span."""

    def select(self, timeout=None):
        if txtrace.active and (timeout is None or timeout > 0):
            with txtrace.stage("loop_wait"):
                return super().select(timeout)
        return super().select(timeout)


class ServingLoop(asyncio.SelectorEventLoop):
    """The loop both buses serve on (``asyncio.run``'s ``loop_factory``):
    its selector is timed (``loop_wait``), and what it runs for a readable
    socket is inside span ``socket_read`` while ``txtrace.active``: the
    transport's ``recv`` and the copy into the stream's buffer (a request's
    1 MiB body comes in as four reads or more), an accept, a wakeup from
    another thread.  That work is asyncio's own, under no line of this
    program that a ``with`` could hold, so the loop's hook for registering
    a reader is wrapped.

    THIS LEANS ON A PRIVATE NAME OF CPYTHON 3.12: ``_add_reader`` of
    ``asyncio.selector_events.BaseSelectorEventLoop`` is what ``add_reader``
    and every selector transport call there.  A Python that renames it
    still serves, but the span vanishes without an error, and
    ``serving_unnamed_pct`` rises by the reads' ~0.7 ms a request;
    tests/test_request_timeline.py fails then and says so.  Off, a
    readable socket costs one call (the closure, made once a registration)
    and one branch more than the plain loop."""

    def __init__(self) -> None:
        super().__init__(TimedSelector())

    def _add_reader(self, fd, callback, *args):
        def timed(*a):
            if txtrace.active:
                with txtrace.stage("socket_read"):
                    return callback(*a)
            return callback(*a)

        return super()._add_reader(fd, timed, *args)


def _count_reject(reason: str, on_reject=None) -> None:
    """Shared rejected-frame accounting (the byzantine fault domain's
    drop-and-count discipline, docs/fault_domains.md): the always-on
    ``bus.rejected_frames`` series plus the per-reason byzantine.* family,
    and the caller's per-connection hook (first-reject `_debug` record)."""
    if _obs.enabled:
        _obs.counter("bus.rejected_frames").inc()
        _obs.counter(f"byzantine.rejected.{reason}").inc()
    if on_reject is not None:
        on_reject(reason)


async def read_message(
    reader: asyncio.StreamReader, message_size_max: int, on_reject=None
):
    """Read one framed message; returns (header, command, body) or None on
    clean EOF.

    Corruption discipline (message_bus.zig terminate-on-invalid, refined
    for the byzantine fault domain): a bad HEADER means the length prefix
    cannot be trusted, so framing is lost — FrameError, the caller drops
    the connection.  A bad BODY under a valid header leaves framing intact
    — the frame is skipped, counted (``bus.rejected_frames`` /
    ``byzantine.rejected.*``, plus the caller's ``on_reject`` hook), and
    the connection keeps serving: one malformed frame must not let a
    malicious peer sever an honest link."""
    while True:
        try:
            head = await reader.readexactly(wire.HEADER_SIZE)
        except (asyncio.IncompleteReadError, ConnectionResetError):
            return None
        try:
            h, command = wire.decode_header(head)
        except ValueError as err:
            _count_reject(getattr(err, "reason", "header"), on_reject)
            raise FrameError(f"bad header: {err}") from err
        size = int(h["size"])
        if size > message_size_max:
            _count_reject("oversize", on_reject)
            raise FrameError(f"size {size} exceeds message_size_max")
        body = b""
        if size > wire.HEADER_SIZE:
            try:
                body = await reader.readexactly(size - wire.HEADER_SIZE)
            except (asyncio.IncompleteReadError, ConnectionResetError):
                return None
        try:
            # Empty bodies verify too: a header-only frame with a stale
            # checksum_body is forged/corrupt even though its header
            # checksum (which covers the stale field) passes.
            with txtrace.stage("ingress_verify"):
                wire.verify_body(h, body)
        except ValueError as err:
            _count_reject(getattr(err, "reason", "body"), on_reject)
            continue  # framing intact: skip the frame, keep the connection
        return h, command, body


class _HeaderFirst:
    """A stream whose first ``readexactly`` hands back header bytes already
    read: lets ``_handle_connection`` stamp a request's header arrival
    itself and still have ``read_message`` frame, check and verify it."""

    __slots__ = ("_head", "_reader")

    def __init__(self, head: bytes, reader: asyncio.StreamReader) -> None:
        self._head = head
        self._reader = reader

    async def readexactly(self, n: int) -> bytes:
        head = self._head
        if head is not None:
            self._head = None
            return head
        return await self._reader.readexactly(n)


class ReplicaServer:
    """Serve one replica over TCP (the `tigerbeetle start` loop,
    src/tigerbeetle/main.zig:133+266-269)."""

    # Requests executed per group: bounds memory (K x 1 MiB bodies) while
    # amortizing the group's single WAL fsync (vsr.zig pipeline_prepare_
    # queue_max spirit: enough overlap to hide the barrier, no more).
    GROUP_MAX = 32
    # Concurrent reply-flush tasks (groups whose fsync/drain is still in
    # flight) before the processor must wait for one to finish.
    FLUSH_MAX = 8

    # MEMORY BUDGET INVARIANT (message_pool.zig:17-58's role — the
    # reference proves at comptime that its static message pool can never
    # deadlock; this is the asyncio equivalent, enforced at runtime):
    #
    #   bodies resident <= queue (2*GROUP_MAX)            [put() backpressure]
    #                    + (FLUSH_MAX + 1) * GROUP_MAX    [in-flight groups]
    #
    # i.e. <= 352 message bodies regardless of client behavior, because:
    #   1. connection readers await queue.put() (a pipelining protocol
    #      violator stalls its OWN reader, never the server);
    #   2. the processor admits at most FLUSH_MAX concurrent flush tasks;
    #   3. every flush completes in bounded time: each drain() is capped by
    #      drain_timeout_ms, after which the slow consumer is EVICTED
    #      (connection closed) — so no client can hold a flush task, and
    #      therefore the processor, hostage.
    # Deadlock-freedom: the processor never awaits anything a client
    # controls beyond that bounded drain.

    def __init__(self, replica: Replica, host: Optional[str] = None,
                 port: Optional[int] = None, statsd=None) -> None:
        from ..config import PROCESS_DEFAULT

        self.process = getattr(replica, "process_config", None) or (
            PROCESS_DEFAULT
        )
        self.replica = replica
        # ProcessConfig supplies the listen defaults (config.zig
        # address/port); explicit arguments override.
        self.host = host if host is not None else self.process.address
        self.port = port if port is not None else self.process.port
        self.statsd = statsd  # utils.statsd.StatsD; never blocks, optional
        self._statsd_flushed_at = 0.0  # last registry->statsd bridge flush
        self._server: Optional[asyncio.base_events.Server] = None
        self._accepted: set = set()
        # Pipelined request plane: connection readers enqueue; one processor
        # task drains everything pending into a single group commit (decode
        # of batch N+1 overlaps execution of batch N; the group shares one
        # WAL fsync).  The reference's single-threaded io_uring loop has the
        # same shape: many connections, one executor, batched barriers.
        self._requests: Optional[asyncio.Queue] = None
        self._processor: Optional[asyncio.Task] = None
        self._flushes: set = set()
        # Commit groups picked up while txtrace was active: a group's
        # sequence number, the ``seq`` of its spans and of its timeline
        # (obs/txtrace.py).
        self._group_seq = 0
        # Connections whose request header has been read and whose body
        # has not been enqueued yet (raised and lowered only while txtrace
        # is active): what a pickup observes as ``net.pickup.arriving``.
        self._arriving = 0
        # Overload control (vsr/overload.py): with the knob ON, a full
        # request queue SIGNALS busy (retryable, with a retry hint) instead
        # of silently backpressuring the connection reader until the client
        # times out.  Off (default) the put() backpressure is unchanged.
        self.overload_control = bool(
            getattr(replica, "overload_control", None)
            or overload.enabled()
        )

    async def start(self) -> int:
        # Bounded: put() backpressures connection readers, so a protocol-
        # violating client pipelining requests cannot buffer unbounded
        # ~1 MiB bodies server-side (MessagePool semantics, SURVEY §2 #41).
        self._requests = asyncio.Queue(maxsize=2 * self.GROUP_MAX)
        self._processor = asyncio.get_running_loop().create_task(
            self._process_requests()
        )
        self._server = await asyncio.start_server(
            self._handle_connection, self.host, self.port,
            backlog=self.process.tcp_backlog,
            # Stream buffer sized to a full message: the default 64 KiB limit
            # makes readexactly(1 MiB) resume the transport ~16 times per
            # request (syscall + copy each).
            limit=self.replica.config.message_size_max + wire.HEADER_SIZE,
        )
        self.port = self._server.sockets[0].getsockname()[1]
        log.info("replica %d listening on %s:%d (commit pipeline depth %d)",
                 self.replica.replica, self.host, self.port,
                 getattr(self.replica, "pipeline_depth", 1))
        return self.port

    async def serve_forever(self) -> None:
        """Serve (the server accepts since ``start``) until cancelled, then
        ``close``.  NOT ``async with self._server`` nor
        ``Server.serve_forever()``: on the way out both await
        ``wait_closed()``, which since Python 3.12 waits for every accepted
        transport to detach, and one whose ``connection_lost`` never comes
        (a callback lost to an exception thrown into the loop) holds the
        shutdown for ever."""
        assert self._server is not None
        try:
            await asyncio.get_running_loop().create_future()
        finally:
            await self.close()

    async def close(self) -> None:
        if self._server is not None:
            self._server.close()
        if self._processor is not None:
            self._processor.cancel()
            try:
                await self._processor
            except asyncio.CancelledError:
                pass
            except Exception:
                # A processor that died BEFORE the cancel carries the real
                # failure; losing it here would hide a server-loop crash.
                log.exception("request processor failed before close")
            self._processor = None
        for task in list(self._flushes):
            task.cancel()
        self._flushes.clear()
        # Don't await Server.wait_closed(): since Python 3.12 it waits for
        # all connection handlers, and an idle client's connection never
        # ends on its own (see cluster_bus.ClusterServer.close).
        for w in list(self._accepted):
            try:
                w.close()
            except (OSError, RuntimeError):
                pass  # already-closed transport / closed event loop
        self._accepted.clear()

    async def _process_requests(self) -> None:
        """Drain the request queue in groups; one group commit per wakeup.

        The group's WAL fsync is NOT awaited here: replies are released by a
        completion task when it lands, and the processor starts the next
        group immediately — a latency spike on the shared disk (hundreds of
        ms observed on cloud block devices) then costs only the spike's
        bandwidth, not a pipeline stall per group."""
        assert self._requests is not None
        while True:
            if self._requests.empty() and getattr(
                self.replica, "pipeline_pending", False
            ):
                # Queue idle: no next group will come due to drive the
                # pending group's readbacks — flush so its replies release
                # now (latency beats overlap when there is nothing to
                # overlap with).  Same failure discipline as the group
                # call below: a flush error fails that group's reply
                # promise (its flush task drops the connections), and the
                # processor must keep serving everyone else.
                if _obs.enabled:
                    _obs.counter("pipeline.flush.idle").inc()
                try:
                    with txtrace.stage("pipeline_flush"):
                        self.replica.pipeline_flush()
                except Exception:
                    log.exception("pipeline flush failed")
            group = [await self._requests.get()]
            while len(group) < self.GROUP_MAX:
                try:
                    group.append(self._requests.get_nowait())
                except asyncio.QueueEmpty:
                    break
            observing = self.statsd is not None or _obs.enabled
            timeline = None
            if txtrace.active:
                self._group_seq += 1
                timeline = txtrace.group_begin(self._group_seq)
                if _obs.enabled:
                    # Requests that miss this group by the length of their
                    # own body read.
                    _obs.histogram(
                        "net.pickup.arriving", "requests"
                    ).observe(self._arriving)
            t0 = time.monotonic() if observing else 0.0
            try:
                with txtrace.stage("commit_group", n=len(group)):
                    replies, fsync = self.replica.on_request_group_pipelined(
                        [(h, body) for h, body, _w, _t in group],
                        deferred_replies=True,
                    )
            except Exception:
                # A group execution failure is a server-side fault (storage
                # error mid-commit); surviving connections would otherwise
                # wait forever for withheld replies — drop them so clients
                # failover/retry (message_bus.zig terminate discipline).
                log.exception("group commit failed; dropping %d connections",
                              len(group))
                for _h, _b, w, _t in group:
                    w.close()
                continue
            if timeline is not None:
                timeline.returned(replies, fsync)
            if observing:
                self._emit_stats(group, time.monotonic() - t0)
            if fsync is None:
                await self._flush_group(group, replies, fsync, timeline)
            else:
                # Reply release rides the durability barrier; the processor
                # moves on.  (Tracked so close() can cancel stragglers.)
                # FLUSH_MAX caps concurrent in-flight groups (see the
                # memory-budget invariant above).  The coroutine is created
                # only HERE: a cancellation during the cap wait must not
                # orphan a never-awaited coroutine.
                while len(self._flushes) >= self.FLUSH_MAX:
                    await asyncio.wait(
                        list(self._flushes),
                        return_when=asyncio.FIRST_COMPLETED,
                    )
                task = asyncio.get_running_loop().create_task(
                    self._flush_group(group, replies, fsync, timeline)
                )
                self._flushes.add(task)
                task.add_done_callback(self._flushes.discard)

    async def _flush_group(self, group, replies, fsync,
                           timeline=None) -> None:
        if fsync is not None:
            try:
                await asyncio.wrap_future(fsync)
            except Exception:
                log.exception("group fsync failed; dropping %d connections",
                              len(group))
                for _h, _b, w, _t in group:
                    w.close()
                return
        if isinstance(replies, concurrent.futures.Future):
            # Pipelined engine: the reply list comes due when the group's
            # deferred readbacks land (next group / pipeline_flush) — the
            # reply barrier now awaits BOTH the fsync and the execution.
            try:
                replies = await asyncio.wrap_future(replies)
            except Exception:
                log.exception(
                    "pipelined group failed; dropping %d connections",
                    len(group),
                )
                for _h, _b, w, _t in group:
                    w.close()
                return
        # Stamps of the requests whose replies are written below (only
        # with a timeline, i.e. picked up while txtrace was active).
        released = None if timeline is None else []
        with txtrace.stage("reply_release",
                           seq=0 if timeline is None else timeline.seq,
                           n=len(group)):
            for (h, _b, writer, stamps), outs in zip(group, replies):
                if writer.is_closing():
                    continue
                for out in outs:
                    writer.write(out)
                if outs:
                    # The request header's trace rides the reply we just
                    # released (replica._commit_prepare copied it) — close
                    # the server half of the causal chain here.
                    txtrace.hop(int(h["trace"]), "bus.release",
                                replica=self.replica.replica)
                    if released is not None and stamps is not None:
                        released.append(stamps)
        if released:
            timeline.t_released = now_us()
            for t_header, t_enqueued in released:
                txtrace.request_observe(timeline, t_header, t_enqueued)
        # Parallel bounded drains: one slow client must not serialize the
        # group, and a client that stops reading is evicted after
        # drain_timeout_ms (the bounded-send-queue discipline; a stuck
        # drain here would hold the flush task — and under fsync=None the
        # processor itself — hostage).
        timeout = self.process.drain_timeout_ms / 1000.0
        await asyncio.gather(*(
            self._drain_or_evict(writer, timeout)
            for _h, _b, writer, _t in group
            if not writer.is_closing()
        ))

    async def _drain_or_evict(self, writer, timeout: float) -> None:
        try:
            await asyncio.wait_for(writer.drain(), timeout)
        except asyncio.TimeoutError:
            peer = writer.get_extra_info("peername")
            log.warning("evicting slow consumer %s (drain > %.1fs)",
                        peer, timeout)
            # abort(), not close(): close() flushes the buffer first, which
            # for a zero-window peer never completes — the buffered replies
            # would stay resident forever and the eviction would be a lie.
            writer.transport.abort()
        except (ConnectionResetError, BrokenPipeError):
            pass

    def _emit_stats(self, group, elapsed_s: float) -> None:
        """Per-group observability: the direct UDP samples the reference
        emits (benchmark_load.zig:120-129 spirit) AND the registry series
        every sink reads (obs/metrics).  Both best-effort, off the commit
        path's critical section."""
        events = 0
        for h, body, _w, _t in group:
            try:
                op = wire.Operation(int(h["operation"]))
                if op in (wire.Operation.create_accounts,
                          wire.Operation.create_transfers):
                    events += len(body) // 128
            except ValueError:
                pass
        per_request_ms = elapsed_s * 1000.0 / len(group)
        if self.statsd is not None:
            self.statsd.count("requests", len(group))
            self.statsd.timing("request_ms", per_request_ms)
            if events:
                self.statsd.count("events", events)
        if _obs.enabled:
            _obs.counter("net.requests").inc(len(group))
            _obs.counter("net.events").inc(events)
            _obs.histogram("net.group_size", "requests").observe(len(group))
            # Reply-release overlap: groups whose fsync barrier is still in
            # flight while the processor already serves the next group.
            _obs.histogram("net.flush_inflight", "groups").observe(
                len(self._flushes)
            )
            # Microseconds: log2 buckets need sub-ms resolution here (a
            # loopback group commit is routinely < 1 ms per request).
            _obs.histogram("net.request_us", "us").observe(
                per_request_ms * 1000.0
            )
            if self.statsd is not None:
                now = time.monotonic()
                if now - self._statsd_flushed_at >= STATSD_FLUSH_INTERVAL_S:
                    self._statsd_flushed_at = now
                    _obs.flush_statsd(self.statsd)

    async def _handle_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        peer = writer.get_extra_info("peername")
        if self.process.tcp_nodelay:
            import socket as _socket

            sock = writer.get_extra_info("socket")
            if sock is not None:
                try:
                    sock.setsockopt(
                        _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1
                    )
                except OSError:
                    pass
        self._accepted.add(writer)
        # First-reject-per-connection record (mirrors cluster_bus's
        # first-drop discipline): one _debug line + warning per connection,
        # however many malformed frames follow.
        rejected = {"n": 0}

        def on_reject(reason: str) -> None:
            rejected["n"] += 1
            if rejected["n"] == 1:
                dbg = getattr(self.replica, "_debug", None)
                if dbg is not None:
                    dbg("frame_reject_first", reason=reason, peer=str(peer))
                log.warning(
                    "rejected malformed frame from %s: %s (connection kept)",
                    peer, reason,
                )

        # The request timeline's first stamp (obs/txtrace.py): when the
        # header of the frame being read came in; 0 = none (txtrace off, or
        # between frames).  Non-zero, the connection counts as arriving.
        t_header = 0
        try:
            while True:
                source = reader
                if txtrace.active:
                    # Header and body reads split HERE and nowhere else:
                    # read_message finds the header's bytes in ``source``.
                    try:
                        head = await reader.readexactly(wire.HEADER_SIZE)
                    except (asyncio.IncompleteReadError,
                            ConnectionResetError):
                        break
                    t_header = now_us()
                    self._arriving += 1
                    source = _HeaderFirst(head, reader)
                msg = await read_message(
                    source, self.replica.config.message_size_max,
                    on_reject=on_reject,
                )
                stamps = None
                if t_header:
                    stamps = (t_header, now_us())
                    t_header = 0
                    self._arriving -= 1
                if msg is None:
                    break
                h, command, body = msg
                if wire.u128(h, "cluster") != self.replica.cluster:
                    log.warning("wrong cluster %x", wire.u128(h, "cluster"))
                    continue
                if command == wire.Command.request:
                    if self.overload_control and self._requests.full():
                        # Admission shed: the queue drains one group per
                        # processor wakeup, so a few ticks is an honest
                        # retry hint.  The request was never journaled —
                        # resending is not a duplicate.
                        if _obs.enabled:
                            _obs.counter("overload.shed.queue").inc()
                            _obs.counter("overload.busy_sent").inc()
                        writer.write(overload.busy_message(
                            self.replica.replica, self.replica.cluster,
                            self.replica.view, h, wire.BUSY_QUEUE,
                            retry_after_ticks=5,
                        ))
                        await writer.drain()
                        continue
                    txtrace.hop(int(h["trace"]), "bus.ingress",
                                replica=self.replica.replica,
                                request=int(h["request"]))
                    # ``stamps``: (header read, enqueued) for the request
                    # timeline; None when txtrace is off (no clock read).
                    await self._requests.put((h, body, writer, stamps))
                    continue
                for out in self._dispatch(h, command, body):
                    writer.write(out)
                await writer.drain()
        except FrameError as err:
            log.warning("dropping connection %s: %s", peer, err)
        except Exception:
            # A dispatch failure must not take down the server loop; drop the
            # connection like any other corrupt peer (message_bus.zig
            # terminate-on-invalid discipline).
            log.exception("dispatch error, dropping connection %s", peer)
        except (ConnectionResetError, BrokenPipeError):
            pass
        finally:
            if t_header:  # the connection ended inside a frame
                self._arriving -= 1
            self._accepted.discard(writer)
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    def _dispatch(self, h: np.ndarray, command: wire.Command, body: bytes):
        if command == wire.Command.request:
            # Normal requests route through the group processor; this path
            # only serves callers that bypass the connection loop (tests).
            return self.replica.on_request(h, body)
        if command == wire.Command.ping_client:
            pong = wire.new_header(
                wire.Command.pong_client, cluster=self.replica.cluster,
                view=self.replica.view,
            )
            pong["replica"] = self.replica.replica
            return [wire.encode(pong, b"")]
        log.warning("unhandled command %s", command.name)
        return []


def _exit_after_dumps(code: int) -> None:
    """End the process now with ``code``, the exit-time dumps (metrics
    snapshot, trace, flight recorder: all atexit callbacks) written first.
    Not ``raise SystemExit``: the interpreter's own finalization then tears
    the device runtime down, which on a TPU host has taken longer than a
    supervisor waits (PERF.md section 6, PR 26: a stop that was still in
    there after 8 s, its signal handlers already reset, died of the next
    signal it was sent)."""
    import atexit

    atexit._run_exitfuncs()
    for stream in (sys.stdout, sys.stderr):
        try:
            stream.flush()
        except (OSError, ValueError):
            pass  # a closed pipe must not keep the process
    os._exit(code)


def run_server(replica: Replica, host: str = "127.0.0.1", port: int = 0,
               ready_callback=None, statsd=None) -> None:
    """Blocking entry point: serve until cancelled; on SIGTERM (main thread
    only) stop serving, write the exit-time dumps and exit with code 143."""
    # Overlap checkpoints with request processing (replica.zig:3153-3169):
    # safe in solo mode — no view changes, so no concurrent superblock
    # writer; the sim keeps checkpoints synchronous for determinism.
    replica.async_checkpoint = True
    terminated = []

    async def main():
        server = ReplicaServer(replica, host, port, statsd=statsd)
        actual_port = await server.start()
        serving = asyncio.current_task()

        def on_sigterm() -> None:
            terminated.append(True)
            serving.cancel()

        # SIGTERM as a callback of the loop, not as an exception thrown into
        # whatever the main thread is executing: cli's handler raises
        # SystemExit wherever it lands, and inside the loop's own
        # bookkeeping that lost a task's wakeup, so the shutdown waited for
        # a second signal (a third of the chip runs, PERF.md section 6).
        try:
            asyncio.get_running_loop().add_signal_handler(
                signal.SIGTERM, on_sigterm
            )
        except (ValueError, RuntimeError, NotImplementedError):
            pass  # not the main thread (tests): the process's handler stays
        if ready_callback is not None:
            ready_callback(actual_port)
        await server.serve_forever()

    try:
        asyncio.run(main(), loop_factory=ServingLoop)
    except KeyboardInterrupt:
        pass
    except asyncio.CancelledError:
        if not terminated:
            raise
    if terminated:
        _exit_after_dumps(143)  # the code cli's own handler exits with
