"""VOPR-style deterministic cluster simulation.

The analogue of the reference simulator (src/simulator.zig, SURVEY §3.4):
a full multi-replica cluster — the *production* consensus code
(vsr/consensus.py), not a model of it — runs in one process on virtual time,
over a seeded packet simulator (delays/loss/partitions, sim/network.py) and
in-memory crash-faulting storage (sim/storage.py).  Simulated clients drive a
seeded workload; the cluster can crash/restart/partition replicas at any
tick.

Oracles (src/testing/cluster/state_checker.zig):
- StateChecker: after faults stop, every replica's (commit_min, ledger
  digest) must converge — byte-level state determinism across replicas.
- Reply coherence: a client must never observe two different replies for
  the same request number (linearizability of the session protocol).
- Conservation: in every converged ledger, total debits == total credits
  (double-entry invariant over the whole cluster history).

Everything is derived from ``seed``: two runs with the same seed and the
same fault schedule are byte-identical (VOPR reproducibility, vopr.zig).
"""

from __future__ import annotations

import dataclasses
import os
import random
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import types
from ..config import ClusterConfig, LedgerConfig, LEDGER_TEST, TEST_MIN
from ..obs.txtrace import (
    Blackbox, dump_blackboxes as _dump_blackboxes, txtrace,
)
from ..testing.workload import WorkloadGen
from ..vsr import wire
from ..vsr.consensus import NORMAL, VsrReplica
from ..vsr.journal import JournalWriteFailure
from .network import PacketSimulator
from .storage import SimStorage

TICK_NS = 10_000_000  # one simulated tick = 10 ms
WALL_EPOCH_NS = 1_700_000_000 * 1_000_000_000  # virtual wall clock base


class ByzantineActor:
    """Seeded Byzantine wrapper around ONE replica (the fifth fault domain,
    docs/fault_domains.md): a man-in-the-middle on the replica's egress
    plus an injector of forged frames.  The wrapped replica's INTERNAL
    state stays honest (it journals and commits like everyone else, so the
    cluster oracles still cover it); only what it SENDS lies.

    Attack repertoire, each drawn from the actor's dedicated rng stream so
    pinned seeds replay bit-identically:

    - ``equivocate``: a forwarded prepare is replaced by two CONFLICTING
      fully-valid variants (mutated body, checksums recomputed, the
      primary's origin header kept) sent to different peers — the classic
      conflicting-prepares-for-one-op-number attack.
    - ``corrupt``: a forwarded frame's body is bit-flipped with the STALE
      ``checksum_body`` kept and only the header checksum recomputed — the
      satellite-audit class that slips past header-only verification.
    - ``replay``: captured ingress frames (peers' heartbeats, votes, old
      prepares) are re-sent later under the actor's own connection —
      stale-view replays and impersonation in one.
    - ``lie_reply``: a forged client reply for a request learned from the
      prepare stream, claiming fabricated results (stale body checksum —
      see the threat model in docs/fault_domains.md for what a fully-valid
      forged reply would additionally require).
    """

    KINDS = ("equivocate", "corrupt", "replay", "lie_reply")
    #: Primary-seat-only frame classes (vopr --byzantine --primary-seat):
    #: equivocating same-view start_views and unsolicited fork-serving
    #: headers responses, forged from the seat's own prepare stream.
    #: Deliberately NOT in the default set — arming them changes the rng
    #: draw sequence, and pinned backup-seat seeds must keep replaying
    #: bit-identically.
    PRIMARY_KINDS = ("equiv_sv", "fork_serve")

    def __init__(
        self,
        replica: int,
        n_replicas: int,
        cluster_id: int,
        seed: int,
        kinds=None,
        rate: float = 0.2,
        window: Tuple[int, int] = (0, 1 << 60),
    ) -> None:
        self.replica = replica
        self.n = n_replicas
        self.cluster_id = cluster_id
        self.rng = random.Random(seed)
        self.kinds = set(kinds) if kinds else set(self.KINDS)
        unknown = self.kinds - set(self.KINDS) - set(self.PRIMARY_KINDS)
        assert not unknown, f"unknown byzantine kinds: {sorted(unknown)}"
        self.rate = rate
        self.window = window
        # verify=False is the run-level negative control (the cluster also
        # strips ingress verification everywhere); the actor itself attacks
        # identically either way — same seed, same draws, same frames.
        self.verify = True
        self.active = True
        self.attacks: Dict[str, int] = {
            k: 0 for k in self.KINDS + self.PRIMARY_KINDS
        }
        # Fork material for the primary-seat kinds: the last prepare the
        # wrapped seat originated (captured at egress — a primary never
        # RECEIVES prepares, so observe_ingress cannot supply it).
        self._fork_material = None
        # Bounded observation state (learned from the wrapped replica's own
        # ingress): client-request facts for forging replies, captured raw
        # frames for replays.
        self._requests: List[dict] = []
        self._replay_pool: List[Tuple[Tuple[str, int], bytes]] = []

    def _on(self, now: int) -> bool:
        return self.active and self.window[0] <= now < self.window[1]

    # -- observation ---------------------------------------------------------

    def observe_ingress(
        self, h, command: wire.Command, body: bytes, message: bytes, now: int
    ) -> None:
        """Record attack material from frames delivered TO the wrapped
        replica (it legitimately sees the prepare stream and peer votes)."""
        if not self._on(now):
            return
        if command == wire.Command.prepare and wire.u128(h, "client"):
            self._requests.append({
                "client": wire.u128(h, "client"),
                "request": int(h["request"]),
                "op": int(h["op"]),
                "commit": int(h["commit"]),
                "view": int(h["view"]),
                "timestamp": int(h["timestamp"]),
                "operation": int(h["operation"]),
                "request_checksum": wire.u128(h, "request_checksum"),
            })
            del self._requests[:-32]
        if command in (wire.Command.commit, wire.Command.prepare_ok,
                       wire.Command.ping, wire.Command.pong):
            if self.rng.random() < 0.25:
                self._replay_pool.append(message)
                del self._replay_pool[:-16]

    # -- frame forgery --------------------------------------------------------

    def _flip(self, body: bytes, salt: int = 0) -> bytes:
        out = bytearray(body)
        i = (self.rng.randrange(len(out)) + salt) % len(out)
        out[i] ^= 1 << self.rng.randrange(8)
        return bytes(out)

    def _stale_body_frame(self, h, body: bytes) -> bytes:
        """A frame whose header checksum VERIFIES but whose checksum_body
        does not match the body it carries — the corruption class that a
        header-only ingress check silently accepts."""
        from ..vsr.checksum import checksum as _checksum

        h = h.copy()
        h["size"] = wire.HEADER_SIZE + len(body)
        # checksum_body left as-is (stale for the flipped body) — or, for a
        # header-only frame, deliberately poisoned.
        if not wire.u128(h, "checksum_body") or not body:
            stale = _checksum(body + b"\x00")
            h["checksum_body_lo"] = stale & 0xFFFF_FFFF_FFFF_FFFF
            h["checksum_body_hi"] = stale >> 64
        c = _checksum(wire.checksum_input(h.tobytes()))
        h["checksum_lo"] = c & 0xFFFF_FFFF_FFFF_FFFF
        h["checksum_hi"] = c >> 64
        return h.tobytes() + body

    def _forge_reply(self, req: dict) -> bytes:
        """A lying client reply: fabricated result bytes for a real request
        (facts lifted from the observed prepare)."""
        lie = np.zeros(1, dtype=types.EVENT_RESULT_DTYPE)
        lie[0]["index"] = 0
        lie[0]["result"] = 0xBAD
        h = wire.new_header(
            wire.Command.reply,
            cluster=self.cluster_id,
            view=req["view"],
            request_checksum=req["request_checksum"],
            client=req["client"],
            op=req["op"],
            commit=req["commit"],
            timestamp=req["timestamp"],
            request=req["request"],
            operation=req["operation"],
        )
        h["replica"] = self.replica
        return self._stale_body_frame(h, lie.tobytes())

    # -- the attack surface ---------------------------------------------------

    def transform(self, envelopes, now: int):
        """Filter the wrapped replica's egress: pass, corrupt, or replace
        with conflicting forgeries."""
        if not self._on(now):
            return envelopes
        out = []
        primary_armed = bool(self.kinds & set(self.PRIMARY_KINDS))
        for dst, message in envelopes:
            command = message[110] if len(message) > 110 else 0
            is_prepare = command == int(wire.Command.prepare)
            if (
                primary_armed and is_prepare
                and len(message) > wire.HEADER_SIZE
            ):
                ph, _, pbody = wire.decode(message)
                if wire.u128(ph, "client") and pbody:
                    self._fork_material = (ph, pbody)
                    # The primary never RECEIVES prepares, so the lying-
                    # reply material observe_ingress gathers for a backup
                    # seat is learned from the seat's own egress instead.
                    self._requests.append({
                        "client": wire.u128(ph, "client"),
                        "request": int(ph["request"]),
                        "op": int(ph["op"]),
                        "commit": int(ph["commit"]),
                        "view": int(ph["view"]),
                        "timestamp": int(ph["timestamp"]),
                        "operation": int(ph["operation"]),
                        "request_checksum": wire.u128(
                            ph, "request_checksum"
                        ),
                    })
                    del self._requests[:-32]
            draw = self.rng.random()
            if (
                is_prepare and "equivocate" in self.kinds
                and draw < self.rate
                and len(message) > wire.HEADER_SIZE
            ):
                h, _, body = wire.decode(message)
                evil_a = wire.encode(h.copy(), self._flip(body))
                evil_b = wire.encode(h.copy(), self._flip(body, salt=7))
                self.attacks["equivocate"] += 1
                out.append((dst, evil_a))
                others = [
                    ("replica", r) for r in range(self.n)
                    if r != self.replica and ("replica", r) != dst
                ]
                if others:
                    out.append((self.rng.choice(others), evil_b))
                continue  # the honest frame is suppressed: equivocation
            if (
                is_prepare and "corrupt" in self.kinds
                and draw < 2 * self.rate
                and len(message) > wire.HEADER_SIZE
            ):
                h, _, body = wire.decode(message)
                self.attacks["corrupt"] += 1
                out.append((dst, self._stale_body_frame(h, self._flip(body))))
                continue
            out.append((dst, message))
        return out

    def inject(self, now: int):
        """Frames the actor originates on its own: stale replays and lying
        client replies."""
        if not self._on(now):
            return []
        out = []
        if (
            "replay" in self.kinds and self._replay_pool
            and self.rng.random() < self.rate / 2
        ):
            victim = self.rng.randrange(self.n)
            if victim != self.replica:
                self.attacks["replay"] += 1
                out.append((
                    ("replica", victim),
                    self._replay_pool[
                        self.rng.randrange(len(self._replay_pool))
                    ],
                ))
        if (
            "lie_reply" in self.kinds and self._requests
            and self.rng.random() < self.rate / 2
        ):
            req = self._requests[self.rng.randrange(len(self._requests))]
            self.attacks["lie_reply"] += 1
            out.append((("client", req["client"]), self._forge_reply(req)))
        for kind in self.PRIMARY_KINDS:
            if (
                kind in self.kinds and self._fork_material is not None
                and self.rng.random() < self.rate / 2
            ):
                victim = self.rng.randrange(self.n)
                if victim != self.replica:
                    self.attacks[kind] += 1
                    out.append((("replica", victim), self._fork_frame(kind)))
        return out

    def _fork_frame(self, kind: str) -> bytes:
        """A primary-seat forgery built from the seat's own last prepare:
        the body's first byte flipped, checksums recomputed — fully
        wire-valid, and sent under the seat's OWN origin (the transport
        MAC-stamps it legally; containment must come from the consensus
        layer's anchor certification, not from the MAC)."""
        ph, pbody = self._fork_material
        evil = wire.encode(
            ph.copy(), bytes([pbody[0] ^ 1]) + pbody[1:]
        )
        evil_h = wire.decode_header(evil)[0]
        if kind == "equiv_sv":
            # Equivocating start_view for the seat's CURRENT view (the
            # only view whose SVs pass the primary-origin check), naming
            # the fork as the canonical head.
            h = wire.new_header(
                wire.Command.start_view,
                cluster=self.cluster_id,
                view=int(ph["view"]),
                op=int(ph["op"]),
                commit=int(ph["commit"]),
            )
        else:  # fork_serve
            # Unsolicited fork-serving headers "response" (the PR 6 gap's
            # probe): proposes the fork as a repair target — under the
            # ingress discipline, repair-target certification must come
            # from anchors, never from a single headers frame.
            h = wire.new_header(
                wire.Command.headers,
                cluster=self.cluster_id,
                view=int(ph["view"]),
            )
        h["replica"] = self.replica
        return wire.encode(h, wire.pack_headers([evil_h]))


class SimClient:
    """A simulated client: register, then a finite stream of workload
    requests with retry/failover (vsr/client.zig semantics on virtual time)."""

    def __init__(
        self,
        client_id: int,
        cluster_id: int,
        n_replicas: int,
        seed: int,
        n_requests: int = 10,
        batch: int = 8,
        retry_ticks: int = 80,
        start_tick: int = 0,
        aggressive: bool = False,
    ) -> None:
        self.client_id = client_id
        self.cluster_id = cluster_id
        self.n_replicas = n_replicas
        self.rng = random.Random(seed)
        self.workload = WorkloadGen(seed)
        self.n_requests = n_requests
        self.batch = batch
        self.retry_ticks = retry_ticks
        self.start_tick = start_tick  # flood cohorts activate mid-run
        # Adversarial cohort: ignores busy retry-after hints and caps its
        # backoff low — overload control must contain a flood of clients
        # that do NOT cooperate, or the protection is only as strong as
        # client politeness.
        self.aggressive = aggressive

        self.session = 0
        self.request_number = 0
        self.parent = 0
        self.target = self.rng.randrange(n_replicas)
        self.inflight: Optional[dict] = None
        self.requests_done = 0
        self.evicted = False
        # request number -> reply header checksum (coherence oracle).
        self.reply_log: Dict[int, int] = {}
        self.results: List[Tuple[int, bytes]] = []
        # Overload-control accounting: explicit busy replies back the
        # client off (jittered exponential + the server hint, mirroring
        # client.py); latencies record send->reply ticks for every
        # completed request.
        from ..vsr.timeout import Timeout

        self._busy_backoff = Timeout(
            random.Random(seed ^ 0xB5), base_ticks=2, max_ticks=64
        )
        self.backoff_until = 0
        self.busy_seen = 0
        self.latencies: List[int] = []
        # Optional hook (client_id, reply_header, operation, body) fired on
        # every ACCEPTED reply — the cluster wires it to the auditor's
        # lying-reply oracle (Auditor.observe_reply).
        self.reply_observer = None

    @property
    def done(self) -> bool:
        return self.evicted or (
            self.requests_done >= self.n_requests and self.inflight is None
        )

    # -- request generation ---------------------------------------------------

    def _next_request(self) -> Optional[Tuple[wire.Operation, bytes]]:
        if self.session == 0:
            return wire.Operation.register, b""
        if self.requests_done >= self.n_requests:
            return None
        k = self.requests_done
        if k == 0:
            return (
                wire.Operation.create_accounts,
                self.workload.accounts_batch(self.batch).tobytes(),
            )
        if k % 5 == 4 and self.workload.account_ids:
            ids = self.rng.sample(
                self.workload.account_ids,
                min(4, len(self.workload.account_ids)),
            )
            arr = np.zeros(2 * len(ids), dtype="<u8")
            for i, v in enumerate(ids):
                arr[2 * i] = v & 0xFFFF_FFFF_FFFF_FFFF
                arr[2 * i + 1] = v >> 64
            return wire.Operation.lookup_accounts, arr.tobytes()
        return (
            wire.Operation.create_transfers,
            self.workload.transfers_batch(
                self.batch, invalid_rate=0.1, dup_rate=0.1, pending_rate=0.2
            ).tobytes(),
        )

    def tick(self, now: int) -> List[Tuple[Tuple[str, int], bytes]]:
        if self.evicted or now < self.start_tick:
            return []
        if now < self.backoff_until:
            return []  # busy-signaled: deliberately waiting, not retrying
        if self.inflight is not None:
            if now - self.inflight["sent"] >= self.retry_ticks:
                if not self.inflight.pop("busy_hold", False):
                    # Failover: rotate target and resend (client.zig
                    # reconnect).  A busy-scheduled resend must NOT rotate:
                    # busy means the primary is ALIVE — the real clients
                    # all resend on the same connection, and rotating here
                    # would bill the measured sweep an extra forward hop
                    # plus a second shed opportunity per busy retry.
                    self.target = (self.target + 1) % self.n_replicas
                self.inflight["sent"] = now
                return [(("replica", self.target), self.inflight["message"])]
            return []
        nxt = self._next_request()
        if nxt is None:
            return []
        operation, body = nxt
        h = wire.new_header(
            wire.Command.request,
            cluster=self.cluster_id,
            client=self.client_id,
            request=self.request_number,
            parent=self.parent,
            session=self.session,
            operation=int(operation),
        )
        # Causal trace stamp (docs/tracing.md), same discipline as the
        # network client: a sampled request carries a nonzero id in the
        # carved header bytes and the replicas' hops chain onto it.  With
        # sampling off (every pinned seed's default) this is one attribute
        # read returning 0 — schedules replay bit-identically.
        trace = txtrace.maybe_trace(int(self.client_id) & 0xFFFF_FFFF)
        if trace:
            h["trace"] = trace
            txtrace.hop(trace, "client.request", phase="start",
                        request=self.request_number)
        message = wire.encode(h, body)
        request_checksum = wire.header_checksum(wire.decode_header(message)[0])
        self.inflight = {
            "message": message,
            "checksum": request_checksum,
            "operation": operation,
            "sent": now,
            "first_sent": now,
        }
        return [(("replica", self.target), message)]

    def on_message(
        self, h: np.ndarray, command: wire.Command, body: bytes, now: int
    ) -> None:
        if command == wire.Command.eviction:
            self.evicted = True
            self.inflight = None
            return
        if command == wire.Command.busy:
            # Explicit shed signal: back off (jittered exponential, floored
            # at the server's retry-after hint) instead of hammering the
            # retry cadence — mirrors client.py's busy handling.
            if self.inflight is not None and (
                wire.u128(h, "request_checksum") == self.inflight["checksum"]
            ):
                self.busy_seen += 1
                if self.aggressive:
                    ticks = min(self._busy_backoff.next_backoff(), 4)
                else:
                    ticks = max(
                        self._busy_backoff.next_backoff(),
                        int(h["retry_after_ticks"]),
                    )
                self.backoff_until = now + ticks
                # The backoff IS the retry schedule: rearm the resend clock
                # so the normal retry doesn't fire the moment it expires,
                # and pin the resend to the SAME replica (no failover on
                # busy — the server is alive, just shedding).
                self.inflight["sent"] = now + ticks - self.retry_ticks
                self.inflight["busy_hold"] = True
            return
        if command != wire.Command.reply:
            return
        request_n = int(h["request"])
        trace = int(h["trace"])
        if trace:
            # The reply carries the request's trace id back: this hop
            # closes the causal chain (flow binding ``f``).
            txtrace.hop(trace, "client.reply", phase="end",
                        request=request_n)
        # Coherence oracle: one logical outcome per request number, ever.
        # Identity is (op, body checksum) — a post-view-change primary
        # legitimately re-sends the reply with new view/replica header
        # fields, but the assigned op and result bytes must never differ.
        reply_identity = (int(h["op"]), wire.u128(h, "checksum_body"))
        seen = self.reply_log.get(request_n)
        assert seen is None or seen == reply_identity, (
            f"client {self.client_id:#x}: two different replies for request "
            f"{request_n}: {seen} vs {reply_identity}"
        )
        self.reply_log[request_n] = reply_identity
        if self.inflight is None:
            return
        if wire.u128(h, "request_checksum") != self.inflight["checksum"]:
            return  # stale reply
        if self.reply_observer is not None:
            # Safety oracle: the accepted reply must agree with committed
            # state (testing/auditor.observe_reply — the byzantine fault
            # domain's lying-reply check).
            self.reply_observer(
                self.client_id, h, self.inflight["operation"], body
            )
        if self.inflight["operation"] == wire.Operation.register:
            self.session = int(h["op"])
            self.request_number = 1
        else:
            self.results.append((request_n, body))
            self.requests_done += 1
            self.request_number += 1
        self.latencies.append(now - self.inflight["first_sent"])
        self._busy_backoff.reset(0)
        self.backoff_until = 0
        self.parent = self.inflight["checksum"]
        self.inflight = None


class OpenLoopClient(SimClient):
    """Open-loop session: requests come from a PRE-GENERATED script of
    (arrival_tick, operation, body) entries (sim/openloop.OpenLoopGen) —
    arrivals land on the schedule whether or not earlier requests
    completed.  The session protocol still serializes one request at a
    time per client id, so when the cluster lags a BACKLOG forms and the
    arrival→reply latency (``queue_latencies``) grows — the open-loop
    queueing signal a closed loop can never produce."""

    def __init__(
        self,
        client_id: int,
        cluster_id: int,
        n_replicas: int,
        seed: int,
        script: List[Tuple[int, wire.Operation, bytes]],
        retry_ticks: int = 80,
    ) -> None:
        super().__init__(
            client_id, cluster_id, n_replicas, seed,
            n_requests=len(script), retry_ticks=retry_ticks,
        )
        self.script = list(script)
        self.queue_latencies: List[int] = []  # arrival -> reply, in ticks
        self._now = 0
        self._last_arrival: Optional[int] = None

    def tick(self, now: int) -> List[Tuple[Tuple[str, int], bytes]]:
        self._now = now
        out = super().tick(now)
        if (
            self.inflight is not None
            and self._last_arrival is not None
            and "arrival" not in self.inflight
        ):
            self.inflight["arrival"] = self._last_arrival
            self._last_arrival = None
        return out

    def _next_request(self):
        if not self.script or self._now < self.script[0][0]:
            return None  # nothing due yet (register rides the first due op)
        if self.session == 0:
            return wire.Operation.register, b""
        arrival, operation, body = self.script.pop(0)
        self._last_arrival = arrival
        return operation, body

    def on_message(self, h, command, body, now: int) -> None:
        inflight = self.inflight
        super().on_message(h, command, body, now)
        if (
            inflight is not None and self.inflight is None
            and "arrival" in inflight
        ):
            self.queue_latencies.append(now - inflight["arrival"])


class SimCluster:
    """N replicas + M clients on virtual time with injectable faults."""

    def __init__(
        self,
        workdir: str,
        n_replicas: int = 3,
        n_clients: int = 2,
        seed: int = 0,
        cluster_id: int = 7,
        requests_per_client: int = 8,
        config: Optional[ClusterConfig] = None,
        ledger_config: Optional[LedgerConfig] = None,
        batch_lanes: int = 64,
        net: Optional[PacketSimulator] = None,
        read_fault_probability: float = 0.0,
        misdirect_probability: float = 0.0,
        hash_log: bool = True,
        audit: bool = True,
        hot_transfers_capacity_max: Optional[int] = None,
        n_standbys: int = 0,
        viz: bool = False,
        scrub_interval: int = 0,
        merkle: bool = False,
        overload: Optional[dict] = None,
        byzantine: Optional[dict] = None,
        auth: Optional[dict] = None,
        machine_factory=None,
    ) -> None:
        self.workdir = workdir
        # Pluggable state-machine factory (vsr/replica.py): the model
        # checker (sim/mc.py) runs this same cluster — the production
        # consensus code — over its digest-chain machine stand-in.
        self.machine_factory = machine_factory
        self.n = n_replicas
        # Non-voting stream consumers at indexes [n, n + n_standbys)
        # (constants.zig:31-35); they journal + commit via the prepare
        # stream but never ack or vote, and may be PROMOTED into a voting
        # slot mid-schedule (VsrReplica.promote).
        self.n_standbys = n_standbys
        self.total = n_replicas + n_standbys
        self.seed = seed
        self.cluster_id = cluster_id
        self.config = config or TEST_MIN
        self.ledger_config = ledger_config or LEDGER_TEST
        self.batch_lanes = batch_lanes
        # Optional cold-tier cap: evictions + rehydration run under
        # consensus and crash/restart (BASELINE config-4 tiering).
        self.hot_transfers_capacity_max = hot_transfers_capacity_max
        # Device fault domain (docs/fault_domains.md): 0 = off (default —
        # pinned seeds replay bit-identically); N arms every replica's
        # scrub mirror at cadence N, enabling SDC detection and dispatch
        # recovery under the injectors below.
        self.scrub_interval = scrub_interval
        # Merkle commitment mode (docs/commitments.md): the scrub check
        # substrate becomes the on-device tree; at intervals > 1 there is
        # NO host mirror — SDC must be detected by root mismatch and
        # recovered through checkpoint + WAL replay.
        self.merkle = merkle
        # Overload fault domain (docs/fault_domains.md): when set, every
        # replica's ingress rides a BOUNDED admission queue drained with a
        # per-tick dispatch budget — the sim twin of a server whose event
        # loop admits finitely per scheduling quantum.  Keys:
        #   queue_cap         declared bound (the bounded-memory oracle
        #                     checks it every step)
        #   dispatch_budget   messages dispatched per replica per tick
        #   priority          class-aware drain/shed (vsr/overload.py) vs
        #                     plain FIFO tail drop — the negative control
        #                     the liveness oracle must demonstrably fail
        #   signal            shed client requests get explicit busy
        #                     replies; replicas run with overload_control
        # None (default): direct dispatch, bit-identical to every pinned
        # seed's schedule.
        self.overload = None
        self.admission: List = []
        self.overload_shed_busy = 0
        if overload is not None:
            from ..vsr.overload import AdmissionQueue

            self.overload = {
                "queue_cap": int(overload.get("queue_cap", 32)),
                "dispatch_budget": int(overload.get("dispatch_budget", 8)),
                "priority": bool(overload.get("priority", True)),
                "signal": bool(overload.get("signal", True)),
            }
            self.admission = [
                AdmissionQueue(
                    self.overload["queue_cap"], self.overload["priority"]
                )
                for _ in range(n_replicas + n_standbys)
            ]
            # Counters from queues retired by crash() (the queue's items
            # die with the replica, but its accounting must survive into
            # overload_stats() or the flood's heaviest window vanishes
            # from the oracles).
            self._admission_retired = {
                "admitted": 0, "shed": 0, "depth_peak": 0,
                "shed_by_class": {},
            }
        # Byzantine fault domain (docs/fault_domains.md): one replica's
        # egress is wrapped by a seeded ByzantineActor (its own rng stream:
        # seed ^ 0xB12A — arming it never shifts a base schedule's draws).
        # Keys: replica (index, default n-1), kinds, rate, window
        # ((start, end) ticks), verify — False is the NEGATIVE CONTROL: the
        # cluster delivers frames without checksum/source verification and
        # replicas skip their ingress checks, modeling a build whose
        # verification is broken so the same pinned attack schedule must
        # demonstrably fail the safety oracles.
        # Wire authentication (vsr/auth.py, docs/fault_domains.md "Byzantine
        # primary").  None (default): zero-MAC legacy wire, bit-identical to
        # every pinned seed.  A dict arms a deterministic cluster keychain
        # on every replica and MAC-stamps SOURCE_AUTHENTICATED egress in
        # _route.  Keys: ``strict`` (default True — unauthenticated replica
        # frames rejected, certified commits require ack certificates;
        # False = mixed-version accept-and-count), ``seed`` (keychain
        # derivation, default the cluster seed), ``off_replicas`` (iterable
        # of indexes left auth-OFF: the mixed-version degradation tests).
        self.auth_config: Optional[dict] = None
        self.auth_keychain = None
        self._auth_off: frozenset = frozenset()
        if auth is not None:
            from ..vsr.auth import Keychain

            a = dict(auth)
            a.setdefault("strict", True)
            self.auth_keychain = Keychain(
                cluster_id, seed=int(a.get("seed", seed))
            )
            self._auth_off = frozenset(a.get("off_replicas", ()))
            self.auth_config = a
        self.byzantine = None
        self._byz: Optional[ByzantineActor] = None
        # Ingress drop-and-count accounting (reason -> frames), always-on
        # for the sim's source-auth and decode rejections so oracles can
        # assert on it without the metrics registry.
        self.rejected_frames: Dict[str, int] = {}
        if byzantine is not None:
            b = dict(byzantine)
            self._byz = ByzantineActor(
                replica=int(b.get("replica", n_replicas - 1)),
                n_replicas=n_replicas,
                cluster_id=cluster_id,
                seed=seed ^ 0xB12A,
                kinds=b.get("kinds"),
                rate=float(b.get("rate", 0.2)),
                window=tuple(b.get("window", (0, 1 << 60))),
            )
            self._byz.verify = bool(b.get("verify", True))
            self.byzantine = b
        self.rng = random.Random(seed)
        self.net = net or PacketSimulator(seed=seed + 1)
        self.t = 0
        # One-line-per-event status grid (obs/vopr_viz): strictly read-only
        # over the cluster, so enabling it cannot shift a seed's schedule.
        self.viz = None
        if viz:
            from ..obs.vopr_viz import ClusterViz

            self.viz = ClusterViz()

        # Per-replica wall-clock offsets (exercise the Marzullo clock).
        self.wall_offsets = [
            self.rng.randrange(-40, 40) * 1_000_000 for _ in range(self.total)
        ]
        # One fault atlas across the cluster keeps injected storage faults
        # repairable (never a quorum of copies of one object).
        from .storage import FaultAtlas

        self.atlas = FaultAtlas(self.n)
        # The CORE (simulator.zig's Core): a view-change-quorum-sized set
        # of replicas exempt from STORAGE faults.  A fault on one quorum
        # member's copy of a committed op plus the OTHER member being
        # merely offline exceeds every protocol's budget (2 lost copies at
        # f=1) — the atlas alone cannot see crash overlap, so the standing
        # guarantee is a damage-free electable quorum.  The randomized
        # schedulers (sim/vopr.py, adversary tests) additionally refrain
        # from CRASHING core members while storage faults are active;
        # scripted tests without fault probabilities may crash anyone.
        from ..vsr.consensus import quorums

        core_size = quorums(self.n)[1]
        faults_requested = read_fault_probability or misdirect_probability
        if faults_requested and core_size >= self.n:
            # Exempting everyone would silently disable the requested
            # fault families (n <= 2): leave one replica faultable — such
            # tiny clusters have no surviving-quorum guarantee under
            # faults anyway.
            core_size = self.n - 1
        self.core = set(self.rng.sample(range(self.n), core_size))
        self.storages = [
            SimStorage(
                self.config, seed=seed * 101 + i, replica=i, atlas=self.atlas,
                read_fault_probability=(
                    0.0 if i in self.core else read_fault_probability
                ),
                misdirect_probability=(
                    0.0 if i in self.core else misdirect_probability
                ),
            )
            for i in range(self.total)
        ]
        # Divergence oracle: per-replica op->digest logs that SURVIVE
        # restarts (like the disk), so crash-replay digests are checked
        # against the original run (utils/hash_log.OpHashLog).
        from ..utils.hash_log import OpHashLog

        self.hash_logs = [
            OpHashLog() if hash_log else None for _ in range(self.total)
        ]
        # Op-ordered reply auditor (testing/auditor.py, auditor.zig's role):
        # every replica's commits — including crash-replays — are checked
        # bit-for-bit against each other and against the oracle model.
        from ..testing.auditor import Auditor

        self.auditor = Auditor() if audit else None
        # Flight recorders (obs/txtrace.Blackbox): one per replica SEAT,
        # surviving restarts like the disk and the hash logs, so a
        # postmortem dump carries the protocol history from BEFORE a
        # crash.  Pure ring appends (no clocks, no behavior change) —
        # pinned seeds replay bit-identically with the recorder on.
        self.blackboxes = [Blackbox(f"r{i}", cap=2048)
                          for i in range(self.total)]
        self.replicas: List[Optional[VsrReplica]] = [None] * self.total
        self.alive = [False] * self.total
        for i in range(self.total):
            VsrReplica.format(
                self._data_path(i),
                cluster=cluster_id,
                replica=i,
                replica_count=self.n,
                standby_count=self.n_standbys,
                cluster_config=self.config,
                storage=self.storages[i],
            )
            self.storages[i].sync()
            self.start(i)

        self.clients = {
            (seed * 1000 + 17 * (j + 1)) | 1: SimClient(
                client_id=(seed * 1000 + 17 * (j + 1)) | 1,
                cluster_id=cluster_id,
                n_replicas=self.n,
                seed=seed * 77 + j,
                n_requests=requests_per_client,
            )
            for j in range(n_clients)
        }
        for c in self.clients.values():
            self._wire_client(c)

    def _wire_client(self, client: SimClient) -> None:
        """Attach the lying-reply oracle: every reply a client ACCEPTS is
        cross-checked against the auditor's committed records."""
        if self.auditor is not None:
            client.reply_observer = self._observe_client_reply

    def _observe_client_reply(self, client_id, h, operation, body) -> None:
        if (
            self.auth_keychain is not None
            and self.auth_config["strict"]
            and not (self._byz is not None and not self._byz.verify)
            and int(h["replica"]) not in self._auth_off
        ):
            # Auditor cross-check (belt to the dispatch gate's braces):
            # under strict auth, every reply a client ACCEPTS must verify
            # under its claimed origin's key.
            assert self.auth_keychain.verify(h), (
                f"client {client_id} accepted a reply for op "
                f"{int(h['op'])} that fails MAC verification under "
                f"claimed origin {int(h['replica'])}"
            )
        self.auditor.observe_reply(
            int(h["op"]), operation.name, body,
            client=client_id, request=int(h["request"]),
        )

    def _data_path(self, i: int) -> str:
        return os.path.join(self.workdir, f"replica_{i}.data")

    # -- replica lifecycle ----------------------------------------------------

    def _make_replica(self, i: int) -> VsrReplica:
        def monotonic(i=i):
            return (self.t + 1) * TICK_NS

        def realtime(i=i):
            return WALL_EPOCH_NS + (self.t + 1) * TICK_NS + self.wall_offsets[i]

        replica = VsrReplica(
            self._data_path(i),
            cluster_config=self.config,
            ledger_config=self.ledger_config,
            batch_lanes=self.batch_lanes,
            storage=self.storages[i],
            monotonic=monotonic,
            realtime=realtime,
            seed=self.seed * 31 + i,
            hash_log=self.hash_logs[i],
            hot_transfers_capacity_max=self.hot_transfers_capacity_max,
            scrub_interval=self.scrub_interval,
            merkle=self.merkle or None,
            machine_factory=self.machine_factory,
        )
        # Virtual time: device-recovery backoff must never wall-sleep.
        replica.machine.retry_tick_s = 0
        # The seat's flight recorder rides across restarts.
        replica.blackbox = self.blackboxes[i]
        if self.merkle:
            # The VOPR merkle kind IS the mirror-off proof: even at the
            # interval-1 cadence, detection must come from root mismatch
            # and recovery from checkpoint + WAL replay.
            replica.machine.scrub_paranoid = False
        if self._byz is not None and not self._byz.verify:
            # Negative control: the consensus-level byzantine checks are
            # forced off along with the transport's (see step()).
            replica.ingress_verify = False
        if self.auth_keychain is not None and i not in self._auth_off:
            replica.auth = self.auth_keychain
            replica.auth_strict = bool(self.auth_config["strict"])
        if self.overload is not None:
            # One knob across the domain: the primary's shed points signal
            # busy exactly when the governor does.
            replica.overload_control = self.overload["signal"]
        if self.auditor is not None:
            def observe(op, operation, ts, body, results, replay, i=i):
                self.auditor.observe_commit(
                    op, operation, ts, body, results, replica=i, replay=replay
                )

            replica.commit_observer = observe
        return replica

    def start(self, i: int) -> None:
        assert not self.alive[i]
        self.replicas[i] = self._make_replica(i)
        self.replicas[i].open()
        self.alive[i] = True

    def crash(self, i: int) -> None:
        """Kill a replica: unsynced storage writes may tear
        (simulator.zig replica_crash_probability)."""
        assert self.alive[i]
        self.alive[i] = False
        self.storages[i].crash()
        self.replicas[i] = None
        if self.overload is not None:
            # A crashed replica's kernel buffers die with it — but its
            # shed/admitted accounting must not (overload_stats()).
            from ..vsr.overload import AdmissionQueue

            old = self.admission[i]
            retired = self._admission_retired
            retired["admitted"] += old.admitted
            retired["shed"] += old.shed
            retired["depth_peak"] = max(
                retired["depth_peak"], old.depth_peak
            )
            for cls, n in old.shed_by_class.items():
                retired["shed_by_class"][cls] = (
                    retired["shed_by_class"].get(cls, 0) + n
                )
            self.admission[i] = AdmissionQueue(
                self.overload["queue_cap"], self.overload["priority"]
            )

    def restart(self, i: int) -> None:
        self.start(i)

    def add_reconfigure_client(
        self, at_tick: int, new_rc: int, new_sc: int, seed: int = 0,
    ) -> int:
        """Attach a one-shot scripted client that submits a committed
        ``reconfigure`` op at ``at_tick`` — the LIVE membership-change
        path (docs/reconfiguration.md), as opposed to promote_standby's
        stopped-file surgery.  Id stream is distinct (seed ^ 0x2ECF) so
        base-client schedules stay untouched."""
        cid = ((seed ^ 0x2ECF) * 1000 + 29) | 1
        self.clients[cid] = OpenLoopClient(
            client_id=cid,
            cluster_id=self.cluster_id,
            n_replicas=self.n,
            seed=seed ^ 0x2ECF,
            script=[(
                at_tick,
                wire.Operation.reconfigure,
                wire.reconfigure_body(new_rc, new_sc),
            )],
        )
        self._wire_client(self.clients[cid])
        return cid

    def promote_standby(self, standby: int, voter_slot: int) -> None:
        """Promote a (stopped) standby's data file into a (stopped) voting
        slot — the in-sim twin of VsrReplica.promote + the operator moving
        the file to the retired voter's address (tests/test_standby.py).
        The standby index is retired permanently; the promoted node serves
        from ``voter_slot`` with everything it learned from the stream."""
        assert standby >= self.n and not self.alive[standby]
        assert voter_slot < self.n and not self.alive[voter_slot]
        from ..vsr.superblock import PROMOTION_SUSPECT_OP, SuperBlock

        sb = SuperBlock(self.storages[standby])
        state = sb.open()
        assert state.replica >= state.replica_count, "already a voter"
        state.replica = voter_slot
        # The promoted identity opens log_suspect until a canonical
        # start_view certifies it (seed 600919; VsrReplica.promote).
        state.log_adopted_op = PROMOTION_SUSPECT_OP
        sb.checkpoint(state)
        self.storages[standby].sync()
        # The promoted file now serves from the voter's ADDRESS slot; the
        # retired voter's old storage is discarded (new machine, same
        # address) and the standby index never runs again.
        self.storages[voter_slot] = self.storages[standby]
        self.hash_logs[voter_slot] = self.hash_logs[standby]
        self.start(voter_slot)

    def inject_device_sdc(self, i: int, rng) -> bool:
        """Flip one seeded bit in replica ``i``'s device-resident ledger
        (the device-SDC fault kind; sim/vopr.py schedules it).  Returns
        False when the replica is down or holds no live account yet."""
        if not self.alive[i] or self.replicas[i] is None:
            return False
        return self.replicas[i].machine.inject_sdc_bitflip(rng)

    def inject_dispatch_fault(self, i: int, n: int = 1) -> bool:
        """Arm ``n`` forced dispatch exceptions on replica ``i``'s machine
        (the next n device readbacks raise through the dispatch funnel)."""
        if not self.alive[i] or self.replicas[i] is None:
            return False
        self.replicas[i].machine.inject_device_faults(n)
        return True

    def partition(self, groups: List[List[int]]) -> None:
        self.net.partition([[("replica", r) for r in g] for g in groups])

    def heal(self) -> None:
        self.net.heal()

    # -- the tick loop (simulator.zig main loop) ------------------------------

    def _ingress_reject(self, reason: str) -> None:
        """Drop-and-count (never crash, never apply): the byzantine.*
        rejection family, mirrored in a plain dict so oracles can assert
        on it with the registry disabled."""
        self.rejected_frames[reason] = self.rejected_frames.get(reason, 0) + 1
        from ..obs.metrics import registry as _obs

        if _obs.enabled:
            _obs.counter(f"byzantine.rejected.{reason}").inc()

    def _source_ok(self, src, h, command: wire.Command) -> bool:
        """Transport-level source authentication (the sim twin of the
        cluster bus's pinned peer identity): a frame whose header asserts a
        voter identity must have arrived FROM that voter; client frames
        must carry their own sender's client id.  Relayed commands
        (prepare, forwarded requests, re-served replies) are exempt — their
        header origin is legitimately not the transport source."""
        skind, sid = src
        if skind == "replica":
            if command in wire.SOURCE_AUTHENTICATED_COMMANDS:
                if (
                    self.auth_keychain is not None
                    and self.auth_config["strict"]
                ):
                    # Strict auth: the MAC is the load-bearing identity
                    # check, so the transport pin is lifted — this is the
                    # adversarial-network model the tbmc byzantine-primary
                    # scope exhausts (a forged-identity frame must FAIL at
                    # _ingress_auth, not lean on transport pinning).
                    return True
                return int(h["replica"]) == sid
            return True
        if command in (wire.Command.request, wire.Command.ping_client):
            return wire.u128(h, "client") == sid
        return False

    def dispatch(self, src, dst, message: bytes) -> None:
        """Deliver ONE frame to its destination process: decode, transport
        source-auth, byzantine observation, admission, handler, route.
        This is the single-event cluster step — step() folds the packet
        simulator's due frames through it, and the model checker
        (sim/mc.py) replays explicit per-frame schedules through exactly
        the same path (docs/tbmc.md)."""
        unverified = self._byz is not None and not self._byz.verify
        kind, ident = dst
        if kind == "replica":
            if not self.alive[ident]:
                return
            try:
                if unverified:
                    # NEGATIVE CONTROL ONLY: parse without checksum or
                    # source verification (wire.decode_unverified).
                    h, command, body = wire.decode_unverified(message)
                else:
                    h, command, body = wire.decode(message)
            except ValueError as err:
                # Corrupt frame: dropped like a bad TCP peer — and
                # counted by reason (drop-and-count discipline).
                self._ingress_reject(getattr(err, "reason", "decode"))
                return
            if not unverified and not self._source_ok(src, h, command):
                self._ingress_reject("impersonation")
                return
            if self._byz is not None and ident == self._byz.replica:
                self._byz.observe_ingress(
                    h, command, body, message, self.t
                )
            if self.overload is not None:
                self._admit(ident, h, command, body)
                return
            try:
                out = self.replicas[ident].on_message(h, command, body)
            except JournalWriteFailure:
                # Persistently misdirected medium: fail-stop — the
                # replica crashes (and may be restarted by the fault
                # schedule); the cluster must survive it.
                self.crash(ident)
                return
            self._route(dst, out)
        else:
            client = self.clients.get(ident)
            if client is None:
                return
            try:
                if unverified:
                    h, command, body = wire.decode_unverified(message)
                else:
                    h, command, body = wire.decode(message)
            except ValueError as err:
                self._ingress_reject(getattr(err, "reason", "decode"))
                return
            if (
                command == wire.Command.reply
                and not unverified
                and self.auth_keychain is not None
                and self.auth_config["strict"]
                and int(h["replica"]) not in self._auth_off
            ):
                # Replies are MAC'd at CREATION under the committing
                # replica's key (vsr/replica._commit_prepare) and survive
                # verbatim re-serving, so under strict auth a client-bound
                # reply that fails its claimed origin's key is a forgery
                # (e.g. the byzantine actor's lie_reply): drop-and-count.
                if not self.auth_keychain.verify(h):
                    self._ingress_reject("unauthenticated_reply")
                    return
            client.on_message(h, command, body, self.t)

    def tick_replica(self, i: int) -> None:
        """Run one replica tick and route its output — the timer half of
        the cluster step (step() and the model checker share it)."""
        try:
            self._route(("replica", i), self.replicas[i].tick())
        except JournalWriteFailure:
            self.crash(i)

    def step(self) -> None:
        self.t += 1
        for src, dst, message in self.net.deliver(self.t):
            self.dispatch(src, dst, message)
        if self._byz is not None and self.alive[self._byz.replica]:
            for dst, message in self._byz.inject(self.t):
                self.net.send(
                    ("replica", self._byz.replica), dst, message, self.t
                )
        if self.overload is not None:
            self._drain_admission()
        for i in range(self.total):
            if self.alive[i]:
                self.tick_replica(i)
        for cid, client in self.clients.items():
            self._route(("client", cid), client.tick(self.t))
        if self.viz is not None:
            self.viz.sample(self)

    # -- overload governor (the fourth fault domain) ---------------------------

    def _admit(self, ident: int, h, command, body) -> None:
        """Offer an inbound message to replica ``ident``'s bounded
        admission queue; shed client requests get an explicit busy reply
        when signaling is on (everything else relies on sender timeouts)."""
        from ..vsr import overload as ovl  # deferred: only overload runs

        cls = ovl.classify(command)
        client = (
            wire.u128(h, "client") if command == wire.Command.request else 0
        )
        shed = self.admission[ident].offer(cls, client, (h, command, body))
        for scls, _sclient, (sh, scommand, _sbody) in shed:
            if (
                scls == ovl.CLASS_CLIENT
                and scommand == wire.Command.request
                and self.overload["signal"]
            ):
                replica = self.replicas[ident]
                busy = ovl.busy_message(
                    ident, self.cluster_id,
                    replica.view if replica is not None else 0,
                    sh, wire.BUSY_QUEUE,
                    retry_after_ticks=4 * self.overload["dispatch_budget"],
                )
                self.overload_shed_busy += 1
                self._route(
                    ("replica", ident),
                    [(("client", wire.u128(sh, "client")), busy)],
                )

    def _drain_admission(self) -> None:
        for i in range(self.total):
            q = self.admission[i]
            # Bounded-memory oracle: the declared cap holds at all times.
            assert len(q) <= q.cap, (
                f"replica {i} admission queue {len(q)} > declared cap "
                f"{q.cap}"
            )
            if not self.alive[i]:
                continue
            for _ in range(self.overload["dispatch_budget"]):
                item = q.pop()
                if item is None:
                    break
                _cls, _client, (h, command, body) = item
                try:
                    out = self.replicas[i].on_message(h, command, body)
                except JournalWriteFailure:
                    self.crash(i)
                    break
                self._route(("replica", i), out)

    def add_flood_clients(
        self,
        count: int,
        seed: int,
        n_requests: int = 4,
        retry_ticks: int = 4,
        start_tick: int = 0,
        batch: int = 8,
        aggressive: bool = True,
    ) -> List[int]:
        """Attach an aggressive client cohort (the overload fault's load):
        short retry cadence, activation at ``start_tick``.  Ids are derived
        from a DISTINCT stream (seed ^ 0xF100D) so base-client schedules
        stay untouched."""
        ids = []
        for j in range(count):
            cid = ((seed ^ 0xF100D) * 1000 + 13 * (j + 1)) | 1
            self.clients[cid] = SimClient(
                client_id=cid,
                cluster_id=self.cluster_id,
                n_replicas=self.n,
                seed=(seed ^ 0xF100D) * 77 + j,
                n_requests=n_requests,
                batch=batch,
                retry_ticks=retry_ticks,
                start_tick=start_tick,
                aggressive=aggressive,
            )
            self._wire_client(self.clients[cid])
            ids.append(cid)
        return ids

    def overload_stats(self) -> dict:
        """Governor accounting for oracles and metrics."""
        if self.overload is None:
            return {}
        shed_by_class: Dict[str, int] = {}
        from ..vsr.overload import CLASS_NAMES

        retired = self._admission_retired
        for cls, n in retired["shed_by_class"].items():
            shed_by_class[CLASS_NAMES[cls]] = n
        for q in self.admission:
            for cls, n in q.shed_by_class.items():
                name = CLASS_NAMES[cls]
                shed_by_class[name] = shed_by_class.get(name, 0) + n
        return {
            "admitted": retired["admitted"] + sum(
                q.admitted for q in self.admission
            ),
            "shed": retired["shed"] + sum(
                q.shed for q in self.admission
            ),
            "shed_by_class": shed_by_class,
            "depth_peak": max(
                retired["depth_peak"],
                *(q.depth_peak for q in self.admission),
            ),
            "busy_replies": self.overload_shed_busy,
            "client_busy_seen": sum(
                c.busy_seen for c in self.clients.values()
            ),
        }

    def _auth_stamp(self, sid: int, message: bytes) -> bytes:
        """MAC-stamp a SOURCE_AUTHENTICATED egress frame whose header
        claims the sending replica itself as origin.  Stamping sits AFTER
        the byzantine transform (see _route): the byz actor's own-identity
        forgeries legally carry valid MACs (it holds its own key), while
        forged-identity frames stay unstamped — the MAC layer, not the
        transport pin, must catch them."""
        if sid in self._auth_off or len(message) < wire.HEADER_SIZE:
            return message
        if (
            message[110] not in wire.SOURCE_AUTHENTICATED_BYTES
            or message[111] != sid
        ):
            return message
        return self.auth_keychain.stamp(message)

    def _route(self, src, envelopes) -> None:
        if self._byz is not None and src == ("replica", self._byz.replica):
            # The Byzantine wrapper owns this replica's egress: frames may
            # pass, corrupt, or fan out as conflicting forgeries.
            envelopes = self._byz.transform(envelopes, self.t)
        if self.auth_keychain is not None and src[0] == "replica":
            sid = src[1]
            envelopes = [
                (dst, self._auth_stamp(sid, m)) for dst, m in envelopes
            ]
        for dst, message in envelopes:
            self.net.send(src, dst, message, self.t)

    def run(self, ticks: int) -> None:
        for _ in range(ticks):
            self.step()

    def dump_blackboxes(self, directory: str,
                        prefix: str = "blackbox") -> List[str]:
        """Write every replica seat's flight-recorder history as
        ``<prefix>_r<i>.txt`` postmortem artifacts (docs/tracing.md); the
        VOPR calls this for failing seeds, next to the viz grid."""
        return _dump_blackboxes(self.blackboxes, directory, prefix=prefix)

    # -- oracles --------------------------------------------------------------

    def clients_done(self) -> bool:
        return all(c.done for c in self.clients.values())

    def converged(self) -> bool:
        live = [r for r, a in zip(self.replicas, self.alive) if a]
        if not live:
            return False
        if any(r.status != NORMAL for r in live):
            return False
        commits = {r.commit_min for r in live}
        if len(commits) != 1:
            return False
        digests = {r.machine.digest() for r in live}
        return len(digests) == 1

    def check_converged(self) -> None:
        """StateChecker: all live replicas at identical (commit_min, digest)."""
        live = [
            (i, r) for i, (r, a) in enumerate(zip(self.replicas, self.alive)) if a
        ]
        assert live, "no live replicas"
        states = {
            i: (r.commit_min, r.status, r.machine.digest()) for i, r in live
        }
        values = set(states.values())
        if len(values) != 1:
            from ..utils.hash_log import first_divergence

            logs = [log for log in self.hash_logs if log is not None]
            pin = first_divergence(logs) if logs else None
            raise AssertionError(
                f"replicas diverged: {states}"
                + (f"; first divergence at op {pin[0]}: "
                   f"{ {r: hex(d) for r, d in pin[1].items()} }" if pin else "")
            )

    def check_conservation(self) -> None:
        """Double-entry invariant: Σ debits_posted == Σ credits_posted and
        Σ debits_pending == Σ credits_pending over all accounts (shared
        oracle definition: utils/conservation.py)."""
        from ..utils.conservation import live_rows, u128_field_total

        for i, (r, a) in enumerate(zip(self.replicas, self.alive)):
            if not a:
                continue
            acc = r.machine.ledger.accounts
            live = live_rows(acc)
            assert u128_field_total(
                acc, "debits_posted", live
            ) == u128_field_total(acc, "credits_posted", live), (
                f"replica {i}: posted debits != credits"
            )
            assert u128_field_total(
                acc, "debits_pending", live
            ) == u128_field_total(acc, "credits_pending", live), (
                f"replica {i}: pending debits != credits"
            )

    def run_until(self, predicate, max_ticks: int = 20_000, step: int = 50) -> bool:
        for _ in range(0, max_ticks, step):
            self.run(step)
            if predicate():
                return True
        return False
