"""Replica: the VSR participant owning journal, state machine, and sessions.

Mirrors the reference replica's lifecycle and commit pipeline
(src/vsr/replica.zig): requests become prepares (op assigned, batch timestamp
from the clock, parent hash-chained — :1308-1337), prepares are journaled to
the WAL before execution (:1364+), commit runs the state machine and builds a
checksummed reply (:3678-3836), replies are stored per client session for
retry idempotency (client_sessions.zig), and every ``vsr_checkpoint_interval``
ops the ledger snapshot + superblock are made durable (:3153-3169).

This module is transport-agnostic and synchronous: `on_request(header, body)`
returns the messages to send.  The TCP message bus (net/) and the consensus
message flow for multi-replica clusters layer on top; single-replica mode
commits immediately after journaling (quorum of 1).

Recovery (`open`): superblock quorum read -> checkpoint snapshot load ->
journal scan -> replay the hash-chained suffix of the WAL beyond the
checkpoint (§3.1 of SURVEY).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import types
from ..config import ClusterConfig, LedgerConfig
from ..machine import DeviceStateUnrecoverable, TpuStateMachine
from ..obs.metrics import registry as _obs
from ..obs.txtrace import dump_blackboxes, txtrace
from ..utils.tracer import tracer
from . import checkpoint as checkpoint_mod
from . import wire
from .journal import Journal
from .storage import Storage
from .superblock import (
    PROMOTION_SUSPECT_OP, SuperBlock, SuperBlockState,
)

U64_MASK = 0xFFFF_FFFF_FFFF_FFFF


@dataclasses.dataclass
class Session:
    """One client's session (client_sessions.zig): session number is the
    commit number of its register op; the last reply is retained for retry
    idempotency."""

    client: int
    session: int           # commit number of the register prepare
    request: int           # most recent request number seen
    reply_bytes: bytes     # full wire reply (header+body) for that request
    slot: int = 0          # stable client_replies zone slot (0..clients_max-1)


class InvalidRequest(Exception):
    """Request rejected before journaling (malformed body / bad operation) —
    the reference drops such requests at header validation
    (message_header.zig Request.invalid_header)."""


class ForestDamage(RuntimeError):
    """Checkpoint files (manifest/base/runs/cold) are corrupt or missing.

    ``damage`` lists (kind, ident, expected_checksum) triples.  A solo
    replica treats this as fatal; a consensus replica repairs the files
    from peers via request_blocks/block (the reference's
    grid_blocks_missing.zig path) before falling back to full state sync.
    ``cold_paths`` maps a cold entry's expected checksum to its relative
    file name so the receiver knows where to install the fetched bytes
    (cold runs are addressed by checksum on the wire)."""

    def __init__(self, damage, cold_paths=None):
        super().__init__(f"checkpoint files damaged: {damage}")
        self.damage = damage
        self.cold_paths = cold_paths or {}


class Replica:
    def __init__(
        self,
        data_path: str,
        cluster_config: Optional[ClusterConfig] = None,
        ledger_config: Optional[LedgerConfig] = None,
        batch_lanes: int = 8192,
        # Production default; sim injects a seeded clock for replay.
        time_ns=time.time_ns,  # tblint: ignore[nondet]
        storage: Optional[Storage] = None,
        aof_path: Optional[str] = None,
        hash_log=None,
        hot_transfers_capacity_max: Optional[int] = None,
        process_config=None,
        host_engine: bool = False,
        scrub_interval: Optional[int] = None,
        merkle: Optional[bool] = None,
        machine_factory=None,
    ) -> None:
        self.data_path = data_path
        # Optional determinism oracle (utils/hash_log.OpHashLog): per-commit
        # ledger digests; wired by the VOPR cluster.
        self.hash_log = hash_log
        self.config = cluster_config or ClusterConfig()
        self.ledger_config = ledger_config or LedgerConfig()
        if batch_lanes < self.config.batch_max_create_transfers:
            # A wire-legal batch (bounded only by message_size_max) larger
            # than the kernel's lane count would assert inside the commit
            # path at runtime — the server would drop the connection, the
            # client would resend, forever.  Fail fast at startup instead.
            raise ValueError(
                f"batch_lanes={batch_lanes} < batch_max="
                f"{self.config.batch_max_create_transfers}: the commit "
                "kernel could not fit a maximum wire batch"
            )
        self.batch_lanes = batch_lanes
        self.time_ns = time_ns

        # Injectable storage lets the VOPR simulator substitute an in-memory
        # fault-injecting backend (testing/storage.zig's role).
        from ..config import PROCESS_DEFAULT

        self.process_config = process_config or PROCESS_DEFAULT
        self.storage = storage if storage is not None else Storage(
            data_path, self.config,
            direct_io=self.process_config.direct_io,
            direct_io_required=self.process_config.direct_io_required,
        )
        # LSM-equivalent durable layer: base snapshot + delta runs + manifest
        # (lsm/forest.py); full snapshots only at majors/capacity changes.
        from ..lsm.forest import Forest

        self.forest = Forest(data_path)
        # Optional append-only audit log of committed prepares (aof.zig).
        self.aof = None
        if aof_path:
            from .aof import AOF

            self.aof = AOF(aof_path)
        self.superblock = SuperBlock(self.storage)
        self.journal = Journal(self.storage)
        # ``machine_factory`` (default: the real TpuStateMachine) lets the
        # model checker (sim/mc.py) substitute its digest-chain stand-in —
        # the consensus/journal/session layers are what get explored, the
        # ledger folds to its digest (docs/tbmc.md).
        self.machine = (machine_factory or TpuStateMachine)(
            self.ledger_config, batch_lanes=batch_lanes,
            # Always derived from the data file (not from the CLI flag): a
            # restart WITHOUT --hot-transfers-log2-max must still be able to
            # reload a checkpoint whose cold_manifest references the spill.
            spill_dir=data_path + ".cold",
            hot_transfers_capacity_max=hot_transfers_capacity_max,
            # Native host data plane (host_engine.py): the solo-server OLTP
            # entry points opt in; sim/cluster replicas stay on the device
            # path (per-commit digests + tiering live there).
            host_engine=host_engine,
        )
        if scrub_interval is not None:
            # Device fault domain cadence (docs/fault_domains.md); the
            # mirror arms at the end of open(), once the restored state is
            # digest-verified and the WAL replayed.
            self.machine.scrub_interval = scrub_interval
        if merkle is not None:
            # Merkle commitment mode (docs/commitments.md): the scrub
            # substrate becomes the on-device incremental tree; the full
            # mirror survives only at the interval-1 paranoid cadence.
            self.machine.merkle_enabled = bool(merkle)

        self.cluster = 0
        self.replica = 0
        self.replica_count = 1
        self.standby_count = 0
        # Primary rotation offset (docs/reconfiguration.md): a committed
        # membership change keeps the current view's primary fixed under
        # the new modulus by adjusting this offset; persisted in the
        # superblock so restarts agree.
        self._primary_offset = 0
        # Membership this process OPENED with (refreshed from the
        # superblock on every open): read only by the tbmc
        # ``reconfig_stale_quorum`` knockout, which models a node sizing
        # its view-change quorum from the pre-reconfigure membership.
        self._boot_replica_count = 1
        # Wire authentication (vsr/auth.Keychain); None = zero-MAC legacy
        # wire.  The consensus layer (VsrReplica) adds the strict-mode
        # policy knobs; the base replica only needs the keychain to stamp
        # the replies it creates (_commit_prepare).
        self.auth = None
        # Optional commit observer (testing/auditor.py): called with every
        # committed op's (op, operation, timestamp, body, results, replay)
        # — the simulator's op-ordered reply auditor hooks in here.
        self.commit_observer = None
        # Optional flight recorder (obs/txtrace.Blackbox): attached by the
        # CLI server (TB_BLACKBOX), the simulator, and the consensus layer;
        # None = off (zero cost).  Dumped on device recovery, crash-path
        # exits, and on demand (dump_blackbox).
        self.blackbox = None
        # Overlapped checkpointing (single-replica TCP server only; see
        # checkpoint()).  _ckpt_thread holds the in-flight background write;
        # _ckpt_result its finished SuperBlockState until adopted.
        self.async_checkpoint = False
        self._last_group_fsync = None  # latest group-commit WAL barrier
        self._ckpt_thread = None
        # (SuperBlockState, cold_garbage) of a finished background write.
        self._ckpt_result = None
        self._ckpt_error: Optional[BaseException] = None
        # Captures taken at their aligned op while a write was still in
        # flight, awaiting their own background write (in order).
        self._ckpt_queue: List[tuple] = []
        # commit_min of the newest capture (see _checkpoint_due).
        self._ckpt_captured_op = 0
        # Cross-group commit pipeline (pipeline_depth >= 2, the TCP serving
        # engine; docs/commit_pipeline.md): at most ONE group's readbacks +
        # bookkeeping may be pending while the next group is admitted,
        # journaled, and dispatched.  Each in-flight entry is
        # (run, DeviceCommitHandle, its group's result_bodies dict).
        self._pipeline_inflight: List[tuple] = []
        self._pipeline_pending: Optional[dict] = None
        self.view = 0
        self.op = 0                 # latest journaled op
        self.commit_min = 0         # latest committed (executed) op
        self.op_checkpoint = 0
        self.parent_checksum = 0    # checksum of prepare at self.op
        self.sessions: Dict[int, Session] = {}
        self._sb_state: Optional[SuperBlockState] = None
        # Serializes superblock writers: the serving thread (_persist_view)
        # vs the background checkpoint thread.  See _superblock_install.
        import threading

        self._sb_lock = threading.Lock()

    # -- format / open -------------------------------------------------------

    @classmethod
    def format(
        cls,
        data_path: str,
        cluster: int,
        replica: int = 0,
        replica_count: int = 1,
        standby_count: int = 0,
        cluster_config: Optional[ClusterConfig] = None,
        storage: Optional[Storage] = None,
    ) -> None:
        """Create + initialize a data file (main.zig format path; the root
        prepare op=0 anchors the hash chain, message_header.zig Prepare.root)."""
        from .superblock import validate_membership

        config = cluster_config or ClusterConfig()
        validate_membership(replica, replica_count, standby_count)
        if storage is None:
            storage = Storage.format(data_path, config)
        try:
            superblock = SuperBlock(storage)
            superblock.format(cluster, replica, replica_count, standby_count)
            root = wire.new_header(
                wire.Command.prepare,
                cluster=cluster,
                op=0,
                operation=int(wire.Operation.root),
            )
            journal = Journal(storage)
            journal.write_prepare(wire.encode(root, b""))
        finally:
            storage.close()

    @classmethod
    def promote(cls, data_path: str, new_replica: int,
                cluster_config: Optional[ClusterConfig] = None) -> None:
        """Promote a STANDBY data file to voting index ``new_replica``.

        Rewrites the superblock identity in place, keeping the WAL and
        checkpoint the standby accumulated from the prepare stream — the
        promoted voter rejoins warm and repairs only the tail (the
        reference reserves standby promotion for operator reconfiguration,
        constants.zig:31-35; the operator must first retire any live
        replica that holds the target index).

        The promoted file opens LOG_SUSPECT (round-5 VOPR find, seed
        600919): the retired voter's journal — and the prepare_oks it
        contributed to commit quorums — is gone, so the promoted identity's
        (log_view, op) claim must not enter canonical selection until a
        view change carried by the REAL voters (whose quorum provably
        intersects every commit quorum) certifies its log via start_view.
        Without this, a view-change quorum of {other voter, promoted}
        could select a canonical log missing an op the retired voter had
        committed — the sweep caught exactly that as a double-commit
        divergence at the refilled op."""
        config = cluster_config or ClusterConfig()
        storage = Storage(data_path, config)
        try:
            superblock = SuperBlock(storage)
            state = superblock.open()
            if state.replica < state.replica_count:
                raise ValueError(
                    f"replica {state.replica} is already a voter"
                )
            if not (0 <= new_replica < state.replica_count):
                raise ValueError(
                    f"target index {new_replica} is not a voting slot "
                    f"(replica_count={state.replica_count})"
                )
            state.replica = new_replica
            state.log_adopted_op = PROMOTION_SUSPECT_OP
            superblock.checkpoint(state)
        finally:
            storage.close()

    def open(self) -> None:
        """Recover durable state: superblock -> checkpoint -> WAL replay."""
        recovery = self._open_durable_state()
        # Establish the head: the highest hash-chained op from the checkpoint.
        self._replay(recovery)
        # Arm the device fault domain from this VERIFIED state (checkpoint
        # digest checked + checksummed WAL replayed).  No-op at interval 0.
        self.machine.scrub_arm()

    def _open_durable_state(self):
        """Superblock quorum read + checkpoint snapshot load + journal scan
        (everything except WAL replay, which consensus defers until the
        replica knows how far the cluster committed)."""
        sb = self.superblock.open()
        self._sb_state = sb
        self.cluster = sb.cluster
        self.replica = sb.replica
        self.replica_count = sb.replica_count
        self.standby_count = sb.standby_count
        self._primary_offset = getattr(sb, "primary_offset", 0)
        self._boot_replica_count = self.replica_count
        self.view = sb.view
        self.op_checkpoint = sb.op_checkpoint
        self.commit_min = sb.op_checkpoint

        loaded = self._load_checkpoint_state(sb)
        if loaded is not None:
            ledger, meta = loaded
            self._install_checkpoint_ledger(ledger, meta, sb)
            self.sessions = {
                int(client_hex, 16): Session(
                    client=int(client_hex, 16),
                    session=s["session"],
                    request=s["request"],
                    reply_bytes=self._read_client_reply(s["slot"], s["reply_size"]),
                    slot=s["slot"],
                )
                for client_hex, s in meta.get("sessions", {}).items()
            }

        return self.journal.recover()

    def _load_checkpoint_state(self, sb) -> Optional[tuple]:
        """(ledger, meta) from the durable checkpoint, or None when no
        checkpoint exists (genesis).  Damage maps to ForestDamage (peer-
        repairable); shared by open() and recover_device_state()."""
        if sb is None or not (
            sb.op_checkpoint > 0 or sb.checkpoint_file_checksum != 0
        ):
            return None
        if sb.manifest_checksum:
            try:
                return self.forest.open(
                    sb.op_checkpoint, sb.manifest_checksum
                )
            except (OSError, RuntimeError, ValueError, KeyError) as err:
                # Only now pay for a full verify pass (the happy path
                # reads each file exactly once): enumerate what is
                # damaged so consensus can fetch it from peers.
                damage = self.forest.verify(
                    sb.op_checkpoint, sb.manifest_checksum
                )
                if damage:
                    raise ForestDamage(damage) from err
                raise
        # Legacy full-snapshot checkpoint (no manifest).
        ledger, meta = checkpoint_mod.load(
            self.data_path, sb.op_checkpoint, sb.checkpoint_file_checksum
        )
        # Seed the forest so state-sync can materialize this
        # checkpoint and the next checkpoint goes delta.
        self.forest.seed_base(
            ledger, sb.op_checkpoint, sb.checkpoint_file_checksum
        )
        return ledger, meta

    def _install_checkpoint_ledger(self, ledger, meta, sb) -> None:
        """Swap the checkpoint snapshot into the machine and verify its
        digest against the superblock anchor."""
        self.machine.ledger = ledger
        try:
            self.machine.restore_host_state(meta["machine"])
        except (OSError, RuntimeError, AssertionError) as err:
            # Cold-tier spill files are checkpoint state too: a restart
            # whose durable manifest references a missing/corrupt cold
            # run (crash between a sync install and its cold fetch, or
            # a damaged disk) must route to peer block repair like any
            # other checkpoint file — round-5 standby-sweep find: this
            # crashed the replica (and the whole sweep) instead.
            damage, cold_paths = self._verify_cold(meta)
            if damage:
                raise ForestDamage(damage, cold_paths=cold_paths) from err
            raise
        digest = self.machine.digest()
        if digest != sb.ledger_digest:
            raise RuntimeError(
                f"checkpoint digest mismatch: ledger {digest:#x} != "
                f"superblock {sb.ledger_digest:#x}"
            )
        want = meta.get("merkle_root")
        if want is not None:
            # Replay-free commitment verification: recompute the canonical
            # Merkle roots from the restored arrays (host numpy, no device
            # work) and compare against the captured commitment.
            from ..ops import merkle as merkle_mod

            got = merkle_mod.np_ledger_roots(ledger)
            exp = (
                int(want["accounts"]), int(want["transfers"]),
                int(want["posted"]),
            )
            if got != exp:
                raise RuntimeError(
                    "checkpoint merkle root mismatch: "
                    f"{[hex(g) for g in got]} != {[hex(e) for e in exp]}"
                )

    def _verify_cold(self, meta) -> tuple:
        """Enumerate damaged cold-tier run files referenced by a
        checkpoint's machine snapshot: (damage_triples, checksum->relpath).
        Wraps ColdStore.verify_manifest (one enumeration, incl. unsafe-path
        rejection); cold runs are requested from peers BY CHECKSUM (block
        kind 'cold'), so ident rides as 0 and the path map tells the
        receiver where to install the fetched bytes."""
        try:
            damaged = self.machine.cold.verify_manifest(
                meta.get("machine", {}).get("cold_manifest", [])
            )
        except ValueError:
            return [], {}  # hostile/unsafe manifest: not peer-repairable
        if any(not expect for _, expect in damaged):
            # A checksum-less entry cannot be addressed on the wire: no
            # peer-repair path — the caller re-raises toward state sync.
            return [], {}
        return (
            [("cold", 0, expect) for _, expect in damaged],
            {expect: name for name, expect in damaged},
        )

    def _restore_root(self):
        """Regenerate + rewrite the deterministic root prepare (op 0 is a
        pure function of the cluster id, replica.format): a latent fault on
        its WAL slot must not brick recovery."""
        root = wire.new_header(
            wire.Command.prepare,
            cluster=self.cluster,
            op=0,
            operation=int(wire.Operation.root),
        )
        raw = wire.encode(root, b"")
        self.journal.write_prepare(raw)
        h, _, _ = wire.decode(raw)
        entry = type("Entry", (), {})()
        entry.header = h
        entry.body = b""
        return entry

    def _replay(self, recovery) -> None:
        """Replay the contiguous, hash-chained WAL suffix beyond commit_min."""
        # Find the chain anchor: the entry at commit_min (or the root).
        anchor = recovery.entries.get(self.commit_min)
        if anchor is None and self.commit_min == 0:
            anchor = self._restore_root()
        if anchor is None:
            # The checkpoint op's slot was since overwritten by a newer op
            # (ring wrapped): it must chain from the checkpoint regardless —
            # the chain links below still verify each step.
            self.parent_checksum = 0
        else:
            self.parent_checksum = wire.header_checksum(anchor.header)
        self.op = self.commit_min

        op = self.commit_min + 1
        while op in recovery.entries:
            entry = recovery.entries[op]
            if entry.body is None:
                break  # faulty slot: torn write of an unacknowledged op
            parent = wire.u128(entry.header, "parent")
            if self.parent_checksum and parent != self.parent_checksum:
                break  # chain broken: stale entry from an older ring lap
            self._commit_prepare(entry.header, entry.body, replay=True)
            self.parent_checksum = wire.header_checksum(entry.header)
            self.op = op
            self.commit_min = op
            op += 1
            if self._checkpoint_due():
                # Keep checkpoint ops on the fixed op_checkpoint + interval
                # grid even through replay (see consensus._commit_journal).
                self.checkpoint()

    # -- request handling (the hot path, §3.2) -------------------------------

    def on_request(self, header: np.ndarray, body: bytes) -> List[bytes]:
        """Handle a verified client request; returns wire messages to send
        back (replica.zig on_request :1308-1337 + commit_op :3678-3836)."""
        self._settle_or_recover()  # strict op order vs any pipelined group
        self._scrub_poll()
        client = wire.u128(header, "client")
        try:
            operation = wire.Operation(int(header["operation"]))
            self._validate_request(operation, body)
        except (ValueError, InvalidRequest):
            # Malformed request: drop it *before* journaling — a journaled
            # prepare must always be executable, or replay would wedge.
            return []
        request_n = int(header["request"])

        session = self.sessions.get(client)
        if operation != wire.Operation.register:
            if session is None:
                # Unknown session: evict so the client re-registers.
                return [self._eviction(client, wire.EVICTION_NO_SESSION)]
            if int(header["session"]) != session.session:
                # MISMATCH echoes the offending session so a re-registered
                # client discards stale evictions about its OLD session
                # while a live duplicate-id client still surfaces the
                # violation (consensus.py keeps the same rule).
                return [self._eviction(
                    client, wire.EVICTION_SESSION_MISMATCH,
                    session=int(header["session"]),
                )]
            if request_n == session.request and session.reply_bytes:
                return [session.reply_bytes]  # duplicate: resend stored reply
            if request_n < session.request:
                return []  # stale: drop
        elif session is not None:
            # Duplicate register retry.
            if session.reply_bytes:
                return [session.reply_bytes]
            return []

        self._checkpoint_land_if_wal_full(1)
        if self.async_checkpoint:
            # Server mode: overlap the WAL fsync with the device kernel
            # (the prefetch-stage role, SURVEY §2 #16 — the reference
            # overlaps LSM prefetch IO with compute the same way).  The
            # prepare is WRITTEN before execution; only its fsync runs
            # concurrently, and the reply is withheld until both the
            # execution AND the fsync finished — a crash in the window
            # loses an op no client was ever answered for.
            prepare_h, prepare_body = self._prepare(header, body, operation,
                                                    sync=False)
            fsync = self._io_pool_submit(self._journal_sync_staged)
            reply = self._commit_prepare(prepare_h, prepare_body, replay=False)
            fsync.result()
        else:
            prepare_h, prepare_body = self._prepare(header, body, operation)
            reply = self._commit_prepare(prepare_h, prepare_body, replay=False)
        assert reply is not None
        out = [reply]
        if self._checkpoint_due():
            self.checkpoint()
        return out

    def on_request_group(
        self, requests: List[Tuple[np.ndarray, bytes]]
    ) -> List[List[bytes]]:
        """Group commit: journal every admitted request, ONE fsync for the
        group (overlapped with execution), replies withheld until both land.
        Blocking variant of on_request_group_pipelined."""
        out, fsync = self.on_request_group_pipelined(requests)
        if fsync is not None:
            fsync.result()
        return out

    @property
    def pipeline_depth(self) -> int:
        """Commit-pipeline depth (machine.pipeline_depth: TB_PIPELINE env,
        default 2, CLI --pipeline-depth).  Depth 1 routes every group
        through the sequential engine — bit-for-bit the pre-pipeline
        serving path."""
        return self.machine.pipeline_depth

    @pipeline_depth.setter
    def pipeline_depth(self, value: int) -> None:
        self.machine.pipeline_depth = value

    def on_request_group_pipelined(self, requests, deferred_replies=False):
        """Group commit with the durability barrier EXPOSED: returns
        (replies, fsync_future_or_None).  Replies must not be released to
        clients until the future resolves — but the caller may start the
        next group immediately, so a slow fsync (shared-disk latency spikes)
        costs bandwidth, never pipeline stalls.

        The reference's single-threaded data plane has the same shape:
        io_uring submission batching (src/io/linux.zig:33-110) keeps N
        prepares in flight sharing barriers, with replies gated on
        completion (replica.zig commit pipeline).  Reply lists are
        index-aligned with the input (empty list = dropped, client
        retries).

        With pipeline_depth >= 2 the admitted group commits through the
        pipelined engine (docs/commit_pipeline.md): the leading device run
        dispatches BEFORE the WAL writes (fsync/compute overlap) and codes
        readbacks are deferred.  With ``deferred_replies`` additionally
        True, the returned replies may be a concurrent.futures.Future of
        the reply list — group N's readbacks + bookkeeping then overlap
        group N+1's admission/journaling/dispatch, and the caller must
        await the future exactly like the fsync barrier (and call
        pipeline_flush() when its queue idles, or the last group's replies
        never come due).  The reply barrier is unchanged either way: a
        reply is released only after BOTH the group fsync and the op's
        execution."""
        out: List[List[bytes]] = [[] for _ in requests]
        admitted: List[Tuple[int, wire.Operation, np.ndarray, bytes]] = []
        self._checkpoint_land_if_wal_full(len(requests))
        self._scrub_poll()  # group boundary: the scrub cadence's home
        # Clients with an op in the still-pending group: their session
        # state (request number, stored reply) is not yet updated, so a
        # resend could double-commit — drop, the client retries (the
        # cross-group twin of the in-group duplicate guard below).
        busy = (
            {
                wire.u128(h, "client")
                for _i, h, _b in self._pipeline_pending["prepared"]
            }
            if self._pipeline_pending is not None else frozenset()
        )
        for i, (header, body) in enumerate(requests):
            client = wire.u128(header, "client")
            try:
                operation = wire.Operation(int(header["operation"]))
                self._validate_request(operation, body)
            except (ValueError, InvalidRequest):
                continue
            request_n = int(header["request"])
            session = self.sessions.get(client)
            if operation != wire.Operation.register:
                if session is None:
                    out[i] = [self._eviction(
                        client, wire.EVICTION_NO_SESSION
                    )]
                    continue
                if int(header["session"]) != session.session:
                    # Session-echoing MISMATCH (same rule as on_request
                    # and consensus.py).
                    out[i] = [self._eviction(
                        client, wire.EVICTION_SESSION_MISMATCH,
                        session=int(header["session"]),
                    )]
                    continue
                if client in busy:
                    continue
                if request_n == session.request and session.reply_bytes:
                    out[i] = [session.reply_bytes]
                    continue
                if request_n < session.request:
                    continue
                # A client pipelining into the same group twice (protocol
                # violation: one in-flight request per session) would race
                # its own session state; only the first is admitted.
                if any(
                    wire.u128(h, "client") == client
                    for _, _, h, _ in admitted
                ):
                    continue
            elif session is not None:
                if session.reply_bytes:
                    out[i] = [session.reply_bytes]
                continue
            admitted.append((i, operation, header, body))
        if not admitted:
            # No new commits — but duplicate-resend replies above may belong
            # to a group whose fsync is still in flight; gate them on the
            # latest barrier (>= their own group's, the IO pool is FIFO) so
            # a reconnecting client cannot observe a reply ahead of its
            # durability.
            last = self._last_group_fsync
            if last is not None and not last.done():
                return out, last
            return out, None
        if self.blackbox is not None:
            self.blackbox.record("group", n=len(admitted), op=self.op,
                                 depth=self.pipeline_depth)
        if self.pipeline_depth > 1 and self.hash_log is None:
            return self._commit_group_pipelined(admitted, out,
                                                deferred_replies)
        return self._commit_group_sequential(admitted, out)

    def _commit_group_sequential(self, admitted, out):
        """Depth-1 commit engine: journal every admitted request, ONE fsync
        for the group, then execute + reply strictly per op — the
        pre-pipeline serving path, preserved bit-for-bit (and the path the
        determinism oracle requires: per-op digests must capture per-op
        effects)."""
        self._pipeline_settle()  # a depth change mid-run must not reorder
        prepared = []
        # Here ``prepare`` holds the journal appends too (this engine
        # writes each prepare as it is assigned).
        with txtrace.stage("prepare", n=len(admitted)):
            for i, operation, header, body in admitted:
                prepare_h, prepare_body = self._prepare(
                    header, body, operation, sync=False
                )
                prepared.append((i, prepare_h, prepare_body))
        fsync = self._io_pool_submit(
            self._journal_sync_staged, txtrace.group_seq
        )
        self._last_group_fsync = fsync
        runs = self._group_device_runs(prepared)
        precomputed: Dict[int, bytes] = {}
        for j, (i, prepare_h, prepare_body) in enumerate(prepared):
            run = runs.get(j)
            if run is not None:
                # The run's device dispatch executes HERE, at its position
                # in op order — never in a pre-pass: an interleaved
                # non-transfer op (a lookup, a create_accounts) must
                # observe exactly the ops before it, or replies diverge
                # from backups' and crash-replay's strict op-order
                # execution.
                res = self.machine.commit_group_fast(
                    [r[1] for r in run], [r[2] for r in run]
                )
                if res is not None:
                    for (jj, _b, _t), results in zip(run, res):
                        precomputed[jj] = _encode_results(results)
            reply = self._commit_prepare(
                prepare_h, prepare_body, replay=False,
                result_body=precomputed.get(j),
            )
            assert reply is not None
            out[i] = [reply]
        if self._checkpoint_due():
            self.checkpoint()
        return out, fsync

    def _commit_group_pipelined(self, admitted, out, deferred_replies):
        try:
            return self._commit_group_pipelined_inner(
                admitted, out, deferred_replies
            )
        except BaseException as err:
            # A failed group must not strand an earlier group's reply
            # promise (the bus flush task would await it forever).
            self._pipeline_abort(err)
            raise

    def _commit_group_pipelined_inner(self, admitted, out, deferred_replies):
        """Pipelined commit engine (depth >= 2): three overlaps, one reply
        barrier.

        1. fsync/compute overlap — ops and prepare headers are assigned
           first, the LEADING device runs dispatch (the whole prefix up
           to the first non-deferrable op), and only then are the
           group's WAL writes + fsync issued: the journal IO of group N
           runs while group N's device dispatches are in flight.  Safe: the
           device ledger is volatile (durable state only moves at
           checkpoints, which settle the pipeline first), and no reply is
           released before both the fsync and the execution — a crash in
           the window loses ops no client was ever answered for, exactly
           the pre-pipeline recovery semantics.
        2. deferred D2H readback — device runs return DeviceCommitHandles
           executing on the machine's dispatch lane; with
           ``deferred_replies`` the whole group's readbacks + bookkeeping
           stay PENDING past return (replies become a Future the caller
           awaits like the fsync barrier), so group N's readbacks and
           reply construction overlap group N+1's admission, journaling,
           and dispatch.  Handles resolve in dispatch order (commit
           timestamps and index appends are op-ordered).
        3. every op still EXECUTES at its position in op order: a
           non-deferrable op (lookup, create_accounts, a refused run)
           first drains the in-flight handles — its results must observe
           exactly the ops before it, and a query must see their index
           appends.

        Bookkeeping + reply construction (phase B) then run per op in
        order via _commit_prepare with the precomputed result bodies —
        either before return (blocking callers) or when the pending group
        comes due (next call / pipeline_flush)."""
        pending = self._pipeline_pending
        if pending is not None and (
            pending["last_op"]
            - max(self.op_checkpoint, self._ckpt_captured_op)
            >= self.config.vsr_checkpoint_interval
        ):
            # The pending group's bookkeeping crosses a checkpoint
            # boundary: settle + checkpoint BEFORE dispatching anything
            # new — the capture must see a ledger exactly at its
            # commit_min, never one with a newer group's effects applied.
            if _obs.enabled:
                _obs.counter("pipeline.stall.checkpoint").inc()
            self.pipeline_flush()
        messages: List[bytes] = []
        prepared = []
        inflight = self._pipeline_inflight
        result_bodies: Dict[int, bytes] = {}
        skip: set = set()
        runs: Dict[int, List[Tuple]] = {}
        # Overlap #1: the leading run's dispatch goes to the lane BEFORE
        # the WAL writes in the finally (and before the previous group's
        # bookkeeping).  The WHOLE header-assign + lead-dispatch section
        # rides the try: whatever fails, every op that advanced self.op
        # has its encoded message journaled — self.op and the WAL must
        # never disagree, or the next group's hash chain points at ops
        # recovery cannot find.
        try:
            with txtrace.stage("prepare", n=len(admitted)):
                for i, operation, header, body in admitted:
                    prepare_h, prepare_body = self._prepare(
                        header, body, operation, sync=False,
                        defer_write=messages
                    )
                    prepared.append((i, prepare_h, prepare_body))
                runs = self._group_device_runs(prepared, single_ok=True)
            if _obs.enabled:
                _obs.gauge("pipeline.depth").set(self.pipeline_depth)
                _obs.counter("pipeline.groups").inc()
            # The LEADING PREFIX of device runs — every run up to the
            # first non-deferrable op — dispatches here, before the WAL
            # writes and before the previous group's readbacks come due:
            # while the serving thread sits in group N-1's resolves
            # (one device round trip apiece), the lane executes
            # ALL of group N's prefix, not just its first run.  Op order
            # is preserved: only consecutive leading runs dispatch early
            # (a run past a non-deferrable op still dispatches at its own
            # position in phase A, after that op's barrier drain).
            j = 0
            while j in runs:
                run = runs[j]
                handle = self._dispatch_run(run, prepared)
                if handle is None:
                    break  # refused: its ops execute inline in phase A
                self._pipeline_track(run, handle, result_bodies, skip)
                j += len(run)
        finally:
            with txtrace.stage("wal_write", n=len(messages)):
                for message in messages:
                    self.journal.write_prepare(message, sync=False)
        fsync = self._io_pool_submit(
            self._journal_sync_staged, txtrace.group_seq
        )
        self._last_group_fsync = fsync

        def drain(reason: str) -> None:
            if inflight and _obs.enabled:
                _obs.counter(f"pipeline.stall.{reason}").inc()
                if self.machine.shards:
                    # Per-shard commit-lane stall twin: every shard's lane
                    # drains together (replicated dispatch), so one series
                    # covers the mesh (docs/observability.md).
                    _obs.counter(f"pipeline.shard.stall.{reason}").inc()
            while inflight:
                self._pipeline_retire()

        # The previous group comes due: its dispatches ran ahead of ours
        # on the FIFO lane, so its readbacks + bookkeeping + reply promise
        # land now — while OUR lead executes.
        self._pipeline_finish_pending()

        # Phase A: op-order execution; device runs defer their readbacks.
        for j, (i, prepare_h, prepare_body) in enumerate(prepared):
            if j in skip:
                continue
            run = runs.get(j)
            if run is not None and j != 0:
                handle = self._dispatch_run(run, prepared)
                if handle is not None:
                    self._pipeline_track(run, handle, result_bodies, skip)
                    continue
                if _obs.enabled:
                    _obs.counter("pipeline.stall.refusal").inc()
                # Refused run (mid-run fast-path refusal, tiering, ...):
                # its ops fall through to per-op execution at their own
                # positions below.
            operation = wire.Operation(int(prepare_h["operation"]))
            if operation in (wire.Operation.register, wire.Operation.root):
                continue  # no state-machine execution; bookkeeping-only
            # Overlap #3 barrier: this op's results must observe every
            # prior op's effects AND index appends.
            drain("barrier")
            t0 = time.perf_counter_ns() if _obs.enabled else 0  # tblint: ignore[nondet] metrics
            with tracer.span("state_machine_commit",
                             op=int(prepare_h["op"]),
                             operation=operation.name):
                result_bodies[j] = self._execute(
                    operation, prepare_body, int(prepare_h["timestamp"])
                )
            if _obs.enabled:
                _obs.histogram("replica.commit_us", "us").observe(
                    (time.perf_counter_ns() - t0) / 1e3  # tblint: ignore[nondet] metrics
                )

        if deferred_replies and inflight:
            # Group N stays pending: readbacks + bookkeeping + replies
            # come due with group N+1 (or pipeline_flush when the queue
            # idles).  The reply barrier is unchanged — the caller awaits
            # the promise AND the fsync before releasing anything.
            import concurrent.futures

            promise: "concurrent.futures.Future" = (
                concurrent.futures.Future()
            )
            self._pipeline_pending = {
                "prepared": prepared,
                "out": out,
                "result_bodies": result_bodies,
                "promise": promise,
                "last_op": int(prepared[-1][1]["op"]),
                # For the spans of its phase B, which runs inside a later
                # group's call.
                "seq": txtrace.group_seq,
            }
            return promise, fsync

        drain("flush")
        self._pipeline_phase_b(prepared, result_bodies, out,
                               txtrace.group_seq)
        if self._checkpoint_due():
            self.checkpoint()
        return out, fsync

    # -- pipelined-engine plumbing (docs/commit_pipeline.md) ------------------

    @property
    def pipeline_pending(self) -> bool:
        """True while a commit group's readbacks/bookkeeping are deferred
        (the bus polls this to flush when its request queue idles)."""
        return self._pipeline_pending is not None or bool(
            self._pipeline_inflight
        )

    def pipeline_flush(self) -> None:
        """Drain the pipelined commit engine: resolve every in-flight
        device readback, run the pending group's bookkeeping + replies
        (fulfilling its reply promise), and take any checkpoint that came
        due.  No-op when nothing is pending.  Called by the bus when the
        request queue idles, by every blocking commit entry point, and by
        close()."""
        self._settle_or_recover()
        if self._checkpoint_due():
            self.checkpoint()

    def _settle_or_recover(self) -> None:
        """_pipeline_settle, routing a device-fault escalation raised while
        resolving deferred handles (mirror suspect / cold tier active —
        DeviceStateUnrecoverable) into the durable-state rebuild instead of
        crashing the serving path.  The failed group was already aborted by
        the settle (reply promises failed, clients retry); recovery
        restores the committed prefix and serving continues."""
        try:
            self._pipeline_settle()
        except DeviceStateUnrecoverable:
            self.recover_device_state()

    def _pipeline_settle(self) -> None:
        """Resolve all in-flight handles + pending bookkeeping WITHOUT the
        checkpoint-due check (checkpoint() itself calls this; the due
        check there would recurse)."""
        try:
            while self._pipeline_inflight:
                self._pipeline_retire()
            self._pipeline_finish_pending()
        except BaseException as err:
            self._pipeline_abort(err)
            raise

    def _pipeline_track(self, run, handle, result_bodies, skip) -> None:
        if _obs.enabled:
            _obs.counter("pipeline.dispatches").inc()
            _obs.histogram("pipeline.inflight", "handles").observe(
                len(self._pipeline_inflight) + 1
            )
        skip.update(jj for jj, _b, _t in run)
        self._pipeline_inflight.append((run, handle, result_bodies))

    def _pipeline_retire(self) -> None:
        """Resolve the OLDEST in-flight run (dispatch order == op order)
        into its group's result bodies.  The resolve IS the deferred ops'
        commit stage, so it carries the commit-stage series/span the
        blocking path records per op (one observation per run here)."""
        run, handle, result_bodies = self._pipeline_inflight.pop(0)
        t0 = time.perf_counter_ns() if _obs.enabled else 0  # tblint: ignore[nondet] metrics
        with tracer.span("state_machine_commit", deferred=True,
                         operation="create_transfers", batches=len(run)):
            results = handle.resolve()
        if _obs.enabled:
            # Queue wait (the join) is pipeline idle time, NOT commit
            # work: it rides txtrace.stage.dispatch_wait; commit_us must
            # stay comparable with the blocking path's execution-only
            # series.
            _obs.histogram("replica.commit_us", "us").observe(max(
                (time.perf_counter_ns() - t0) / 1e3  # tblint: ignore[nondet] metrics
                - handle.join_wait_s * 1e6, 0.0,
            ))
        for (jj, _b, _t), res in zip(run, results):
            result_bodies[jj] = _encode_results(res)

    def _pipeline_finish_pending(self) -> None:
        """Run the pending group's remaining readbacks + phase B and
        fulfill its reply promise."""
        pending = self._pipeline_pending
        if pending is None:
            return
        # Its handles are the oldest in-flight entries (FIFO): resolve
        # exactly those — a newer group's may already be queued behind.
        while self._pipeline_inflight and (
            self._pipeline_inflight[0][2] is pending["result_bodies"]
        ):
            self._pipeline_retire()
        self._pipeline_pending = None
        try:
            self._pipeline_phase_b(
                pending["prepared"], pending["result_bodies"], pending["out"],
                pending["seq"],
            )
        except BaseException as err:
            # The promise must ALWAYS resolve (the bus flush task awaits
            # it); _pipeline_abort can no longer see this group — pending
            # was just detached — so fail it here and re-raise.
            if not pending["promise"].done():
                pending["promise"].set_exception(
                    RuntimeError(f"pipelined group commit failed: {err!r}")
                )
            raise
        pending["promise"].set_result(pending["out"])

    def _pipeline_phase_b(self, prepared, result_bodies, out,
                          seq: int = 0) -> None:
        """Phase B: bookkeeping + reply construction, strictly in op
        order.  The reply barrier is unchanged: the caller withholds these
        until the group fsync resolves.  ``seq``: the group's sequence
        number at the bus, for the span."""
        with txtrace.stage("phase_b", seq=seq, n=len(prepared)):
            for j, (i, prepare_h, prepare_body) in enumerate(prepared):
                reply = self._commit_prepare(
                    prepare_h, prepare_body, replay=False,
                    result_body=result_bodies.get(j),
                )
                assert reply is not None
                out[i] = [reply]

    def _pipeline_abort(self, err) -> None:
        """Engine failure: QUIESCE in-flight handles (join their lane
        dispatches — an orphaned closure would keep mutating the machine's
        ledger concurrently with the serving thread — and release their
        staging sets) and fail the pending reply promise so its flush task
        unblocks (the bus then drops those connections — clients retry,
        exactly the group-failure discipline)."""
        for _run, handle, _rb in self._pipeline_inflight:
            handle.discard()
        self._pipeline_inflight.clear()
        pending, self._pipeline_pending = self._pipeline_pending, None
        if pending is not None and not pending["promise"].done():
            pending["promise"].set_exception(
                RuntimeError(f"pipelined group commit failed: {err!r}")
            )

    def _dispatch_run(self, run, prepared=None):
        """Dispatch one device run deferred; returns a DeviceCommitHandle
        or None (not eligible — the engine executes the ops inline)."""
        machine = self.machine
        batches = [b for _jj, b, _t in run]
        timestamps = [t for _jj, _b, t in run]
        if len(run) == 1:
            handle = machine.commit_fast_deferred(batches[0], timestamps[0])
        else:
            handle = machine.commit_group_fast(
                batches, timestamps, deferred=True
            )
        if handle is not None and prepared is not None and txtrace.active:
            # Bind traced ops of this run into their causal chains at the
            # moment the run enters the FIFO dispatch lane — the deferred
            # engine's twin of the replica.execute span (docs/tracing.md).
            for jj, _b, _t in run:
                trace = int(prepared[jj][1]["trace"])
                if trace:
                    txtrace.hop(trace, "replica.dispatch_lane",
                                replica=self.replica,
                                op=int(prepared[jj][1]["op"]),
                                run_len=len(run))
        return handle

    def _group_device_runs(
        self, admitted, single_ok: bool = False
    ) -> Dict[int, List[Tuple]]:
        """Identify runs of consecutive create_transfers prepares for the
        grouped device dispatch (machine.commit_group_fast): per-op dispatch
        pays one host<->device round trip per batch — grouping amortizes it
        across the whole commit group.  Returns {first_admitted_index: run} where
        run = [(admitted_index, batch, timestamp), ...]; the commit loop
        dispatches each run when it REACHES it, preserving op order.
        Results are bit-identical to per-op commits (loop order == op
        order, per-op prepare timestamps ride along), and a run of k costs
        k steps of the device loop, whatever GROUP_K is: cutting a group
        into two runs costs one more dispatch and join, not a second
        GROUP_K of steps.

        ``single_ok`` (the pipelined engine): length-1 runs are emitted
        too — a lone create_transfers op dispatches DEFERRED through the
        per-batch fast kernel (machine.commit_fast_deferred), so its
        readback overlaps as a grouped run's does.  The longest run is the
        machine's GROUP_K; a stand-in machine that cannot group
        (sim/mc.py: GROUP_K = 1) gets runs of one at most."""
        runs: Dict[int, List[Tuple]] = {}
        if self.hash_log is not None:
            # The determinism oracle records a per-op ledger digest at
            # commit time; a grouped dispatch applies the whole run before
            # the per-op bookkeeping, so every digest but the run's last
            # would capture later ops' effects and false-alarm against
            # strict per-op replicas.  The oracle outranks the serving
            # optimization.
            return runs
        min_len = 1 if single_ok else 2
        max_len = getattr(self.machine, "GROUP_K", 1)
        run: List[Tuple[int, np.ndarray, int]] = []

        def flush() -> None:
            if len(run) >= min_len:
                runs[run[0][0]] = list(run)
            run.clear()

        for j, (_i, h, body) in enumerate(admitted):
            if (
                wire.Operation(int(h["operation"]))
                == wire.Operation.create_transfers
            ):
                if len(run) >= max_len:
                    flush()
                run.append((
                    j,
                    np.frombuffer(body, dtype=types.TRANSFER_DTYPE),
                    int(h["timestamp"]),
                ))
            else:
                flush()
        flush()
        return runs

    def _io_pool_submit(self, fn, *args):
        if getattr(self, "_io_pool", None) is None:
            import concurrent.futures

            self._io_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tb-wal-fsync"
            )
        return self._io_pool.submit(fn, *args)

    def _journal_sync_staged(self, seq: int = 0):
        """journal.sync under the ``wal_fsync`` stage — the stage times the
        durability barrier itself (it runs on the IO pool thread), not the
        serving thread's wait for it.  ``seq``: the group that submitted
        it."""
        with txtrace.stage("wal_fsync", seq=seq):
            return self.journal.sync()

    def _prepare(
        self, request_h: np.ndarray, body: bytes, operation: wire.Operation,
        sync: bool = True, defer_write: Optional[List[bytes]] = None,
    ) -> Tuple[np.ndarray, bytes]:
        """Assign op + timestamp, hash-chain, and journal the prepare.

        ``defer_write``: collect the encoded message instead of writing it
        — the pipelined engine journals the whole group AFTER dispatching
        its leading device run, so the WAL IO overlaps device compute (the
        op/chain assignment here stays strictly ordered either way)."""
        # The pre-execution stage (the reference pipeline's prefetch slot:
        # everything between request admission and the state machine —
        # timestamp assignment, hash chain, WAL write).
        # Wall time feeds only the metrics registry, never replica state.
        t0 = time.perf_counter_ns() if _obs.enabled else 0  # tblint: ignore[nondet]
        op = self.op + 1
        count = self._event_count(operation, body)
        timestamp = self.machine.prepare(
            _OP_NAMES.get(operation, "other"), count, self.time_ns()
        )
        h = wire.new_header(
            wire.Command.prepare,
            cluster=self.cluster,
            view=self.view,
            parent=self.parent_checksum,
            request_checksum=wire.header_checksum(request_h),
            client=wire.u128(request_h, "client"),
            op=op,
            commit=self.commit_min,
            timestamp=timestamp,
            request=int(request_h["request"]),
            operation=int(operation),
        )
        h["replica"] = self.replica
        trace = int(request_h["trace"])
        if trace:
            # Sampled request: the trace id rides onto the prepare (and
            # from there onto the reply), inside the header-checksum
            # domain — one causal chain per request (obs/txtrace.py).
            h["trace"] = trace
            txtrace.hop(trace, "replica.prepare", replica=self.replica,
                        op=op)
        message = wire.encode(h, body)
        if defer_write is None:
            self.journal.write_prepare(message, sync=sync)
        else:
            defer_write.append(message)
        decoded, _ = wire.decode_header(message)
        self.op = op
        self.parent_checksum = wire.header_checksum(decoded)
        if _obs.enabled:
            _obs.histogram("replica.prefetch_us", "us").observe(
                (time.perf_counter_ns() - t0) / 1e3  # tblint: ignore[nondet] metrics
            )
        return decoded, body

    def _commit_prepare(
        self, header: np.ndarray, body: bytes, replay: bool,
        result_body: Optional[bytes] = None,
    ) -> Optional[bytes]:
        """Execute a journaled prepare; returns the reply message (stored in
        the session table either way).  ``result_body`` carries a result
        already produced by the grouped device dispatch
        (the grouped run dispatch in on_request_group_pipelined) — the state
        machine was applied there, so
        only the bookkeeping half (AOF, commit_min, session reply) runs
        here."""
        op = int(header["op"])
        operation = wire.Operation(int(header["operation"]))
        timestamp = int(header["timestamp"])
        client = wire.u128(header, "client")

        if operation == wire.Operation.root:
            return None
        if self.aof is not None:
            # Audit append BEFORE execution (replica.zig:3741-3746) — also
            # during replay, so a crash between journaling and appending
            # can't leave a committed op missing from the audit log.  The
            # resulting crash-replay duplicates are exact byte copies and
            # aof.iterate() dedupes them by checksum.
            self.aof.append(wire.encode(header, body))
        if operation == wire.Operation.register:
            result_body = b""
            self.commit_min = op
            session = Session(
                client=client, session=op, request=0, reply_bytes=b""
            )
            self._admit_session(session)
        elif operation == wire.Operation.reconfigure:
            result_body = self._apply_reconfigure(header, body)
            self.commit_min = op
            if _obs.enabled:
                _obs.counter("replica.commits").inc()
        else:
            if result_body is None:
                t0 = time.perf_counter_ns() if _obs.enabled else 0  # tblint: ignore[nondet] metrics
                with tracer.span("state_machine_commit", op=op,
                                 operation=operation.name):
                    # The kernel slice of a traced request's causal chain
                    # (docs/tracing.md): a real-duration span bound into
                    # the flow — the grouped/deferred engine's twin is the
                    # replica.dispatch_lane hop (_dispatch_run).
                    with txtrace.span(int(header["trace"]),
                                      "replica.execute",
                                      replica=self.replica, op=op):
                        result_body = self._execute(
                            operation, body, timestamp
                        )
                if _obs.enabled:
                    _obs.histogram("replica.commit_us", "us").observe(
                        (time.perf_counter_ns() - t0) / 1e3  # tblint: ignore[nondet] metrics
                    )
            self.commit_min = op
            if _obs.enabled:
                _obs.counter("replica.commits").inc()
                count = self._event_count(operation, body)
                if count:
                    _obs.histogram(
                        "replica.batch_events", "events"
                    ).observe(count)
            if self.hash_log is not None and operation in (
                wire.Operation.create_accounts,
                wire.Operation.create_transfers,
            ):
                # Determinism oracle (testing/hash_log.zig): per-op ledger
                # digests pinpoint the FIRST diverging commit across
                # replicas or across a crash-replay (sim/cluster.py).
                self.hash_log.record(op, int(self.machine.digest()))

        if self.commit_observer is not None:
            self.commit_observer(
                op, operation.name, timestamp, body, result_body, replay
            )

        reply_h = wire.new_header(
            wire.Command.reply,
            cluster=self.cluster,
            view=self.view,
            request_checksum=wire.u128(header, "request_checksum"),
            context=wire.header_checksum(header),
            client=client,
            op=op,
            commit=self.commit_min,
            timestamp=timestamp,
            request=int(header["request"]),
            operation=int(operation),
            # Continuous client-side auditing (docs/commitments.md): the
            # canonical accounts commitment root rides every reply —
            # carved from reserved padding, 0 when commitments are off,
            # so merkle-off serving stays bit-identical to pre-root wire.
            root=self.machine.commitment_root(),
        )
        reply_h["replica"] = self.replica
        trace = int(header["trace"])
        if trace:
            reply_h["trace"] = trace
            txtrace.hop(trace, "replica.reply", replica=self.replica, op=op)
        reply = wire.encode(reply_h, result_body)
        if self.auth is not None:
            # Stamp at creation, not egress: the MAC is keyed by the reply's
            # ORIGIN, so a stored reply re-served verbatim by any peer
            # (request_reply repair) still verifies under the creator's key.
            reply = self.auth.stamp(reply)

        session = self.sessions.get(client)
        if session is not None:
            if operation == wire.Operation.register:
                session.session = op
            session.request = int(header["request"])
            session.reply_bytes = reply
            self._store_client_reply(client, reply)
        return reply

    # -- state machine dispatch ----------------------------------------------

    def _execute(
        self, operation: wire.Operation, body: bytes, timestamp: int
    ) -> bytes:
        try:
            return self._execute_inner(operation, body, timestamp)
        except DeviceStateUnrecoverable:
            # The machine's in-process mirror recovery could not apply
            # (mirror suspect / cold tier active): rebuild from durable
            # state — the fault domain's last resort — and re-execute.
            self.recover_device_state()
            return self._execute_inner(operation, body, timestamp)

    def _execute_inner(
        self, operation: wire.Operation, body: bytes, timestamp: int
    ) -> bytes:
        if operation == wire.Operation.create_accounts:
            batch = np.frombuffer(body, dtype=types.ACCOUNT_DTYPE)
            results = self.machine.commit_batch("create_accounts", batch, timestamp)
            return _encode_results(results)
        if operation == wire.Operation.create_transfers:
            batch = np.frombuffer(body, dtype=types.TRANSFER_DTYPE)
            results = self.machine.commit_batch("create_transfers", batch, timestamp)
            return _encode_results(results)
        if operation == wire.Operation.lookup_accounts:
            ids = _decode_ids(body)
            return self.machine.lookup_accounts(ids).tobytes()
        if operation == wire.Operation.lookup_transfers:
            ids = _decode_ids(body)
            return self.machine.lookup_transfers(ids).tobytes()
        if operation == wire.Operation.get_proof:
            # Body: one u128 id (accounts, the PR 10 wire shape) or
            # id + a u64 kind selector (0 accounts / 1 transfers /
            # 2 posted) — validated in _validate_request.
            lanes = np.frombuffer(body, dtype="<u8")
            ident = int(lanes[0]) | (int(lanes[1]) << 64)
            kind = _PROOF_KIND_BY_CODE[int(lanes[2])] if len(lanes) > 2 \
                else "accounts"
            proof = self.machine.get_proof(ident, kind=kind)
            return proof if proof is not None else b""
        if operation in (
            wire.Operation.get_account_transfers,
            wire.Operation.get_account_history,
        ):
            filt = _decode_filter(body)
            rows = (
                self.machine.get_account_transfers(filt)
                if operation == wire.Operation.get_account_transfers
                else self.machine.get_account_history(filt)
            )
            # Reply rows are 128 B each; cap to one message body
            # (scan_buffer sizing, state_machine.zig:697-712).
            return rows[: self.config.message_body_size_max // 128].tobytes()
        raise ValueError(f"unimplemented operation {operation}")

    def _validate_request(self, operation: wire.Operation, body: bytes) -> None:
        """Reject anything that could not commit cleanly. Every prepare that
        reaches the WAL must be executable on replay."""
        max_body = self.config.message_body_size_max
        if len(body) > max_body:
            raise InvalidRequest("body exceeds message_body_size_max")
        if operation == wire.Operation.register:
            if body:
                raise InvalidRequest("register body must be empty")
            return
        if operation in (
            wire.Operation.create_accounts, wire.Operation.create_transfers
        ):
            if len(body) % 128 != 0:
                raise InvalidRequest("body not a multiple of event size")
            if len(body) // 128 > self.batch_lanes:
                raise InvalidRequest("batch exceeds configured lanes")
            return
        if operation in (
            wire.Operation.lookup_accounts, wire.Operation.lookup_transfers
        ):
            if len(body) % 16 != 0:
                raise InvalidRequest("body not a multiple of id size")
            # Replies are 128 B/row vs 16 B/id: cap so the reply always fits
            # in one message (state_machine.zig:70-75 batch_max semantics).
            if len(body) // 16 > max_body // 128:
                raise InvalidRequest("lookup batch exceeds reply capacity")
            return
        if operation in (
            wire.Operation.get_account_transfers,
            wire.Operation.get_account_history,
        ):
            # Any size is accepted: a body that is not exactly one
            # AccountFilter is treated as a zeroed (invalid) filter and
            # yields an empty reply (parse_filter_from_input,
            # state_machine.zig:810-820).
            return
        if operation == wire.Operation.reconfigure:
            # <u4 new_replica_count, <u4 new_standby_count, 8 B reserved.
            # Shape only — semantic checks happen at APPLY under the
            # membership current at that op (deterministic across replicas
            # and replay; an invalid transition commits a reject status).
            if len(body) != 16:
                raise InvalidRequest(
                    "reconfigure body must be 16 bytes "
                    "(u32 replica_count, u32 standby_count, 8 reserved)"
                )
            return
        if operation == wire.Operation.get_proof:
            # 16 B: one u128 id (accounts — PR 10 shape); 24 B: id + u64
            # kind selector.  Every journaled prepare must replay, so the
            # kind is validated HERE, not at execute.
            if len(body) not in (16, 24):
                raise InvalidRequest(
                    "get_proof body must be one u128 id (+ u64 kind)"
                )
            if len(body) == 24:
                kind = int(np.frombuffer(body[16:], "<u8")[0])
                if kind not in _PROOF_KIND_BY_CODE:
                    raise InvalidRequest(f"unknown proof kind {kind}")
            return
        raise InvalidRequest(f"operation {operation!r} not accepted")

    # -- membership reconfiguration (docs/reconfiguration.md) ----------------

    # Reply status codes (u64 LE result body) for operation reconfigure.
    RECONFIGURE_OK = 0
    RECONFIGURE_BAD_TRANSITION = 1   # not a single-step promote/demote
    RECONFIGURE_BOUNDS = 2           # outside REPLICAS_MAX/STANDBYS_MAX/solo
    RECONFIGURE_PRIMARY_DEMOTION = 3  # would demote the serving primary

    def _apply_reconfigure(self, header, body: bytes) -> bytes:
        """Execute a committed membership-change op.  Runs at the SAME op
        on every replica (and on WAL replay), so every input is taken from
        deterministic state: the membership current at this op and the
        prepare header's view — never the local wall clock or the
        replica's own (possibly lagging) view.  Idempotent: re-applying
        the current membership is a success no-op, which makes
        crash-replay safe without any dedup bookkeeping."""
        import numpy as np

        from .superblock import REPLICAS_MAX, STANDBYS_MAX

        lanes = np.frombuffer(body[:8], "<u4")
        new_rc, new_sc = int(lanes[0]), int(lanes[1])
        old_rc, old_sc = self.replica_count, self.standby_count
        status = self.RECONFIGURE_OK
        if (new_rc, new_sc) == (old_rc, old_sc):
            pass  # idempotent re-apply (crash replay)
        elif new_rc + new_sc != old_rc + old_sc or (
            abs(new_rc - old_rc) != 1
        ):
            # One step at a time, voters <-> standbys only: promotion
            # makes standby index old_rc a voter; demotion makes voter
            # index old_rc - 1 the first standby.  Indexes never move.
            status = self.RECONFIGURE_BAD_TRANSITION
        elif not (
            1 <= new_rc <= REPLICAS_MAX and 0 <= new_sc <= STANDBYS_MAX
        ) or (new_rc == 1 and new_sc > 0):
            status = self.RECONFIGURE_BOUNDS
        elif new_rc < old_rc and self._reconfigure_primary(
            int(header["view"]), old_rc
        ) == old_rc - 1:
            # Demoting the replica that is primary at this prepare's view
            # would drop the cluster's serving head without a view change.
            status = self.RECONFIGURE_PRIMARY_DEMOTION
        else:
            self.replica_count, self.standby_count = new_rc, new_sc
            self._membership_changed(old_rc, old_sc, int(header["view"]))
            if _obs.enabled:
                _obs.counter("reconfig.membership_ops").inc()
                _obs.gauge("reconfig.replica_count").set(new_rc)
                _obs.gauge("reconfig.standby_count").set(new_sc)
        if status != self.RECONFIGURE_OK and _obs.enabled:
            _obs.counter("reconfig.membership_rejected").inc()
        return int(status).to_bytes(8, "little")

    def _reconfigure_primary(self, view: int, replica_count: int) -> int:
        """Primary index at ``view`` under an explicit membership (the
        deterministic pre-transition mapping)."""
        return (view + self._primary_offset) % replica_count

    def _membership_changed(self, old_rc: int, old_sc: int,
                            view: int) -> None:
        """Post-transition hook.  The base replica only records the new
        shape (solo replicas can only no-op); VsrReplica overrides to fix
        the primary mapping, rebuild the clock quorum, and persist."""

    def _event_count(self, operation: wire.Operation, body: bytes) -> int:
        if operation in (
            wire.Operation.create_accounts, wire.Operation.create_transfers
        ):
            return len(body) // 128
        return 0

    # -- sessions ------------------------------------------------------------

    def _admit_session(self, session: Session) -> None:
        if len(self.sessions) >= self.config.clients_max and (
            session.client not in self.sessions
        ):
            # Evict the session with the lowest session number (oldest
            # register commit) — client_sessions.zig eviction policy.
            # Selection over SORTED items: session numbers are unique
            # (one commit op per registration), but the choice must be a
            # function of state, never of dict arrival order (tblint
            # nondet dict-selection rule; docs/tbmc.md determinism notes).
            victim = min(
                sorted(self.sessions.items()),
                key=lambda kv: kv[1].session,
            )[1]
            del self.sessions[victim.client]
        existing = self.sessions.get(session.client)
        if existing is not None:
            session.slot = existing.slot
        else:
            used = {s.slot for s in self.sessions.values()}
            session.slot = min(set(range(self.config.clients_max)) - used)
        self.sessions[session.client] = session

    def _eviction(
        self, client: int, reason: int = wire.EVICTION_NO_SESSION,
        session: int = 0,
    ) -> bytes:
        """Eviction carries WHY (wire.EVICTION_*): a capacity-evicted or
        unknown session is retryable (the client re-registers), a session-
        number mismatch is a protocol violation the client must surface.
        ``session`` echoes which session the eviction is about (0 = not
        session-specific) so clients can discard stale MISMATCHes for a
        session they already replaced."""
        h = wire.new_header(
            wire.Command.eviction,
            cluster=self.cluster, view=self.view, client=client,
            reason=reason, session=session,
        )
        h["replica"] = self.replica
        return wire.encode(h, b"")

    def _store_client_reply(self, client: int, reply: bytes) -> None:
        slot = self.sessions[client].slot
        # _validate_request guarantees replies fit one message slot.
        assert len(reply) <= self.config.message_size_max, len(reply)
        off = (
            self.storage.layout.client_replies_offset
            + slot * self.config.message_size_max
        )
        if self.async_checkpoint:
            # Server mode: reply slots are repair state, not commit state —
            # a torn write is re-served from a peer or retried by the client
            # (_read_client_reply tolerates corruption).  The reference
            # writes client_replies asynchronously for the same reason
            # (client_replies.zig); keeping a small O_DIRECT RMW off the
            # serving thread is worth ~0.5 ms/request.  The IO pool is one
            # FIFO worker, so writes for a session stay ordered.
            self._io_pool_submit(lambda: self.storage.write(off, reply))
            return
        self.storage.write(off, reply)

    def _read_client_reply(self, slot: int, size: int) -> bytes:
        if size == 0:
            return b""
        off = (
            self.storage.layout.client_replies_offset
            + slot * self.config.message_size_max
        )
        buf = self.storage.read(off, size)
        try:
            # Slice to the header's own size before verifying: the stored
            # slot may legitimately hold more bytes than this reply
            # (decode() itself rejects trailing bytes on ingress frames).
            h, _ = wire.decode_header(buf)
            raw = buf[: int(h["size"])]
            wire.verify_body(h, raw[wire.HEADER_SIZE:])
            return raw
        except ValueError:
            return b""  # corrupt stored reply: client will retry

    # -- checkpointing (replica.zig:3153-3169) --------------------------------

    @property
    def op_prepare_max(self) -> int:
        """Highest op this replica may journal (vsr.zig op_prepare_max).
        The WAL ring must always retain every op in (op_checkpoint, op] —
        commits replay from it and recovery anchors at the checkpoint — so
        the head may lead the checkpoint by at most the ring size.  A
        replica at this bound stalls until its next checkpoint; a lagging
        replica's head then falls behind the cluster's checkpoint, which is
        exactly the state-sync trigger."""
        return self.op_checkpoint + self.config.journal_slot_count - 1

    def _checkpoint_due(self) -> bool:
        # Measured from the last CAPTURE, not the last adopted checkpoint:
        # under async_checkpoint the adoption (op_checkpoint) lags the
        # in-flight write, and measuring from op_checkpoint would re-trigger
        # a capture on EVERY op after a boundary until adoption — misaligned
        # captures (breaking cross-replica forest determinism) and a
        # synchronous drain two ops later.
        return (
            self.commit_min
            - max(self.op_checkpoint, self._ckpt_captured_op)
            >= self.config.vsr_checkpoint_interval
        )

    def checkpoint(self) -> None:
        """Durably snapshot ledger + sessions + superblock at commit_min.

        With ``async_checkpoint`` on (both TCP servers — single-replica and
        cluster), the expensive half — forest delta + file writes + fsync +
        superblock — runs on a background thread while the replica keeps
        serving (replica.zig:3153-3169 overlaps checkpoint with the
        pipeline the same way); only the device→host snapshot is taken
        inline.  Cluster safety: every superblock write (this thread's
        _persist_view AND the background write) goes through the
        _superblock_install merge-point, which serializes them and merges
        monotonically.  The sim keeps checkpoints synchronous for
        determinism.

        Alignment: the CAPTURE always happens here, at the exact
        op_checkpoint+interval boundary the commit loop invokes us on —
        even when a previous write is still in flight (the capture is then
        queued and written after it).  Cross-replica forest determinism
        (peer block repair matches files by checksum) depends on every
        replica capturing at identical ops."""
        # A capture must never see a ledger ahead of commit_min: settle any
        # pipelined group first (no-op on the paths that already did).
        self._settle_or_recover()
        if self.machine.scrub_armed:
            # Checkpoint boundary: ALWAYS scrub (docs/fault_domains.md) —
            # a device-vs-mirror divergence here is a hard integrity
            # violation the capture must never bake into durable state.
            try:
                self.machine.scrub_check(boundary=True)
            except DeviceStateUnrecoverable:
                self.recover_device_state()
        if self.async_checkpoint:
            self._checkpoint_poll()
            if self._ckpt_thread is not None:
                if len(self._ckpt_queue) >= 1:
                    # Writes persistently slower than the checkpoint
                    # interval: block.  Backpressure must not skip the
                    # aligned capture — skipping would desynchronize this
                    # replica's forest files from its peers' — and the
                    # queue is bounded at one so peak host memory stays at
                    # two captures (in-flight + queued), not unbounded.
                    self._checkpoint_drain()
                self._ckpt_queue.append(self._checkpoint_capture())
                self._checkpoint_poll()  # start it if the write just landed
                return
            self._checkpoint_async_start()
            return
        t0 = time.perf_counter_ns() if _obs.enabled else 0  # tblint: ignore[nondet] metrics
        with tracer.span("checkpoint", op=self.commit_min):
            self._checkpoint_inner()
        if _obs.enabled:
            _obs.histogram("replica.checkpoint_ms", "ms").observe(
                (time.perf_counter_ns() - t0) / 1e6  # tblint: ignore[nondet] metrics
            )

    def _checkpoint_inner(self) -> None:
        arrays, meta, fields = self._checkpoint_capture()
        state = self._checkpoint_write(arrays, meta, fields)
        self._checkpoint_adopt(state, fields["cold_garbage"])

    def _checkpoint_capture(self):
        """The inline half of a checkpoint: everything that must be
        consistent with THIS commit_min — evictions, session snapshot,
        device→host ledger snapshot, digest, clocks.  Span
        ``checkpoint_capture`` (children ``checkpoint_d2h``,
        ``checkpoint_digest``): the serving thread is held for all of it."""
        with txtrace.stage("checkpoint_capture"):
            return self._checkpoint_capture_inner()

    def _checkpoint_capture_inner(self):
        # Tiering: spill the older half of the hot transfers window when it
        # is filling (deterministic: driven by the committed op stream; the
        # runs written here become durable with this checkpoint's manifest).
        m = self.machine
        m._maybe_evict_between_batches()
        self._ckpt_captured_op = self.commit_min
        meta = {
            "machine": m.host_state(),
            "sessions": {
                f"{client:032x}": {
                    "session": s.session,
                    "request": s.request,
                    "reply_size": len(s.reply_bytes),
                    "slot": s.slot,
                }
                for client, s in self.sessions.items()
            },
        }
        if m.merkle_armed:
            # Commitment root over the CANONICAL layout (shard-config
            # independent): restores — and any auditor holding the
            # checkpoint — verify the state against it WITHOUT replay
            # (docs/commitments.md; _install_checkpoint_ledger checks it).
            acc_root, tr_root, po_root = m.merkle_canonical_roots()
            meta["merkle_root"] = {
                "accounts": acc_root, "transfers": tr_root,
                "posted": po_root,
            }
        # checkpoint_ledger(): canonical single-device layout — under
        # TB_SHARDS the live ledger is owner-partitioned, and a checkpoint
        # must restore into ANY shard config (deterministic conversion, so
        # replica checkpoint file checksums stay cluster-comparable).
        with txtrace.stage("checkpoint_d2h"):
            arrays = checkpoint_mod.ledger_to_arrays(m.checkpoint_ledger())
        with txtrace.stage("checkpoint_digest"):
            ledger_digest = m.digest()
        if _obs.enabled:
            _obs.counter("replica.checkpoint.captures").inc()
            _obs.counter("replica.checkpoint.bytes").inc(
                sum(a.nbytes for a in arrays.values())
            )
        fields = dict(
            view=self.view,
            log_view=getattr(self, "log_view", self.view),
            commit_min=self.commit_min,
            commit_max=self.op,
            log_adopted_op=getattr(self, "_log_adopted_op", 0),
            ledger_digest=ledger_digest,
            prepare_timestamp=m.prepare_timestamp,
            commit_timestamp=m.commit_timestamp,
            # Cold runs superseded as of THIS capture: the only ones whose
            # deletion this checkpoint's durability justifies.  Runs merged
            # AFTER capture (concurrent evictions under async_checkpoint)
            # are referenced by the captured cold_manifest and must survive
            # until the NEXT checkpoint lands.
            cold_garbage=list(m.cold.garbage),
        )
        return arrays, meta, fields

    def _checkpoint_write(self, arrays, meta, fields) -> SuperBlockState:
        """The expensive half (file writes + fsync + superblock): safe off
        the serving thread — it touches only the captured host snapshot,
        the forest files, and distinct storage zones.  Span
        ``checkpoint_write``, on whichever thread runs it."""
        with txtrace.stage("checkpoint_write"):
            return self._checkpoint_write_inner(arrays, meta, fields)

    def _checkpoint_write_inner(self, arrays, meta, fields) -> SuperBlockState:
        # Session replies live in the client_replies zone; make them durable
        # before the superblock references their sizes.
        self.storage.sync()
        op = fields["commit_min"]
        file_checksum, manifest_checksum = self.forest.checkpoint_arrays(
            arrays, meta, op
        )
        state = SuperBlockState(
            cluster=self.cluster,
            replica=self.replica,
            replica_count=self.replica_count,
            # Membership metadata must ride EVERY superblock write: round-5
            # standby sweep find — omitting it here let the first
            # checkpoint erase standby_count, so restarted voters stopped
            # broadcasting to standbys forever.
            standby_count=self.standby_count,
            primary_offset=self._primary_offset,
            view=fields["view"],
            log_view=fields["log_view"],
            commit_min=op,
            commit_max=fields["commit_max"],
            log_adopted_op=fields["log_adopted_op"],
            op_checkpoint=op,
            checkpoint_file_checksum=file_checksum,
            ledger_digest=fields["ledger_digest"],
            prepare_timestamp=fields["prepare_timestamp"],
            commit_timestamp=fields["commit_timestamp"],
            manifest_checksum=manifest_checksum,
        )
        state = self._superblock_install(state)
        return state

    def _superblock_install(self, state: SuperBlockState) -> SuperBlockState:
        """The ONLY superblock write path: serializes the serving thread
        (_persist_view on view changes) against the background checkpoint
        thread and monotonically merges their fields so neither writer can
        regress the other's progress (the reference sequences superblock
        updates through a single-owner write queue, superblock.zig
        view_change/checkpoint staging):

        - view/log_view/commit bounds only move forward (a checkpoint
          captured before a view bump must not durably regress the view —
          a restarted replica could then ack in the old view: split brain).
        - The checkpoint anchor group (op_checkpoint + file checksums +
          digest + timestamps) moves forward as a UNIT: a view persist
          racing a landed background checkpoint must not revert the
          superblock to a manifest whose files the adopt step is about to
          GC — restart would anchor on deleted files."""
        with self._sb_lock:
            cur = self.superblock.state
            if state.op_checkpoint < cur.op_checkpoint:
                state = dataclasses.replace(
                    state,
                    op_checkpoint=cur.op_checkpoint,
                    checkpoint_file_checksum=cur.checkpoint_file_checksum,
                    manifest_checksum=cur.manifest_checksum,
                    ledger_digest=cur.ledger_digest,
                    prepare_timestamp=cur.prepare_timestamp,
                    commit_timestamp=cur.commit_timestamp,
                )
            # log_adopted_op travels WITH its writer's (log_view,
            # op_checkpoint): a later adoption may legitimately certify a
            # SHORTER canonical log (view-change truncation of an
            # uncommitted suffix), and a state sync legitimately LOWERS the
            # watermark to the synced checkpoint op at the same log_view —
            # so the lexicographically newer writer wins; max() would let a
            # pre-sync SV target_op survive the sync durably and wedge
            # every post-sync restart log_suspect.
            skey = (state.log_view, state.op_checkpoint)
            ckey = (cur.log_view, cur.op_checkpoint)
            if skey > ckey:
                adopted = state.log_adopted_op
            elif skey < ckey:
                adopted = cur.log_adopted_op
            else:
                a, b = state.log_adopted_op, cur.log_adopted_op
                if (a >= PROMOTION_SUSPECT_OP) != (b >= PROMOTION_SUSPECT_OP):
                    # Certification replaces the promotion sentinel at the
                    # same key: on_start_view's persisted target_op must
                    # actually land, or every later crash re-opens the
                    # promoted replica suspect forever.  (A stale
                    # checkpoint still carrying the sentinel must equally
                    # not resurrect it over a landed certification.)
                    adopted = min(a, b)
                else:
                    adopted = max(a, b)
            state = dataclasses.replace(
                state,
                view=max(state.view, cur.view),
                log_view=max(state.log_view, cur.log_view),
                commit_min=max(state.commit_min, cur.commit_min),
                commit_max=max(state.commit_max, cur.commit_max),
                log_adopted_op=adopted,
            )
            self.superblock.checkpoint(state)
            return state

    def _checkpoint_adopt(self, state: SuperBlockState, cold_garbage) -> None:
        # The background write merged in the view as of ITS write moment; a
        # view change since then is already durable via _persist_view —
        # fold it into the serving thread's view of the superblock too.
        state = dataclasses.replace(
            state,
            view=max(state.view, self.view),
            log_view=max(state.log_view, getattr(self, "log_view", self.view)),
        )
        self._sb_state = state
        self.op_checkpoint = state.op_checkpoint
        # The state-sync responder pack (canonical arrays + trees for the
        # PREVIOUS checkpoint, vsr/consensus.py) is dead weight the moment
        # the checkpoint moves: release it rather than holding a full
        # state copy until the next sync request happens to replace it.
        self._sync_pack_cache = None
        if _obs.enabled:
            _obs.counter("replica.checkpoints").inc()
            _obs.gauge("replica.op_checkpoint").set(self.op_checkpoint)
        # GC only after the superblock referencing the new manifest is
        # durable (crash before this point must find the old files intact).
        self.forest.gc()
        # Same discipline for cold runs — restricted to the files that were
        # already superseded AT CAPTURE (see _checkpoint_capture).
        self.machine.cold.gc(cold_garbage)

    # -- overlapped checkpoint (async_checkpoint; replica.zig:3153-3169) ------

    def _checkpoint_async_start(self) -> None:
        # Wall time feeds ONLY the slow-capture diagnostic below, never
        # replica state — replay stays seed-stable.
        t0 = time.monotonic()  # tblint: ignore[nondet]
        arrays, meta, fields = self._checkpoint_capture()
        dt = time.monotonic() - t0  # tblint: ignore[nondet]
        if dt > 0.05:
            dbg = getattr(self, "_debug", None)
            if dbg is not None:
                dbg("slow_ckpt_capture", ms=round(dt * 1e3, 1),
                    op=self.commit_min)
        self._checkpoint_write_start(arrays, meta, fields)

    def _checkpoint_write_start(self, arrays, meta, fields) -> None:
        import threading

        self._ckpt_error = None
        # 1 while a write is running or a capture waits behind one: set
        # here, cleared by the write's own thread as it ends (adoption waits
        # for the serving thread's next poll; the files are durable by then).
        if _obs.enabled:
            _obs.gauge("replica.checkpoint.inflight").set(1)

        def work():
            # Handoff protocol: the serving thread reads _ckpt_result/
            # _ckpt_error only in _checkpoint_poll, strictly AFTER
            # t.is_alive() goes False — thread termination is the
            # happens-before edge, so these two writes need no lock.
            try:
                state = self._checkpoint_write(arrays, meta, fields)
                garbage = fields["cold_garbage"]
                self._ckpt_result = (state, garbage)  # tblint: ignore[lane-race] is_alive gate
            except Exception as err:  # noqa: BLE001 — surfaced at poll
                self._ckpt_error = err  # tblint: ignore[lane-race] is_alive gate
            if _obs.enabled and not self._ckpt_queue:
                _obs.gauge("replica.checkpoint.inflight").set(0)

        t = threading.Thread(
            target=work, name="tb-checkpoint", daemon=True
        )
        self._ckpt_thread = t
        with tracer.span("checkpoint_async_start", op=fields["commit_min"]):
            t.start()

    def _checkpoint_poll(self) -> None:
        """Adopt a finished background checkpoint and start the next queued
        write, if any (serving thread only)."""
        t = self._ckpt_thread
        if t is not None and t.is_alive():
            return
        if t is not None:
            self._ckpt_thread = None
            if self._ckpt_error is not None:
                err, self._ckpt_error = self._ckpt_error, None
                # Retry path: re-arm the due trigger at the next commit
                # (measured-from-capture would otherwise suppress the next
                # checkpoint until commit_min reaches captured_op+interval —
                # with the production config that is beyond the WAL cap, so
                # one transient EIO would wedge the replica at WAL-full
                # forever).  Queued captures are discarded with it: the
                # fresh capture supersedes them.
                self._ckpt_captured_op = self.op_checkpoint
                self._ckpt_queue.clear()
                raise RuntimeError("background checkpoint failed") from err
            (state, cold_garbage), self._ckpt_result = self._ckpt_result, None
            if state.op_checkpoint >= self.op_checkpoint:
                self._checkpoint_adopt(state, cold_garbage)
            else:
                # Superseded while in flight (state sync adopted a newer
                # anchor) — adopting would regress op_checkpoint.  Still
                # GC the capture's cold garbage (gc() intersects with the
                # CURRENT garbage list, so anything the new state tracks
                # or references survives) or those files leak until
                # restart.
                self.machine.cold.gc(cold_garbage)
        if self._ckpt_thread is None and self._ckpt_queue:
            self._checkpoint_write_start(*self._ckpt_queue.pop(0))

    def _checkpoint_drain(self) -> None:
        while self._ckpt_thread is not None:
            self._ckpt_thread.join()
            self._checkpoint_poll()  # adopts; starts the next queued write

    def _checkpoint_land_if_wal_full(self, incoming: int) -> None:
        """`_checkpoint_poll`, and where ``incoming`` more ops (one per
        request at hand, admitted or not) would pass `op_prepare_max`, make
        the room first: settle the pending pipelined group (its commits may
        be what a due capture waits behind), then wait for the checkpoint
        write in flight and adopt it.  The WAL has journal_slot_count -
        vsr_checkpoint_interval - 1 ops of room past a capture (40 with the
        production journal) and a write of a state of gigabytes outlasts
        them; a request dropped for a full WAL would cost a single-replica
        client its whole timeout (it resends only then), the join costs the
        rest of the write.  After it the checkpoint is at the last capture,
        less than an interval behind: a group has room (net/bus.py
        GROUP_MAX is 32)."""
        self._checkpoint_poll()
        if self.op + incoming > self.op_prepare_max:
            if _obs.enabled:
                _obs.counter("replica.checkpoint.wal_full_waits").inc()
            self.pipeline_flush()
            self._checkpoint_drain()
        assert self.op + incoming <= self.op_prepare_max, (
            self.op, incoming, self.op_prepare_max)

    # -- device fault domain (docs/fault_domains.md) --------------------------

    def _scrub_poll(self) -> None:
        """Run a due scrub check at a commit-group boundary (the cadence
        knob: machine.scrub_interval / --scrub-interval).  Settles the
        pipelined commit engine first — the fold must see a quiesced
        ledger — and escalates an unrecoverable mismatch to the durable-
        state rebuild."""
        m = self.machine
        if not m.scrub_armed or not m.scrub_due:
            return
        self._settle_or_recover()
        try:
            m.scrub_check()
        except DeviceStateUnrecoverable:
            self.recover_device_state()

    def dump_blackbox(self, reason: str = "on_demand") -> Optional[str]:
        """Write the flight recorder's retained history next to the data
        file (postmortem artifact, docs/tracing.md); no-op when no
        recorder is attached.  Best-effort: a dump must never raise over
        the failure that triggered it.  Returns the path or None."""
        box = self.blackbox
        if box is None:
            return None
        box.record("dump", reason=reason, op=self.op,
                   commit_min=self.commit_min)
        directory = os.path.dirname(self.data_path) or "."
        paths = dump_blackboxes([box], directory)
        return paths[0] if paths else None

    def recover_device_state(self) -> None:
        """Last-resort device-state recovery: rebuild the machine from the
        durable checkpoint + WAL replay — the restart recovery path, run
        in process (the fault domain's fallback when the mirror itself is
        suspect or cannot re-materialize, e.g. under the cold tier).

        Sessions, the WAL, and all host-side replica state are intact (the
        fault domain covers only device-resident state); only the machine's
        ledger and derived state are rebuilt.  The prepare clock is
        preserved: already-journaled prepares above commit_min keep their
        timestamps monotone."""
        m = self.machine
        if _obs.enabled:
            _obs.counter("device_recovery.wal_replays").inc()
        # The flight recorder's reason to exist: dump the retained protocol
        # history BEFORE the rebuild mutates anything further.
        self.dump_blackbox("device_recovery")
        prepare_timestamp = m.prepare_timestamp
        m.scrub_disarm()
        m.quarantine()
        sb = self._sb_state
        loaded = self._load_checkpoint_state(sb)
        if loaded is not None:
            ledger, meta = loaded
            self._install_checkpoint_ledger(ledger, meta, sb)
            floor = sb.op_checkpoint
        else:
            m.reset_device_state()
            floor = 0
        recovery = self.journal.recover()
        for op in range(floor + 1, self.commit_min + 1):
            entry = recovery.entries.get(op)
            if entry is None or entry.body is None:
                raise RuntimeError(
                    f"device-state recovery: committed op {op} unreadable "
                    "from the WAL"
                )
            operation = wire.Operation(int(entry.header["operation"]))
            name = _OP_NAMES.get(operation)
            if name is None:
                continue  # register/lookup/query ops: no machine state
            dtype = (
                types.ACCOUNT_DTYPE if name == "create_accounts"
                else types.TRANSFER_DTYPE
            )
            m.commit_batch(
                name, np.frombuffer(entry.body, dtype=dtype),
                int(entry.header["timestamp"]),
            )
        m.prepare_timestamp = max(m.prepare_timestamp, prepare_timestamp)
        m.device_recoveries += 1
        m.scrub_arm()  # re-arm from the freshly verified state

    def close(self) -> None:
        self._pipeline_settle()
        self._checkpoint_drain()
        pool = getattr(self, "_io_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
        lane = getattr(self.machine, "_lane", None)
        if lane is not None:
            lane.shutdown(wait=True)
            self.machine._lane = None
        if self.aof is not None:
            self.aof.close()
        dbg = getattr(self, "_debug_file", None)
        if dbg is not None:
            dbg.close()
            self._debug_file = None
        self.storage.close()


_OP_NAMES = {
    wire.Operation.create_accounts: "create_accounts",
    wire.Operation.create_transfers: "create_transfers",
}

# Wire kind selector for get_proof (ops/merkle.py PROOF_KINDS).
_PROOF_KIND_BY_CODE = {0: "accounts", 1: "transfers", 2: "posted"}


def _encode_results(results: List[Tuple[int, int]]) -> bytes:
    arr = np.zeros(len(results), dtype=types.EVENT_RESULT_DTYPE)
    for i, (index, result) in enumerate(results):
        arr[i]["index"] = index
        arr[i]["result"] = result
    return arr.tobytes()


def _decode_filter(body: bytes) -> np.void:
    """AccountFilter from a request body; wrong-size bodies become a zeroed
    (hence invalid -> empty-reply) filter (state_machine.zig:810-820)."""
    if len(body) == types.ACCOUNT_FILTER_DTYPE.itemsize:
        return np.frombuffer(body, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
    return np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]


def _decode_ids(body: bytes) -> List[int]:
    lanes = np.frombuffer(body, dtype="<u8")
    return [
        int(lanes[2 * i]) | (int(lanes[2 * i + 1]) << 64)
        for i in range(len(lanes) // 2)
    ]
