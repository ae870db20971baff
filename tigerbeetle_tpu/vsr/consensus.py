"""VSR consensus: the multi-replica message-driven participant.

Mirrors the reference replica's consensus protocol (src/vsr/replica.zig):

- Normal operation: the primary (``view % replica_count``) turns requests
  into prepares (op + timestamp assigned, hash-chained — :1308-1337),
  journals locally, and **ring-replicates**: each replica forwards the
  prepare to the next replica in the ring so primary egress stays 1:1
  (:1339-1363).  Backups journal and send prepare_ok to the primary; commit
  happens at a replication quorum (:1469+), in op order, and the primary
  replies to the client (:3678-3836).  Backups learn the commit number from
  prepare headers and periodic commit heartbeats and execute via
  commit_journal (:1591, :3176).
- View change: a backup that stops hearing from the primary broadcasts
  start_view_change for view+1; at a view-change quorum of SVCs each replica
  sends do_view_change (carrying its journal-suffix headers) to the new
  primary, which selects the canonical log — max (log_view, op) — repairs
  any prepares it lacks, and broadcasts start_view (:1702-2013).  Backups
  install the canonical suffix, repair missing bodies, and re-ack the
  uncommitted suffix so it can commit in the new view.
- Repair: request_prepare/request_headers fetch lost WAL entries from peers
  (:2048-2497); a replica whose WAL no longer overlaps the cluster's
  (primary checkpoint beyond its head) state-syncs the latest checkpoint
  snapshot in message-sized chunks (vsr/sync.zig).
- Clock: ping/pong round trips feed the Marzullo-filtered cluster clock
  (clock.py); the primary refuses to assign timestamps while unsynchronized
  (:1322-1325).

The class is transport-agnostic and deterministic: ``on_message`` and
``tick`` return ``(destination, bytes)`` envelopes; time comes from injected
monotonic/realtime sources.  The TCP bus (net/) and the VOPR simulator
(sim/) both drive this same code — the simulator's whole point (SURVEY §4.2)
is that the production consensus path is what gets fault-injected.

Quorums are flexible (vsr.zig:910-986): replication and view-change quorums
need only intersect, so e.g. a 6-replica cluster commits at 3 and
view-changes at 4 (docs/deploy/hardware.md:29-40).

Divergence from the reference, by design: view/log_view are persisted to the
superblock on view change via a quorum write of the full superblock state
(the reference journals view headers separately); and a replica recovering
from restart re-joins via request_start_view instead of a dedicated
recovering_head protocol.
"""

from __future__ import annotations

import dataclasses
import os
import random
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from ..obs.metrics import registry as _obs
from ..obs.txtrace import txtrace
from . import checkpoint as checkpoint_mod
from . import overload
from . import wire
from .clock import Clock
from .replica import ForestDamage, InvalidRequest, Replica, Session
from .superblock import SuperBlockState

# An outbound envelope: (("replica", index) | ("client", client_id), bytes).
Dst = Tuple[str, int]
Msg = Tuple[Dst, bytes]

NORMAL = "normal"
VIEW_CHANGE = "view_change"
RECOVERING = "recovering"
SYNCING = "syncing"

# Timeout cadences in ticks (a tick is ~10 ms wall / 1 step simulated;
# values mirror the reference's relative cadences, vsr.zig:543-712).
PING_INTERVAL = 25
COMMIT_HEARTBEAT = 10
PREPARE_RESEND = 15
NORMAL_HEARTBEAT = 100       # backup: primary presumed SUSPECT after this
PROBE_GRACE = 50             # direct-ping grace before campaigning
PRIMARY_GAP_MULT = 8         # silence budget: x the EWMA inter-word gap
PRIMARY_BUDGET_CAP = 600     # bounded failover: budget never exceeds this
PRIMARY_ABDICATE = 800       # primary commit-stall ticks before stepping down
_FLOOR_STALL_SYNC = 30       # commit-floor-starved heartbeats before syncing
VIEW_CHANGE_RESEND = 25      # SVC/DVC re-broadcast while in view change
VIEW_CHANGE_ESCALATE = 200   # stuck view change: try the next view
RECOVERING_RESEND = 30       # request_start_view cadence while recovering
REPAIR_INTERVAL = 15
SYNC_RESEND = 30
BLOCK_REPAIR_RESEND = 20     # per-chunk block-repair timeout before rotating

# Merkle-anchored incremental state sync (docs/state_sync.md).
SYNC_ROOTS_ATTEMPTS = 3      # unanswered sync_roots rounds PER PEER before
                             # degrading to the full-checkpoint path (covers
                             # merkle-off peers and version skew: an old
                             # responder never answers the new command)
SYNC_VERIFY_FAILURES = 3     # failed subtree/row verifications (lying or
                             # bit-flipped chunks) before degrading to full
SYNC_DIVERGENCE_MAX = 0.5    # diverging fraction of the top frontier above
                             # which descent cannot win (cold start, long
                             # absence): go straight to the full transfer

# request_blocks/block kind codes <-> forest file kinds.
_BLOCK_KIND_CODE = {
    "manifest": wire.BLOCK_KIND_MANIFEST,
    "base": wire.BLOCK_KIND_BASE,
    "run": wire.BLOCK_KIND_RUN,
    "cold": wire.BLOCK_KIND_COLD,
}
_BLOCK_KIND_NAME = {v: k for k, v in _BLOCK_KIND_CODE.items()}
TICK_NS = 10_000_000  # default tick length; the TCP bus overrides tick_ns


def quorums(replica_count: int) -> Tuple[int, int]:
    """(quorum_replication, quorum_view_change) — flexible quorums that
    always intersect (vsr.zig:910-986): 1/1, 2/2, 2/2, 2/3, 3/3, 3/4."""
    if replica_count == 1:
        return 1, 1
    majority = replica_count // 2 + 1
    q_replication = max(2, replica_count + 1 - majority)
    q_view_change = max(majority, replica_count + 1 - q_replication)
    assert q_replication + q_view_change > replica_count
    return q_replication, q_view_change


@dataclasses.dataclass
class PipelineEntry:
    """One in-flight prepare at the primary (replica.zig PipelineQueue)."""

    op: int
    checksum: int
    client: int                 # 0 for re-certified view-change suffix ops
    ok_from: Set[int] = dataclasses.field(default_factory=set)
    repair_rounds: int = 0      # timeouts spent with the body unreadable


class VsrReplica(Replica):
    """A full consensus participant; see module docstring."""

    def __init__(
        self,
        data_path: str,
        *,
        monotonic=None,
        realtime=None,
        seed: int = 0,
        **kwargs,
    ) -> None:
        import time as _time

        # Production defaults only: the VOPR cluster injects seeded sim
        # clocks through these parameters, so replay never sees wall time.
        realtime = realtime or _time.time_ns  # tblint: ignore[nondet]
        monotonic = monotonic or _time.monotonic_ns  # tblint: ignore[nondet]
        super().__init__(data_path, time_ns=realtime, **kwargs)
        self._monotonic = monotonic
        self._realtime = realtime
        self.status = RECOVERING
        self.log_view = 0
        self.commit_max = 0
        self._log_adopted_op = 0
        self.prng = random.Random(seed)
        # Overload control (vsr/overload.py; TB_OVERLOAD / the CLI's
        # --overload-control, sim injects explicitly).  Off by default:
        # every shed point below then behaves bit-identically to the
        # silent-drop behavior pinned seeds and the bench differential
        # replay against.
        self.overload_control = overload.enabled()
        # Byzantine ingress discipline (docs/fault_domains.md byzantine
        # domain).  ON by default — the checks only reject frames an honest
        # cluster never produces (forged origin fields, commit-checksum
        # conflicts), so every pinned seed replays bit-identically.  The
        # VOPR byzantine kind's negative control forces it off
        # (run_byzantine_seed(verify=False)) to prove the verification is
        # what carries safety, the scrub-off discipline.
        self.ingress_verify = True
        # Plain equivocation-detection count (registry-independent): the
        # VOPR byzantine kind reads it for its proof artifacts.
        self.byzantine_detections = 0
        # Model-checker hooks (sim/mc.py, docs/tbmc.md) — inert by default:
        # ``mc_mutations`` arms a seeded protocol mutation (tbmc's
        # passes-with-defenses / fails-without discipline); the
        # deterministic nonce makes request_start_view a pure function of
        # (replica, view) so canonical-state dedup survives RSV retries.
        self.mc_mutations: frozenset = frozenset()
        self.mc_deterministic_nonce = False
        # Content anchors (op -> canonical header checksum) learned from
        # SOURCE-AUTHENTICATED origins only: commit heartbeats
        # (commit_checksum) and installed view-change windows.  Backups
        # execute an op only when its journaled content parent-chains up to
        # an anchor (_content_certified) — the defense that makes a relayed
        # forged prepare inert: it can enter the journal, but it can never
        # EXECUTE, because no honest primary will ever anchor its checksum.
        self._anchors: Dict[int, int] = {}
        # Wire authentication (vsr/auth.py; docs/fault_domains.md "Byzantine
        # primary").  ``auth`` is a Keychain or None — OFF by default: every
        # frame then carries a zero MAC and the wire is bit-identical to the
        # pre-auth protocol, so pinned seeds and goldens are untouched.
        # Armed, every SOURCE_AUTHENTICATED ingress frame passes
        # _ingress_auth (MAC failures drop-and-count as auth.rejected.*);
        # ``auth_strict`` additionally rejects UNauthenticated replica
        # frames and upgrades certified commits from checksum anchors to
        # authenticated ack CERTIFICATES: prepare_ok is broadcast, and a
        # backup executes an op only once _cert_quorum() distinct
        # MAC-verified acks name its exact journaled checksum — the quorum
        # size guarantees two certificates for the same op intersect in an
        # honest replica, so a lying PRIMARY cannot fork execution.
        self.auth = None
        self.auth_strict = False
        # Ack certificates: op -> {checksum -> acking replica set},
        # accumulated only under auth_strict (bounded by _ACK_CERTS_MAX).
        self._ack_certs: Dict[int, Dict[int, Set[int]]] = {}

        # Journaled prepare headers by op for the live window (chain checks,
        # repair responses, DVC/SV bodies).  Pruned at checkpoint.
        self.headers: Dict[int, np.ndarray] = {}
        # Chain-verification floor: headers for ops >= _verify_floor are
        # known canonical (anchored in an SV/DVC install and parent-chained
        # downward); ops in (commit_min, _verify_floor) are SUSPECT — e.g.
        # a restarted replica's own WAL suffix, which may hold prepares a
        # view change since discarded.  _commit_journal refuses to commit a
        # suspect op (VOPR seed 9002: a stale view-0 register was committed
        # at op 1 because the view-4 SV window never reached down to it).
        self._verify_floor = 0
        # Out-of-order prepares waiting for the chain to catch up.
        self.stash: Dict[int, Tuple[np.ndarray, bytes]] = {}
        # Ops whose canonical header is installed but whose body is missing.
        self.missing: Dict[int, int] = {}  # op -> expected header checksum
        # View-change nack protocol: op -> replicas that provably NEVER
        # journaled the missing body (vsr.zig nacks).  At a nack quorum the
        # body cannot have been quorum-journaled, hence never committed,
        # and the new primary truncates it instead of stalling forever.
        self._nacks: Dict[int, Set[int]] = {}

        self.pipeline: Dict[int, PipelineEntry] = {}
        self.svc_from: Dict[int, Set[int]] = {}
        self.dvc_from: Dict[int, Dict[int, dict]] = {}
        self._dvc_sent_for: Optional[int] = None
        self._new_view_pending: Optional[int] = None
        self._pending_finish: Optional[int] = None

        # Sync state (lagging replica fetching a checkpoint snapshot).
        self.sync_target: Optional[dict] = None
        self.sync_buffer = bytearray()
        # Explicit sync responder (block-repair fallback: primary unknown,
        # rotate through peers); None = target the current view's primary.
        self._sync_peer: Optional[int] = None
        # Merkle-anchored incremental catch-up (docs/state_sync.md).
        # sync_mode_force="full" (TB_SYNC_MODE=full / --sync-mode full /
        # the VOPR forced-fallback control) pins the legacy full-checkpoint
        # transfer; sync_verify=False is the NEGATIVE CONTROL ONLY (the
        # scrub-off discipline): subtree/row/state verification off, so a
        # seeded lying responder demonstrably installs divergent state.
        self.sync_mode_force: Optional[str] = (
            "full" if os.environ.get("TB_SYNC_MODE") == "full" else None
        )
        self.sync_verify = True
        self.sync_divergence_max = SYNC_DIVERGENCE_MAX
        # Plain accounting (registry-independent; the VOPR catch-up kind
        # and tools/sync_smoke.py assert on it): lifetime totals plus the
        # mode the LAST completed install used.
        self.sync_stats = {
            "mode": None, "bytes_incremental": 0, "bytes_full": 0,
            "subtrees_shipped": 0, "rows_installed": 0,
            "chunk_retries": 0, "fallbacks": 0,
        }
        # Requester-side descent state (big numpy arrays — deliberately
        # OUTSIDE the mc capsule: reconstructible by re-entering the roots
        # flow) and the responder-side per-checkpoint pack cache.
        self._sync_local: Optional[dict] = None
        self._sync_pack_cache: Optional[object] = None

        # Peer block repair (grid_blocks_missing.zig's role): damaged
        # checkpoint files being refetched before the replica can open.
        self._block_repair: Optional[dict] = None
        self.blocks_repaired = 0
        # Cold-tier fetch during state sync: a synced checkpoint's
        # cold_manifest references the responder's LOCAL spill files, which
        # we must fetch (by checksum) before the install can complete.
        self._cold_fetch: Optional[dict] = None

        # Tick counters.  First ping fires on the first tick so the cluster
        # clock synchronizes before the first client request.
        self._ticks = 0
        self._last_ping = -PING_INTERVAL
        self._last_commit_sent = 0
        self._last_primary_word = 0
        # Primary-liveness suspicion (reference: RTT-adaptive timeouts,
        # vsr.zig:543-712).  A busy-but-alive primary (long fsync, scheduler
        # preemption on a shared host) must not trigger elections: the
        # silence budget adapts to the observed inter-word gap, and a
        # suspecting backup first probes the primary directly (ping) and
        # campaigns only when the probe too goes unanswered.
        self._primary_gap_ewma = 0.0
        self._probe_sent_at: Optional[int] = None
        self._pong_standdowns = 0
        # Commit-floor starvation / primary commit-stall tracking (see
        # _maybe_start_sync and the abdication branch in tick()).
        self._floor_stall = 0
        self._abdicate_commit_mark = -1
        self._abdicate_ticks = 0
        # Max ops executed per _commit_journal call (None = unlimited).
        # The TCP bus sets this and drains the remainder via its commit
        # pump; the sim/VOPR leaves it unset (single-dispatch determinism).
        self.commit_budget: Optional[int] = None
        # True iff the last _commit_journal call stopped ON BUDGET (vs
        # blocked on repair): the bus spawns its pump only for this case —
        # a repair-blocked backlog would otherwise respawn a no-op task
        # every tick for the whole repair window.
        self.commit_budget_stopped = False
        self._vc_started = 0
        # Consecutive stuck-view-change escalations: doubles the
        # escalation window (phase-lock breaking); resets on progress.
        self._vc_escalations = 0
        self._last_sync_req = 0
        # Tick of the last ACCEPTED sync payload byte: the stall detector
        # that drives responder rotation.  Distinct from _last_sync_req —
        # a checkpoint-refresh (on_commit) re-pins the target and re-sends
        # WITHOUT touching this clock, so a dead responder is still
        # rotated away from even while refreshes keep arriving (the
        # stranded-sync wedge; see _enter_sync(refresh=True)).
        self._sync_progress = 0
        self._heartbeat_jitter = 0
        self._recovering_since = 0
        # Event-loop starvation guard state (tick() liveness fairness).
        self._last_tick_mono = None
        # Env-gated replica event log (the reference's log.zig role): one
        # JSONL file per replica, cheap enough to leave on in benchmarks.
        self._debug_file = None
        dbg = os.environ.get("TB_DEBUG_LOG")
        if dbg:
            self._debug_file = open(
                f"{dbg}.r{self.replica}", "a", buffering=1
            )

        # Adaptive retry timeouts (vsr.zig:543-712): RTT-tracked base +
        # exponential backoff + jitter, reset on progress (vsr/timeout.py).
        from .timeout import Rtt, Timeout

        self.rtt = Rtt()
        self._prepare_timeout = Timeout(
            self.prng, PREPARE_RESEND, PREPARE_RESEND * 8, rtt=self.rtt,
            rtt_multiple=4.0,
        )
        self._vc_timeout = Timeout(
            self.prng, VIEW_CHANGE_RESEND, VIEW_CHANGE_RESEND * 6
        )
        self._rsv_timeout = Timeout(
            self.prng, RECOVERING_RESEND, RECOVERING_RESEND * 8
        )
        self._repair_timeout = Timeout(
            self.prng, REPAIR_INTERVAL, REPAIR_INTERVAL * 8, rtt=self.rtt,
            rtt_multiple=3.0,
        )

        self.clock: Optional[Clock] = None

    # -- identity ------------------------------------------------------------

    def primary_index(self, view: Optional[int] = None) -> int:
        v = self.view if view is None else view
        # primary_offset: committed reconfiguration keeps the serving
        # primary fixed across a quorum-membership flip; 0 forever on a
        # never-reconfigured cluster (docs/reconfiguration.md).
        return (v + self._primary_offset) % self.replica_count

    @property
    def is_standby(self) -> bool:
        """Non-voting member (replica index >= replica_count,
        constants.zig:31-35): consumes the prepare stream, never acks,
        never votes, never becomes primary (replica.zig:4874-4878)."""
        return self.replica >= self.replica_count

    @property
    def node_count(self) -> int:
        return self.replica_count + self.standby_count

    def _init_clock(self) -> None:
        self.clock = Clock(
            self.replica_count, self.replica, self._monotonic, self._realtime
        )
        self.time_ns = self._primary_now
        self._heartbeat_jitter = self.prng.randrange(NORMAL_HEARTBEAT // 2)

    @property
    def is_primary(self) -> bool:
        return self.status == NORMAL and self.primary_index() == self.replica

    def _membership_changed(self, old_rc: int, old_sc: int,
                            view: int) -> None:
        """A reconfigure op committed: fix the primary mapping so THIS
        prepare's view keeps its primary under the new modulus (quorum
        flips never move the primary without a view change), rebuild the
        clock over the new voter set, and persist — all pure functions of
        committed state, so every replica (and every replay) lands on the
        same offset."""
        old_primary = (view + self._primary_offset) % old_rc
        self._primary_offset = (old_primary - view) % self.replica_count
        if self.clock is not None:
            # Rebuild the sample quorum over the new voter set WITHOUT
            # re-drawing jitter or resetting time_ns (determinism: the
            # prng stream must not depend on membership history), and
            # CARRY the learned samples AND the current sync estimate
            # over: dropping them would un-synchronize the clock and make
            # the primary shed every request (BUSY_CLOCK) until a full
            # ping round under the NEW quorum — a needless availability
            # dip on every membership flip (pre-flip samples exclude
            # standbys by design, replica.zig:1274, so a 3+1 -> 4+0
            # promotion can never meet quorum 3 from carried samples
            # alone), and a permanent wedge in the frozen-time model
            # checker.  The wall-clock estimate is not invalidated by a
            # membership flip; its confidence basis is merely stale, and
            # the next pong re-runs Marzullo under the new quorum.
            old_clock = self.clock
            self.clock = Clock(
                self.replica_count, self.replica, self._monotonic,
                self._realtime,
            )
            self.clock.samples = dict(old_clock.samples)
            self.clock.epoch_start_monotonic = (
                old_clock.epoch_start_monotonic
            )
            self.clock.offset_ns = old_clock.offset_ns
            self.clock._synchronized = old_clock._synchronized
        self._persist_view()
        if _obs.enabled:
            _obs.counter(
                "reconfig.promotions" if self.replica_count > old_rc
                else "reconfig.demotions"
            ).inc()

    @property
    def commit_backlog(self) -> bool:
        """Journaled ops known-committed but not yet executed (the bus
        commit pump drains these between dispatches)."""
        return self.commit_min < min(self.commit_max, self.op)

    @property
    def quorum_replication(self) -> int:
        return quorums(self.replica_count)[0]

    @property
    def quorum_view_change(self) -> int:
        rc = self.replica_count
        if "reconfig_stale_quorum" in self.mc_mutations:
            # Seeded mutation (tools/tbmc): the view-change quorum is
            # sized from the membership this process OPENED with,
            # ignoring committed reconfigure ops.  After a 3+1 -> 4+0
            # promotion the stale quorum (2 of 4) no longer intersects
            # every replication quorum (2 + 2 = 4, not > 4), so a view
            # change can canonicalize a history that misses a committed
            # op (mc.py exhibits a machine-checked counterexample at the
            # pinned reconfig scope; replication quorums are unaffected
            # because quorums(3)[0] == quorums(4)[0]).
            rc = self._boot_replica_count
        q = quorums(rc)[1]
        if "vc_quorum" in self.mc_mutations:
            # Seeded mutation (tools/tbmc): the classic off-by-one — view
            # changes complete one vote short, so canonical selection can
            # miss a committed op and refill it (mc.py exhibits a
            # machine-checked counterexample at the pinned scope).
            return max(1, q - 1)
        return q

    # -- lifecycle -----------------------------------------------------------

    def open(self) -> None:
        """Recover durable state; do NOT execute journaled-but-uncommitted
        ops — a restarted replica must first learn commit_max from the
        cluster (a journaled op may have been discarded by a view change
        while we were down)."""
        try:
            recovery = self._open_durable_state()
        except ForestDamage as err:
            if self.replica_count == 1:
                raise  # solo: no peer to repair from
            self._enter_block_repair(
                err.damage, getattr(err, "cold_paths", None)
            )
            return
        self._post_open(recovery)

    def _post_open(self, recovery) -> None:
        self.commit_max = self.commit_min
        self.log_view = getattr(self._sb_state, "log_view", self.view)
        # Adoption watermark rides through restarts: _persist_view rewrites
        # it verbatim until the next log_view advance replaces it.
        self._log_adopted_op = getattr(self._sb_state, "log_adopted_op", 0)
        self._load_chain(recovery)
        self._init_clock()
        if self.replica_count == 1:
            # Sole replica: everything chained is committed by definition.
            self._replay_solo()
            self.status = NORMAL
        elif (
            self.op == 0 and self.commit_min == 0 and self.view == 0
            and self.log_view == 0
            and not getattr(self, "_log_suspect", False)
        ):
            # Freshly formatted cluster: nothing to recover, start normal
            # (the reference's format-then-start path).  A factory-fresh
            # but SUSPECT file (a promoted never-caught-up standby) must
            # instead recover via request_start_view so its certification
            # can actually happen.
            self.status = NORMAL
        else:
            self.status = RECOVERING
            self._recovering_since = self._ticks
        # Arm the device fault domain from this digest-verified state; the
        # ops the cluster re-commits from here advance the mirror like any
        # other commit.  No-op at scrub interval 0.
        self.machine.scrub_arm()

    def _load_chain(self, recovery) -> None:
        """Rebuild the in-memory hash chain from the WAL without executing:
        sets self.op/parent_checksum/headers to the contiguous chained
        suffix anchored at the checkpoint (cf. Replica._replay)."""
        anchor = recovery.entries.get(self.commit_min)
        if anchor is None and self.commit_min == 0:
            anchor = self._restore_root()  # deterministic; see replica.py
        if anchor is not None:
            self.parent_checksum = wire.header_checksum(anchor.header)
            self.headers[self.commit_min] = anchor.header
        else:
            self.parent_checksum = 0
        self.op = self.commit_min
        op = self.commit_min + 1
        parent = self.parent_checksum
        while op in recovery.entries:
            entry = recovery.entries[op]
            if entry.body is None:
                break
            if parent and wire.u128(entry.header, "parent") != parent:
                break
            self.headers[op] = entry.header
            parent = wire.header_checksum(entry.header)
            self.op = op
            op += 1
        if self.op > self.commit_min:
            self.parent_checksum = wire.header_checksum(self.headers[self.op])
        # Everything re-loaded from our own WAL is suspect until it chains
        # into canonical state learned from the cluster (solo replicas ARE
        # the cluster: their WAL is canon by quorum=1).
        self._verify_floor = self.op + 1 if self.replica_count > 1 else 0
        # RECOVERING-HEAD detection (replica.zig status.recovering_head):
        # when recovery shows our chained head is AMPUTATED — headers
        # recovered beyond it with bodies lost, foreign (misdirected-write)
        # slot content, or a persisted commit_min above it — our log must
        # not vouch in a view change.  Presenting a truncated op under our
        # real (possibly highest) log_view would WIN the canonical
        # selection and truncate committed history (storage-adversary seed
        # 31000: a twice-read-faulted ex-primary's (log_view=3, op=24) log
        # beat the intact backup's (log_view=0, op=28)).
        beyond_head = any(op > self.op for op in recovery.entries)
        persisted_commit = getattr(self._sb_state, "commit_min", 0)
        # The DVC invariant behind (log_view, op) canonical selection: a
        # durable log_view asserts the journal holds that view's canonical
        # log through self.op.  The durable log_adopted_op (written only
        # when log_view advances) records how far that log was KNOWN to
        # extend at adoption — a recovered head below it means the adopted
        # suffix died with the crash (bodies never journaled), and a DVC
        # claiming (log_view, short-op) would OUT-RANK an intact older-view
        # log and truncate committed history (VOPR seed 500285: a restarted
        # backup's (log_view=2, op=22) beat the intact (log_view=0, op=29)
        # log and ops 24-28, committed, were refilled with new requests).
        # NOT commit_max: that folds in heartbeat-learned cluster commits a
        # lagging-but-intact backup's journal never held, and using it here
        # falsely marked such backups suspect after a crash — wedging view
        # changes when the primary also died (ADVICE r4 medium).
        persisted_adopted = getattr(self._sb_state, "log_adopted_op", 0)
        # The slot of op+1 is the ONE slot a write could have been mid-
        # flight to at crash time (prepares journal serially, synced per
        # write): nonzero-undecodable content THERE is an ordinary torn
        # tail — never acked (acks follow the sync) — not amputation.
        torn_tail_slot = self.journal.slot(self.op + 1)
        corrupt_slots = [
            s for s in getattr(recovery, "corrupt_slots", ())
            if s != torn_tail_slot
        ]
        self._log_suspect = self.replica_count > 1 and (
            bool(recovery.foreign_slots)
            or bool(corrupt_slots)
            or beyond_head
            or persisted_commit > self.op
            or persisted_adopted > self.op
        )
        self._debug(
            "recovered", op=self.op, commit_min=self.commit_min,
            persisted=persisted_commit, suspect=self._log_suspect,
            entries=len(recovery.entries),
            faulty=len(recovery.faulty_slots),
            corrupt=len(corrupt_slots),
            log_view=self.log_view, view=self.view,
        )

    def _replay_solo(self) -> None:
        """Single-replica replay: execute the whole chained suffix."""
        for op in range(self.commit_min + 1, self.op + 1):
            read = self.journal.read_prepare(op)
            assert read is not None, op
            h, body = read
            self._commit_prepare(h, body, replay=True)
            if self._checkpoint_due():
                self.checkpoint()
        self.commit_max = self.commit_min

    def _persist_view(self) -> None:
        """Quorum-write view/log_view into the superblock so a restarted
        replica never regresses its view (replica.zig view durability).
        commit_min rides along: a restart whose WAL chain ends below it is
        PROOF of an amputated suffix (recovering-head detection)."""
        if self._sb_state is None:
            return
        state = dataclasses.replace(
            self._sb_state, view=self.view, log_view=self.log_view,
            commit_min=max(self._sb_state.commit_min, self.commit_min),
            commit_max=max(self._sb_state.commit_max, self.commit_max),
            log_adopted_op=getattr(self, "_log_adopted_op", 0),
            # Membership + primary mapping ride every view write: a
            # committed reconfiguration must never be forgotten by a
            # crash between its commit and the next checkpoint.
            replica_count=self.replica_count,
            standby_count=self.standby_count,
            primary_offset=self._primary_offset,
        )
        # Through the single merge-point: a concurrent background
        # checkpoint (async_checkpoint) must not be reverted or raced.
        state = self._superblock_install(state)
        self._sb_state = state

    # -- message dispatch ----------------------------------------------------

    def _reject_frame(self, reason: str, **kw) -> List[Msg]:
        """Drop-and-count a provably ill-formed ingress frame (never crash,
        never apply): the byzantine.* rejection family every sink reads."""
        if _obs.enabled:
            _obs.counter(f"byzantine.rejected.{reason}").inc()
        if self._debug_file is not None:
            self._debug("ingress_reject", reason=reason, **kw)
        return []

    def _ingress_auth(self, h: np.ndarray) -> bool:
        """MAC gate for SOURCE_AUTHENTICATED ingress (vsr/auth.py): the
        FIRST call in every handler of a source-authenticated command,
        before any header field is consumed — tblint's ingress-auth rule
        enforces that ordering syntactically.  Auth off: always passes
        (the zero-MAC legacy wire).  Keychain armed: a bad MAC drops-and-
        counts (auth.rejected.mac); a MISSING MAC is accepted-and-counted
        in mixed-version mode (an auth-off peer must not wedge a rolling
        upgrade) but rejected under auth_strict when the frame claims a
        cluster-replica origin."""
        if self.auth is None:
            return True
        if "mac_skip" in self.mc_mutations:
            return True  # seeded defense knockout (docs/tbmc.md)
        mac = wire.header_mac(h)
        if not mac:
            if self.auth_strict and int(h["replica"]) < self.replica_count:
                if _obs.enabled:
                    _obs.counter("auth.rejected.missing").inc()
                self._reject_frame(
                    "auth_missing", claimed=int(h["replica"])
                )
                return False
            if _obs.enabled:
                _obs.counter("auth.accepted.unauthenticated").inc()
            return True
        if "key_confusion" in self.mc_mutations:
            # Seeded knockout: verification forgets WHOSE key must match,
            # so a frame MAC'd under ANY cluster key passes — an adversary
            # can then speak as any peer using only its own key.
            hb = h.tobytes()
            ok = any(
                self.auth.mac(origin, hb) == mac
                for origin in range(self.node_count)
            )
        else:
            ok = self.auth.verify(h)
        if not ok:
            if _obs.enabled:
                _obs.counter("auth.rejected.mac").inc()
            self._reject_frame("auth_mac", claimed=int(h["replica"]))
            return False
        if _obs.enabled:
            _obs.counter("auth.verified").inc()
        return True

    # -- authenticated ack certificates (auth_strict) -------------------------

    _ACK_CERTS_MAX = 64

    def _cert_quorum(self) -> int:
        """Certificate size: > (n + f) / 2 with f = 1, so two certificates
        for the same op share an honest member — the honest single-voice
        rule (one ack per op per honest replica) then forbids certificates
        for two DIFFERENT checksums at one op."""
        return (self.replica_count + 3) // 2

    def _note_ack(self, op: int, checksum: int, replica: int) -> None:
        """Record a MAC-verified prepare_ok toward op's certificate.  An
        already-voted replica naming a SECOND checksum is equivocating:
        keep its first vote and count the evidence (the dedup the
        ``equiv_dedup`` mutation removes)."""
        certs = self._ack_certs.setdefault(op, {})
        if "equiv_dedup" not in self.mc_mutations:
            for have, voters in certs.items():
                if have != checksum and replica in voters:
                    self.byzantine_detections += 1
                    if _obs.enabled:
                        _obs.counter("auth.equivocating_acks").inc()
                    return
        certs.setdefault(checksum, set()).add(replica)
        if len(self._ack_certs) > self._ACK_CERTS_MAX:
            for stale in sorted(self._ack_certs)[
                : len(self._ack_certs) - self._ACK_CERTS_MAX
            ]:
                del self._ack_certs[stale]

    def _ack_certified(self, op: int) -> bool:
        """True iff op's JOURNALED content holds a full ack certificate.
        Only consulted under auth_strict (certificates upgrade the anchor
        check, they do not replace it for the legacy wire); the
        ``cert_downgrade`` mutation is the seeded knockout that falls back
        to anchors alone."""
        h = self.headers.get(op)
        if h is None:
            return False
        voters = self._ack_certs.get(op, {}).get(wire.header_checksum(h))
        return voters is not None and len(voters) >= self._cert_quorum()

    # Commands that only the primary of their stamped view ever originates.
    # Prepares keep the preparing primary's header through ring forwarding
    # and repair fills, so the invariant holds for EVERY honest frame of
    # these commands, current-view or archival — a frame violating it is
    # forged regardless of transport-level source authentication.
    _PRIMARY_ORIGIN_COMMANDS = (
        wire.Command.prepare, wire.Command.commit, wire.Command.start_view,
    )

    def on_message(
        self, h: np.ndarray, command: wire.Command, body: bytes
    ) -> List[Msg]:
        if wire.u128(h, "cluster") != self.cluster:
            return []
        if (
            self.ingress_verify
            and "not_primary" not in self.mc_mutations
            and command in self._PRIMARY_ORIGIN_COMMANDS
            and int(h["replica"]) != self.primary_index(int(h["view"]))
        ):
            return self._reject_frame(
                "not_primary", cmd=command.name,
                claimed=int(h["replica"]), view=int(h["view"]),
            )
        if self._block_repair is not None and command not in (
            wire.Command.block, wire.Command.ping, wire.Command.pong
        ):
            # Until our checkpoint files are whole we have no ledger to
            # serve from and no log to vote with; only repair traffic (and
            # clock pings) may proceed.
            return []
        handler = {
            wire.Command.request: self.on_request_msg,
            wire.Command.prepare: self.on_prepare,
            wire.Command.prepare_ok: self.on_prepare_ok,
            wire.Command.commit: self.on_commit,
            wire.Command.start_view_change: self.on_start_view_change,
            wire.Command.do_view_change: self.on_do_view_change,
            wire.Command.start_view: self.on_start_view,
            wire.Command.request_start_view: self.on_request_start_view,
            wire.Command.request_headers: self.on_request_headers,
            wire.Command.request_prepare: self.on_request_prepare,
            wire.Command.nack_prepare: self.on_nack_prepare,
            wire.Command.headers: self.on_headers,
            wire.Command.ping: self.on_ping,
            wire.Command.pong: self.on_pong,
            wire.Command.request_sync_checkpoint: self.on_request_sync_checkpoint,
            wire.Command.sync_checkpoint: self.on_sync_checkpoint,
            wire.Command.request_sync_roots: self.on_request_sync_roots,
            wire.Command.sync_roots: self.on_sync_roots,
            wire.Command.request_sync_subtree: self.on_request_sync_subtree,
            wire.Command.sync_subtree: self.on_sync_subtree,
            wire.Command.request_blocks: self.on_request_blocks,
            wire.Command.block: self.on_block,
            wire.Command.request_reply: self.on_request_reply,
            wire.Command.reply: self.on_reply_repair,
        }.get(command)
        if handler is None:
            return []
        return handler(h, body)

    def _hdr(self, command: wire.Command, **fields) -> np.ndarray:
        h = wire.new_header(
            command, cluster=self.cluster, view=self.view, **fields
        )
        h["replica"] = self.replica
        return h

    def _broadcast_nodes(self, message: bytes) -> List[Msg]:
        """To every node incl. standbys (the reference's
        send_header_to_other_replicas_and_standbys: pings, commit
        heartbeats, start_view)."""
        return [
            (("replica", r), message)
            for r in range(self.node_count)
            if r != self.replica
        ]

    def _broadcast(self, message: bytes) -> List[Msg]:
        return [
            (("replica", r), message)
            for r in range(self.replica_count)
            if r != self.replica
        ]

    # -- normal operation: client requests ----------------------------------

    def on_request_msg(self, h: np.ndarray, body: bytes) -> List[Msg]:
        """Client request: primary prepares + replicates; backups forward to
        the primary (replica.zig on_request :1308-1337)."""
        if self.status != NORMAL or self.is_standby:
            # Standbys never serve clients (replica.zig:4315 misdirected);
            # dropping (not forwarding) matches the reference.
            return []
        if not self.is_primary:
            return [(("replica", self.primary_index()), wire.encode(h, body))]

        client = wire.u128(h, "client")
        try:
            operation = wire.Operation(int(h["operation"]))
            self._validate_request(operation, body)
        except (ValueError, InvalidRequest):
            return []
        request_n = int(h["request"])

        session = self.sessions.get(client)
        if operation != wire.Operation.register:
            if session is None:
                # Unknown session (never registered, or capacity-evicted by
                # a newer client): the client may re-register and retry.
                return [(("client", client), self._eviction(
                    client, wire.EVICTION_NO_SESSION
                ))]
            if int(h["session"]) != session.session:
                # MISMATCH echoes the OFFENDING session: a client that
                # already re-registered after a capacity eviction discards
                # a stale MISMATCH about its old session (e.g. a backup's
                # forwarded copy of the evicted request) instead of dying
                # to it, while a live duplicate-id client — whose current
                # session matches the echo — surfaces it terminally.
                return [(("client", client), self._eviction(
                    client, wire.EVICTION_SESSION_MISMATCH,
                    session=int(h["session"]),
                ))]
            if request_n == session.request:
                if session.reply_bytes:
                    return [(("client", client), session.reply_bytes)]
                # Sync-restored session without its stored reply (the
                # client_replies zone is local-only): repair it from peers
                # (request_reply, ADVICE round-1 medium; the reference's
                # client_replies.zig read-repair path).
                return self._request_reply_repair(client)
            if request_n < session.request:
                return []
        elif session is not None:
            if session.reply_bytes:
                return [(("client", client), session.reply_bytes)]
            return self._request_reply_repair(client)
        # Drop duplicates already being prepared in the pipeline.
        for entry in self.pipeline.values():
            if entry.client == client:
                return []

        # NEW requests (everything above serves duplicates without needing a
        # timestamp) require a synchronized clock and pipeline headroom
        # (replica.zig:1322, :1330).  With overload control on, each shed is
        # SIGNALED (retryable busy + retry-after hint) instead of silently
        # dropped; off, these paths are bit-identical to before.
        if self.clock.realtime_synchronized is None:
            # Clock syncs via ping/pong rounds: retry after one round.
            return self._shed_request(h, wire.BUSY_CLOCK, PING_INTERVAL)
        if len(self.pipeline) >= self.config.pipeline_prepare_queue_max:
            # The pipeline drains at commit speed: one heartbeat away.
            return self._shed_request(h, wire.BUSY_PIPELINE, COMMIT_HEARTBEAT)
        if self.op + 1 > self.op_prepare_max:
            # WAL full until the in-flight checkpoint lands: the longest of
            # the three conditions — hint half a heartbeat budget.
            return self._shed_request(h, wire.BUSY_WAL, NORMAL_HEARTBEAT // 2)
        if self.commit_max > self.op:
            # Ops at/below the known commit watermark exist that we don't
            # hold headers for (e.g. a recovering-head DVC's commit claim):
            # assigning a FRESH op at their position would fork committed
            # history.  Repair/sync must close the gap first.
            return []

        txtrace.hop(int(h["trace"]), "consensus.ingress",
                    replica=self.replica, request=request_n)
        prepare_h, prepare_body = self._prepare(h, body, operation)
        op = int(prepare_h["op"])
        if self.blackbox is not None:
            self.blackbox.record(
                "prepare_primary", view=self.view, op=op,
                checksum=f"{wire.header_checksum(prepare_h):#x}"[:18],
                pipeline=len(self.pipeline),
            )
        self.headers[op] = prepare_h
        self.pipeline[op] = PipelineEntry(
            op=op,
            checksum=wire.header_checksum(prepare_h),
            client=client,
            ok_from={self.replica},
        )
        out: List[Msg] = []
        if self.auth is not None and self.auth_strict:
            # The primary's own attestation joins the certificate: backups
            # need _cert_quorum() distinct votes, the leader's included.
            self._append_ok(out, prepare_h)
        message = wire.encode(prepare_h, prepare_body)
        successor = self._ring_successor()
        if successor is not None:
            out.append((("replica", successor), message))
        self._maybe_commit_pipeline(out)
        return out

    _BUSY_REASON_NAMES = {
        wire.BUSY_PIPELINE: "pipeline",
        wire.BUSY_WAL: "wal",
        wire.BUSY_CLOCK: "clock",
        wire.BUSY_QUEUE: "queue",
    }

    def _shed_request(
        self, h: np.ndarray, reason: int, retry_after_ticks: int
    ) -> List[Msg]:
        """Shed a new client request the primary cannot admit.  Overload
        control OFF: silent drop, bit-identical to the pre-overload path.
        ON: signal — a retryable busy with a retry-after hint, plus the
        overload.* shed accounting."""
        if not self.overload_control:
            return []
        name = self._BUSY_REASON_NAMES.get(reason, "unknown")
        if _obs.enabled:
            _obs.counter(f"overload.shed.{name}").inc()
            _obs.counter("overload.busy_sent").inc()
        self._debug(
            "shed_request", reason=name,
            client=f"{wire.u128(h, 'client'):#x}",
            request=int(h["request"]),
        )
        client = wire.u128(h, "client")
        message = overload.busy_message(
            self.replica, self.cluster, self.view, h, reason,
            retry_after_ticks,
        )
        return [(("client", client), message)]

    def _primary_now(self) -> int:
        now = self.clock.realtime_synchronized
        assert now is not None
        return now

    def _ring_successor(self) -> Optional[int]:
        """Next replica in the replication ring (replica.zig:1339-1363);
        the last active backup jumps off to the standby ring
        (replica.zig:6067-6101); None when the chain completes."""
        if self.replica_count == 1:
            return None
        if not self.is_standby:
            nxt = (self.replica + 1) % self.replica_count
            if nxt != self.primary_index():
                return nxt
        if self.standby_count == 0:
            return None
        # Standby ring rotates with the view so no standby is permanently
        # last (standby_index_to_replica).
        first_standby = self.replica_count + (self.view % self.standby_count)
        if not self.is_standby:
            return first_standby
        my_index = self.replica - self.replica_count
        next_standby = self.replica_count + (
            (my_index + 1) % self.standby_count
        )
        if next_standby != first_standby:
            return next_standby
        return None

    # -- normal operation: replication ---------------------------------------

    def _request_reply_repair(self, client: int) -> List[Msg]:
        """Ask peers for a client's last stored reply (the sync-restored
        session has the request number but not the reply bytes).  checksum 0
        = 'whatever reply you hold for this client's CURRENT session' — the
        session number in the request stops a lagging peer from serving a
        previous session's reply for an equal request number."""
        req = self._hdr(
            wire.Command.request_reply, client=client,
            session=self.sessions[client].session,
        )
        return self._broadcast(wire.encode(req))

    def on_request_reply(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        client = wire.u128(h, "client")
        s = self.sessions.get(client)
        if s is None or not s.reply_bytes or s.session != int(h["session"]):
            return []
        want = wire.u128(h, "reply_checksum")
        if want:
            stored_h, _ = wire.decode_header(s.reply_bytes[: wire.HEADER_SIZE])
            if wire.header_checksum(stored_h) != want:
                return []
        return [(("replica", int(h["replica"])), s.reply_bytes)]

    def on_reply_repair(self, h: np.ndarray, body: bytes) -> List[Msg]:
        """A repaired reply arriving from a peer: adopt it into the session
        and resend to the client."""
        client = wire.u128(h, "client")
        s = self.sessions.get(client)
        if s is None or s.reply_bytes or int(h["request"]) != s.request:
            return []
        raw = wire.encode(h, body)
        s.reply_bytes = raw
        self._persist_reply(client, raw)
        return [(("client", client), raw)]

    def _persist_reply(self, client: int, raw: bytes) -> None:
        """Write a repaired reply into the local client_replies zone so it
        survives restart (mirrors the normal commit path's store)."""
        try:
            self._store_client_reply(client, raw)
        except OSError as err:
            # Repair is best-effort (the reply still went out over the
            # wire), but a disk that rejects the write is worth a record —
            # a silent swallow here hid a full-disk wedge in round 5.
            self._debug("persist_reply_failed", client=client,
                        error=f"{type(err).__name__}: {err}")

    def on_prepare(self, h: np.ndarray, body: bytes) -> List[Msg]:
        view = int(h["view"])
        op = int(h["op"])
        checksum = wire.header_checksum(h)
        out: List[Msg] = []

        # Repair fills are VIEW-AGNOSTIC: a stored prepare keeps the view it
        # was originally prepared in; its identity is its checksum / position
        # in the hash chain, so responses to request_prepare must be accepted
        # even when their header view predates ours (and even mid
        # view-change — the new primary repairs canonical bodies then).
        if op in self.missing and self.missing[op] == checksum:
            self._fill_missing(h, body)
            if self.status == NORMAL:
                self._append_ok(out, h)
                if self.is_primary:
                    # The primary may already hold ack quorums for this and
                    # later pipeline entries (the commit stalled on OUR
                    # missing/corrupt journal copy — VOPR seed 10058):
                    # commit via the pipeline, which advances commit_max.
                    self._maybe_commit_pipeline(out)
                else:
                    self._commit_journal(out)
            return out

        if op > self.op_prepare_max:
            # WAL bound (vsr.zig op_prepare_max): journaling this would
            # overwrite a ring slot holding an op we have not committed.
            # Drop — don't even stash (a stalled replica would accumulate a
            # ring's worth) — the primary's resends / repair refetch it once
            # our checkpoint advances.
            return out

        if view < self.view:
            if self.status == NORMAL and op <= self.op:
                existing = self.headers.get(op)
                if existing is not None and (
                    wire.header_checksum(existing) == checksum
                ):
                    # Duplicate of an adopted prepare (e.g. the new primary's
                    # resend of a re-certified old-view suffix): re-ack in
                    # the CURRENT view.
                    self._append_ok(out, h)
                elif existing is None and op > self.commit_min:
                    self.stash[op] = (h, body)
                    self._fill_gaps(out)
            return out
        if view > self.view or self.status == RECOVERING:
            # We're behind a view change (or freshly restarted): stash and
            # ask the new primary for start_view.
            self.stash[op] = (h, body)
            return self._request_start_view(view)
        if self.status != NORMAL:
            self.stash[op] = (h, body)
            return []

        self._primary_spoke()
        self.commit_max = max(self.commit_max, int(h["commit"]))

        if op <= self.op:
            existing = self.headers.get(op)
            if existing is not None and wire.header_checksum(existing) == checksum:
                self._append_ok(out, h)
            elif existing is None and op > self.commit_min:
                # Header-gap fill (e.g. a start_view whose header window did
                # not reach back to our commit_min): verify DOWNWARD via the
                # parent link of the next header before adopting.
                self.stash[op] = (h, body)
                self._fill_gaps(out)
            elif existing is not None:
                if "equiv_dedup" in self.mc_mutations:
                    # Seeded knockout (docs/tbmc.md): the keep-first rule
                    # is what makes an honest replica speak ONCE per op.
                    # Adopting-and-acking the conflicting copy lets an
                    # equivocating primary assemble ack certificates for
                    # BOTH forks of the same op.
                    self.journal.write_prepare(wire.encode(h, body))
                    self.headers[op] = h
                    if op == self.op:
                        self.parent_checksum = checksum
                    self._append_ok(out, h)
                elif _obs.enabled:
                    # Two different prepares for the same op in the SAME
                    # view: an honest primary assigns each op once, so this
                    # is equivocation evidence (the conflicting frame is
                    # dropped either way; the commit-checksum anchor
                    # adjudicates which copy is canonical).
                    _obs.counter("byzantine.prepare_conflicts").inc()
            return out

        if op == self.op + 1 and wire.u128(h, "parent") == self.parent_checksum:
            self._journal_prepare(h, body)
            txtrace.hop(int(h["trace"]), "consensus.prepare",
                        replica=self.replica, op=op)
            if self.blackbox is not None:
                self.blackbox.record(
                    "prepare", view=view, op=op,
                    checksum=f"{checksum:#x}"[:18],
                    stash=len(self.stash), missing=len(self.missing),
                )
            self._append_ok(out, h)
            successor = self._ring_successor()
            if successor is not None and successor != int(h["replica"]):
                out.append((("replica", successor), wire.encode(h, body)))
            self._drain_stash(out)
            self._commit_journal(out)
        else:
            if (
                self.ingress_verify
                and op == self.op + 1
                and self.op > self.commit_min
                and _obs.enabled
            ):
                # A same-view prepare extending the chain names a different
                # checksum for our uncommitted head: equivocation evidence.
                # Observability only — a single unauthenticated frame must
                # NOT evict the head (a forged parent claim would discard a
                # journaled, possibly-acked op and poison the repair target
                # with an unfulfillable checksum); adjudication belongs to
                # the source-authenticated anchors (on_commit,
                # _content_certified) and the anchor-certified headers
                # path (on_headers).
                _obs.counter("byzantine.prepare_conflicts").inc()
            # Gap (lost prepare) or fork: stash and repair.
            self.stash[op] = (h, body)
            out.extend(self._repair_gaps())
        return out

    def _journal_prepare(self, h: np.ndarray, body: bytes) -> None:
        self.journal.write_prepare(wire.encode(h, body))
        self.headers[int(h["op"])] = h
        self.op = int(h["op"])
        self.parent_checksum = wire.header_checksum(h)

    def _append_ok(self, out: List[Msg], prepare_h: np.ndarray) -> None:
        """Queue a prepare_ok — unless we are a standby (standbys receive
        and replicate prepares but NEVER ack: they must not count toward
        commit quorums, replica.zig:4877)."""
        if self.is_standby:
            return
        if self.auth is not None and self.auth_strict:
            # Authenticated ack certificates: the ack goes to EVERY replica
            # (not just the primary) so backups can assemble a
            # _cert_quorum() certificate before executing; our own vote is
            # recorded locally (no loopback delivery).
            _, frame = self._send_prepare_ok(prepare_h)
            out.extend(
                (("replica", r), frame)
                for r in range(self.replica_count)
                if r != self.replica
            )
            self._note_ack(
                int(prepare_h["op"]),
                wire.header_checksum(prepare_h), self.replica,
            )
        else:
            out.append(self._send_prepare_ok(prepare_h))

    def _send_prepare_ok(self, prepare_h: np.ndarray) -> Msg:
        txtrace.hop(int(prepare_h["trace"]), "consensus.ack",
                    replica=self.replica, op=int(prepare_h["op"]))
        ok = self._hdr(
            wire.Command.prepare_ok,
            parent=wire.u128(prepare_h, "parent"),
            prepare_checksum=wire.header_checksum(prepare_h),
            client=wire.u128(prepare_h, "client"),
            op=int(prepare_h["op"]),
            commit=self.commit_min,
            timestamp=int(prepare_h["timestamp"]),
            request=int(prepare_h["request"]),
            operation=int(prepare_h["operation"]),
        )
        return (("replica", self.primary_index()), wire.encode(ok, b""))

    def _drain_stash(self, out: List[Msg]) -> None:
        """Chain in any stashed prepares that now fit."""
        while self.op + 1 in self.stash and self.op + 1 <= self.op_prepare_max:
            h, body = self.stash.pop(self.op + 1)
            if wire.u128(h, "parent") != self.parent_checksum:
                break
            self._journal_prepare(h, body)
            self._append_ok(out, h)
        # Prune committed stash entries (gap fills for ops <= self.op with
        # unknown headers stay until _fill_gaps verifies them).
        for op in [o for o in self.stash if o <= self.commit_min]:
            del self.stash[op]

    def _fill_gaps(self, out: List[Msg]) -> None:
        """Adopt stashed prepares for header-gap ops, verifying each against
        the parent link of the header above it (downward hash-chain walk),
        then commit as far as possible."""
        changed = True
        while changed:
            changed = False
            for op in sorted(self.stash, reverse=True):
                if op > self.op or op <= self.commit_min:
                    continue
                if self.headers.get(op) is not None:
                    continue
                nxt = self.headers.get(op + 1)
                if nxt is None:
                    continue
                h, body = self.stash[op]
                if wire.u128(nxt, "parent") == wire.header_checksum(h):
                    self.journal.write_prepare(wire.encode(h, body))
                    self.headers[op] = h
                    del self.stash[op]
                    self._append_ok(out, h)
                    self._repipeline(op, h)
                    changed = True
        self._commit_journal(out)

    def _header_gaps(self, limit: int = 8) -> List[int]:
        """Ops above commit_min with no known header (unrepairable via
        `missing`, which needs a checksum).  Returns the HIGHEST ops of the
        gap: adoption verifies downward from the known header above, so the
        top of the gap must fill first."""
        gaps = [
            op
            for op in range(self.commit_min + 1, self.op + 1)
            if op not in self.headers
        ]
        return gaps[-limit:]

    def on_prepare_ok(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        if int(h["replica"]) >= self.replica_count:
            return []  # a standby's ack must never count (defense in depth)
        if self.auth is not None and self.auth_strict:
            # Certificate assembly: every replica collects MAC-verified
            # acks (the strict-mode broadcast), then a backup retries the
            # commit gate — it may have been waiting on exactly this vote.
            self._note_ack(
                int(h["op"]), wire.u128(h, "prepare_checksum"),
                int(h["replica"]),
            )
            if not self.is_primary:
                out: List[Msg] = []
                self._commit_journal(out)
                return out
        if self.status != NORMAL or not self.is_primary:
            return []
        if int(h["view"]) != self.view:
            return []
        op = int(h["op"])
        entry = self.pipeline.get(op)
        if entry is None or entry.checksum != wire.u128(h, "prepare_checksum"):
            return []
        entry.ok_from.add(int(h["replica"]))
        if len(entry.ok_from) == self.quorum_replication:
            # Reset only on REAL progress (an entry reaching quorum) — a
            # duplicate ok, or oks for other entries, must not starve the
            # re-broadcast of a stuck one.
            self._prepare_timeout.reset(self._ticks)
        out: List[Msg] = []
        self._maybe_commit_pipeline(out)
        return out

    def _maybe_commit_pipeline(self, out: List[Msg]) -> None:
        """Commit pipeline entries in op order as quorums arrive."""
        while True:
            op = self.commit_min + 1
            entry = self.pipeline.get(op)
            if entry is None or len(entry.ok_from) < self.quorum_replication:
                break
            self.commit_max = max(self.commit_max, op)
            self._commit_journal(out)
            if self.commit_min < op:
                break  # body missing (shouldn't happen at the primary)
            self.pipeline.pop(op, None)

    def on_commit(self, h: np.ndarray, body: bytes) -> List[Msg]:
        """Commit-number heartbeat from the primary (replica.zig :1591)."""
        if not self._ingress_auth(h):
            return []
        view = int(h["view"])
        if view < self.view:
            return []
        if self.status == SYNCING:
            # Keep the sync target fresh: if the primary checkpointed again
            # mid-fetch, restart against the new snapshot (the responder
            # only serves its exact current checkpoint).  refresh=True:
            # the restart must NOT reset the progress/resend clocks — a
            # refresh is not progress, and under a sustained flood (a new
            # checkpoint every ~interval ops) resetting them here starved
            # the stall rotation forever while the pinned responder was
            # dead (the stranded-sync wedge).
            new_ckpt = int(h["checkpoint_op"])
            if self.sync_target is not None and (
                new_ckpt > self.sync_target["checkpoint_op"]
            ):
                return self._enter_sync(new_ckpt, refresh=True)
            return []
        if view > self.view or self.status == RECOVERING:
            return self._request_start_view(view)
        if self.status != NORMAL or self.is_primary:
            return []
        self._primary_spoke()
        out: List[Msg] = []
        # Commit-content anchoring (byzantine domain): the heartbeat names
        # the checksum of the op it commits.  If OUR header for that op
        # differs, a forged prepare equivocated its content into our chain
        # — evict the fork and repair the canonical body (by checksum, so
        # repair responses are unforgeable) BEFORE the commit path can
        # execute it.  checksum 0 = unanchored (legacy/pruned): skip.
        want = wire.u128(h, "commit_checksum")
        commit_op = int(h["commit"])
        if self.blackbox is not None:
            self.blackbox.record("commit_heartbeat", view=view,
                                 commit=commit_op)
        if want:
            self._note_anchor(commit_op, want)
        if (
            self.ingress_verify and want and commit_op > self.commit_min
            and "anchor_certify" not in self.mc_mutations
        ):
            mine = self.headers.get(commit_op)
            if mine is not None and wire.header_checksum(mine) != want and (
                self._anchor_trusted(commit_op, want)
            ):
                self.byzantine_detections += 1
                if _obs.enabled:
                    _obs.counter("byzantine.equivocation_detected").inc()
                self._debug(
                    "commit_checksum_conflict", op=commit_op,
                    mine=f"{wire.header_checksum(mine):#x}"[:18],
                )
                self._evict_fork(commit_op, want)
                self.commit_max = max(self.commit_max, commit_op)
                out.extend(self._request_missing())
                return out
            if mine is None and self.missing.get(commit_op, want) != want \
                    and self._anchor_trusted(commit_op, want):
                # A forged frame polluted the repair target for this op;
                # the source-authenticated anchor corrects it (honest runs
                # already record the canonical checksum — this is a no-op
                # there).
                self.missing[commit_op] = want
        self.commit_max = max(self.commit_max, commit_op)
        self._commit_journal(out)
        out.extend(self._maybe_start_sync(int(h["checkpoint_op"])))
        return out

    def _note_anchor(self, op: int, checksum: int) -> None:
        """Record a source-authenticated content anchor; bounded by the
        live journal window (pruned below commit_min)."""
        if op <= self.commit_min and op in self._anchors:
            return
        self._anchors[op] = checksum
        if len(self._anchors) > 64:
            for o in [o for o in self._anchors if o < self.commit_min]:
                del self._anchors[o]

    def _anchor_trusted(self, op: int, checksum: int) -> bool:
        """May this anchor EVICT journaled content / pin repair targets?

        Legacy (auth off): yes — anchors are source-authenticated by the
        transport, and the byzantine fault domain models only Byzantine
        BACKUPS, so a commit heartbeat's anchor is honest by assumption.

        Under strict wire auth the primary SEAT itself is in the threat
        model: its forged heartbeat carries a perfectly valid own-key MAC,
        and a bare anchor must not be able to evict an honest journaled
        prepare (whose ack may already have let the cluster commit it —
        the quorum_journal violation the tbmc byzantine-primary scope
        found).  Destructive anchor actions therefore additionally require
        a REPLICATION QUORUM of MAC-verified acks for the anchored
        checksum: every honest anchor has one (the preparing primary's
        attestation plus the backups that acked — all broadcast under
        strict mode), while a Byzantine primary can muster only its own
        vote for a fork it invented."""
        if self.auth is None or not self.auth_strict:
            return True
        voters = self._ack_certs.get(op, {}).get(checksum)
        if voters is not None and len(voters) >= self.quorum_replication:
            return True
        if _obs.enabled:
            _obs.counter("auth.rejected.unsupported_anchor").inc()
        return False

    def _content_certified(self, op: int) -> bool:
        """True iff the journaled content at ``op`` parent-chains up to a
        source-authenticated anchor (see _anchors).  Walking DOWN from the
        anchor, any non-linking header is a detected fork: evicted, with
        the canonical checksum recorded for repair-by-checksum."""
        if "anchor_certify" in self.mc_mutations:
            # Seeded mutation (tools/tbmc): certified commits compiled out
            # — backups execute whatever chains locally, anchored or not.
            return True
        for a in sorted(o for o in self._anchors if o >= op):
            if a > self.op:
                break  # no headers past our head to walk from
            h = self.headers.get(a)
            if h is None:
                continue
            if wire.header_checksum(h) != self._anchors[a]:
                if not self._anchor_trusted(a, self._anchors[a]):
                    # Vote-unsupported anchor conflicting with our journal:
                    # the anchor itself is the suspect (Byzantine primary
                    # seat) — never certify through it, never evict for it.
                    continue
                self.byzantine_detections += 1
                if _obs.enabled:
                    _obs.counter("byzantine.equivocation_detected").inc()
                self._debug("anchor_fork_evicted", op=a)
                self._evict_fork(a, self._anchors[a])
                return False
            k = a
            while k > op:
                hk = self.headers.get(k)
                below = self.headers.get(k - 1)
                if hk is None or below is None:
                    return False  # header gap: repair must fill first
                parent = wire.u128(hk, "parent")
                if wire.header_checksum(below) != parent:
                    if not self._anchor_trusted(k - 1, parent):
                        return False
                    self.byzantine_detections += 1
                    if _obs.enabled:
                        _obs.counter(
                            "byzantine.equivocation_detected"
                        ).inc()
                    self._debug("anchor_chain_fork_evicted", op=k - 1)
                    self._evict_fork(k - 1, parent)
                    return False
                k -= 1
            return True
        return False

    def _evict_fork(self, op: int, canonical_checksum: int) -> None:
        """An uncommitted header at ``op`` is provably not the canonical
        ``canonical_checksum``: evict it and schedule a repair fetch by the
        canonical checksum.  The chain walk and the repair fill's downward
        cascade (_fill_missing) evict any forged ancestors the same way."""
        assert op > self.commit_min
        self.headers.pop(op, None)
        self.stash.pop(op, None)
        self.pipeline.pop(op, None)
        self._nacks.pop(op, None)
        self.missing[op] = canonical_checksum

    def _extend_verification(self) -> None:
        """Walk the parent chain DOWN from the verification floor, marking
        headers canonical — and EVICTING a header that does not chain (a
        stale fork from a discarded view, surviving in our WAL across a
        restart).  Evicted ops become header gaps; the repair machinery
        fetches the canonical headers, the gap-fill adoption re-verifies
        them downward, and this walk resumes."""
        while self._verify_floor > self.commit_min + 1:
            f = self._verify_floor
            h = self.headers.get(f)
            below = self.headers.get(f - 1)
            if h is None or below is None:
                if self._debug_file is not None:
                    self._debug(
                        "verify_walk_gap", floor=f,
                        have_f=h is not None, have_below=below is not None,
                        commit_min=self.commit_min,
                    )
                return  # a gap: repair must fetch headers first
            if wire.u128(h, "parent") == wire.header_checksum(below):
                self._verify_floor = f - 1
                continue
            del self.headers[f - 1]
            self.stash.pop(f - 1, None)
            self.missing.pop(f - 1, None)
            # A primary's re-certification entry built from the stale
            # header can never quorum (backups ack the canonical checksum);
            # drop it — _repipeline rebuilds it when the canonical header
            # is adopted.
            self.pipeline.pop(f - 1, None)
            return

    def _repipeline(self, op: int, h: np.ndarray) -> None:
        """Primary: (re)create the pipeline entry for an uncommitted op
        whose canonical header was adopted via repair (the entry from
        _finish_view_change may have been built from a since-evicted stale
        header; see _extend_verification)."""
        if self.status != NORMAL or not self.is_primary:
            return
        if not (self.commit_min < op <= self.op):
            return
        checksum = wire.header_checksum(h)
        entry = self.pipeline.get(op)
        if entry is None or entry.checksum != checksum:
            self.pipeline[op] = PipelineEntry(
                op=op, checksum=checksum,
                client=wire.u128(h, "client"), ok_from={self.replica},
            )

    def _commit_journal(self, out: List[Msg]) -> bool:
        """Execute journaled ops up to min(commit_max, op), in order
        (replica.zig commit_journal :3176).

        ``commit_budget`` (set by the TCP bus; None = unlimited for the
        sim/VOPR) bounds the ops executed per call: the reference commits
        through an async IO chain that never monopolizes its event loop
        (replica.zig commit_dispatch stages), and a Python replica must
        match that or a large commit backlog blocks heartbeats AND pongs
        for hundreds of ms — measured cluster-wide as primary-liveness
        probes and client failover spikes.  Returns True iff the call
        stopped on budget with backlog remaining (the bus's commit pump
        resumes on the next loop iteration)."""
        self._extend_verification()
        done = 0
        self.commit_budget_stopped = False
        while self.commit_min < min(self.commit_max, self.op):
            if self.commit_budget is not None and done >= self.commit_budget:
                self.commit_budget_stopped = True
                return True
            op = self.commit_min + 1
            if self.replica_count > 1 and op < self._verify_floor:
                # Suspect suffix (restart before the canonical chain was
                # re-established): committing now could execute a prepare a
                # view change discarded.  Repair verifies or replaces it.
                break
            h = self.headers.get(op)
            if h is None:
                break
            if (
                self.ingress_verify and self.replica_count > 1
                and not self.is_primary and not self._content_certified(op)
            ):
                # CERTIFIED COMMITS (byzantine domain): a backup executes
                # only content that chains to a source-authenticated
                # anchor.  Waiting costs at most one commit-heartbeat
                # interval in honest runs; executing early is how a forged
                # relayed prepare becomes committed state.
                break
            if (
                self.auth is not None and self.auth_strict
                and "cert_downgrade" not in self.mc_mutations
                and self.replica_count > 1 and not self.is_primary
                and not self._ack_certified(op)
            ):
                # AUTHENTICATED CERTIFICATES (auth_strict): anchors alone
                # are not proof against a lying PRIMARY — its own-key
                # heartbeat MAC verifies, so it can anchor forked content.
                # Execution additionally requires _cert_quorum() distinct
                # MAC-verified acks naming this exact checksum; quorum
                # intersection plus the honest one-vote-per-op rule makes
                # a second certificate for different content impossible.
                break
            read = self.journal.read_prepare(op)
            if read is None or wire.header_checksum(read[0]) != (
                wire.header_checksum(h)
            ):
                self.missing[op] = wire.header_checksum(h)
                break
            if self._debug_file is not None or self.blackbox is not None:
                self._debug(
                    "commit_op", op=op,
                    operation=int(read[0]["operation"]),
                    prep_view=int(read[0]["view"]),
                    ts=int(read[0]["timestamp"]),
                )
            txtrace.hop(int(read[0]["trace"]), "consensus.commit",
                        replica=self.replica, op=op)
            reply = self._commit_prepare(read[0], read[1], replay=False)
            entry = self.pipeline.pop(op, None)
            if self.is_primary and reply is not None:
                client = wire.u128(read[0], "client")
                if client:
                    out.append((("client", client), reply))
            if self._checkpoint_due():
                # Checkpoint INSIDE the commit loop, so it lands exactly on
                # op_checkpoint + interval on every replica regardless of
                # commit batching — aligned checkpoint ops make the forests
                # byte-identical across replicas (deterministic deltas),
                # which peer block repair depends on (vsr.zig
                # Checkpoint.checkpoint_after's fixed schedule).
                self.checkpoint()
                self._prune_headers()
            done += 1
        return False

    def _prune_headers(self) -> None:
        floor = self.op_checkpoint - 1
        for op in [o for o in self.headers if o < floor]:
            del self.headers[op]

    # -- view change ---------------------------------------------------------

    def _debug(self, event: str, **kw) -> None:
        box = self.blackbox
        if box is not None:
            # Every debug-channel event also lands in the flight recorder
            # (obs/txtrace.Blackbox): the recorder is on in the simulator
            # even when the debug file is not, so postmortem dumps carry
            # the protocol history leading into a failure.
            rec = {"view": self.view, "status": self.status}
            rec.update(kw)
            box.record(event, **rec)
        if self._debug_file is None:
            return
        import json as _json

        rec = {
            "ms": round(self._monotonic() / 1e6, 1),
            "r": self.replica, "view": self.view,
            "status": self.status, "ev": event,
        }
        rec.update(kw)
        self._debug_file.write(_json.dumps(rec) + "\n")

    def _maybe_clear_log_suspect(self) -> None:
        """A recovering-head replica whose log is REPAIRED may rejoin view
        changes: every byte of amputation evidence has been resolved —
        commits caught up to the durable floor, the hash chain verified
        down to it, no missing bodies, no header gaps.  At that point the
        log provably matches committed history and the suspicion (which
        exists because an amputated WAL cannot prove what it acked) no
        longer applies: anything it once acked and lost was either
        committed (now repaired back in) or nack-truncated (provably never
        committed)."""
        if not getattr(self, "_log_suspect", False):
            return
        persisted = getattr(self._sb_state, "commit_min", 0)
        persisted_adopted = getattr(self._sb_state, "log_adopted_op", 0)
        if (
            self.commit_min >= persisted
            # The head must be restored through EVERY durable watermark:
            # log_adopted_op records how far the durable log_view's log was
            # known to extend at adoption — clearing with a shorter head
            # re-arms the seed-500285 truncation (a clean-voting
            # (log_view, short-op) DVC out-ranking an intact log).  The
            # repair machinery CAN drive op there (the headers exist
            # cluster-wide); heartbeat-learned commit_max it could not.
            and self.op >= max(persisted, persisted_adopted)
            and self._verify_floor <= self.commit_min + 1
            and not self.missing
            and not self._header_gaps()
        ):
            self._log_suspect = False
            self._debug(
                "log_suspect_cleared", op=self.op, commit=self.commit_min
            )

    def _primary_spoke(self, real: bool = True) -> None:
        """Record primary-liveness evidence: fold the silence gap into the
        EWMA (feeds the adaptive suspicion budget) and stand down any
        pending probe.  ``real=False`` marks pong-only evidence — a wedged
        primary whose IO loop still answers pings must not defer elections
        forever, so pong-only stand-downs are capped between real words."""
        if real:
            self._pong_standdowns = 0
        else:
            self._pong_standdowns += 1
            if self._pong_standdowns > 3:
                return  # wedged, not busy: let the election proceed
        gap = self._ticks - self._last_primary_word
        if 0 < gap <= PRIMARY_BUDGET_CAP:
            self._primary_gap_ewma += 0.125 * (gap - self._primary_gap_ewma)
        self._last_primary_word = self._ticks
        self._probe_sent_at = None

    def _begin_view_change(self, new_view: int) -> List[Msg]:
        """Move to view_change status for new_view and broadcast SVC
        (replica.zig on view-change timeout)."""
        if self.is_standby:
            return []  # standbys never campaign
        self._debug("begin_view_change", new_view=new_view)
        assert new_view > self.view or (
            new_view == self.view and self.status != NORMAL
        )
        self.view = new_view
        self.status = VIEW_CHANGE
        self._vc_started = self._ticks
        self._vc_timeout.reset(self._ticks)
        self._dvc_sent_for = None
        self._nacks.clear()
        # A candidacy for an OLDER view is abandoned here: finishing it
        # later (deferred-finish paths) would regress self.view — and
        # durably, via _persist_view — leaving a phantom primary of a dead
        # view.
        self._new_view_pending = None
        self._pending_finish = None
        self.pipeline.clear()
        self._persist_view()
        self.svc_from.setdefault(new_view, set()).add(self.replica)
        svc = self._hdr(wire.Command.start_view_change)
        out = self._broadcast(wire.encode(svc, b""))
        out.extend(self._maybe_send_dvc())
        return out

    def on_start_view_change(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        view = int(h["view"])
        if view < self.view or self.replica_count == 1:
            return []
        if self.is_standby or int(h["replica"]) >= self.replica_count:
            # Standbys neither vote nor count (replica.zig:4613); a standby
            # tracks new views via prepares/commits/request_start_view.
            return []
        if self.sync_target is not None:
            # A syncing replica has no log to vote with; joining the view
            # change would strand the half-fetched snapshot (sync_target
            # survives but nothing resumes it).  Keep syncing; we rejoin
            # via request_start_view after the install.
            return []
        out: List[Msg] = []
        if view > self.view:
            out.extend(self._begin_view_change(view))
        elif self.status == NORMAL:
            # Current view is live; ignore stragglers.
            return []
        self.svc_from.setdefault(view, set()).add(int(h["replica"]))
        out.extend(self._maybe_send_dvc())
        return out

    def _maybe_send_dvc(self) -> List[Msg]:
        """At an SVC quorum, send do_view_change to the new primary
        (replica.zig send_do_view_change)."""
        if self.status != VIEW_CHANGE:
            return []
        # Recovering-head replicas (replica.zig status.recovering_head)
        # SEND their DVC too, flagged log_suspect: the receiver excludes
        # it from the quorum and the donor set unless every replica is
        # present (see on_do_view_change).  The suspicion predicate is
        # narrow (foreign/corrupt slots, recovered headers beyond the
        # head, persisted commit bounds above the head): a benign torn
        # tail leaves no recovered header (the headers ring is written
        # last), so ordinary crash-restarts are not suspect.
        if len(self.svc_from.get(self.view, ())) < self.quorum_view_change:
            return []
        return self._send_dvc()

    def _suspect_flag(self) -> int:
        """0 = clean; 1 = ordinary (amputation-evidence) suspicion;
        2 = PROMOTION suspicion — the retired voter's journal (and acks)
        were deliberately destroyed, so this log must not donate even
        under the all-replicas-present valve (its premise, 'every
        possible acker is inside the quorum', is false after promotion)."""
        if not getattr(self, "_log_suspect", False):
            return 0
        from .superblock import PROMOTION_SUSPECT_OP

        if getattr(self, "_log_adopted_op", 0) >= PROMOTION_SUSPECT_OP:
            return 2
        return 1

    def _send_dvc(self) -> List[Msg]:
        self._dvc_sent_for = self.view
        dvc = self._hdr(
            wire.Command.do_view_change,
            op=self.op,
            commit=self.commit_min,
            checkpoint_op=self.op_checkpoint,
            log_view=self.log_view,
            log_suspect=self._suspect_flag(),
        )
        body = wire.pack_headers(self._suffix_headers())
        message = wire.encode(dvc, body)
        new_primary = self.primary_index()
        if new_primary == self.replica:
            decoded, _, dbody = wire.decode(message)
            return self.on_do_view_change(decoded, dbody)
        return [(("replica", new_primary), message)]

    def _suffix_headers(self) -> List[np.ndarray]:
        """The journal-suffix headers that fit one message body (newest
        last); covers at least a full checkpoint interval by config."""
        k_max = self.config.message_body_size_max // wire.HEADER_SIZE
        ops = sorted(o for o in self.headers if o <= self.op)[-k_max:]
        return [self.headers[o] for o in ops]

    def on_do_view_change(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        view = int(h["view"])
        if view < self.view:
            return []
        if self.is_standby or int(h["replica"]) >= self.replica_count:
            return []  # standbys neither gather nor donate DVCs
        if self.sync_target is not None:
            return []  # syncing: see on_start_view_change
        out: List[Msg] = []
        if view > self.view:
            out.extend(self._begin_view_change(view))
        if self.primary_index(view) != self.replica or self.status == NORMAL:
            return out
        try:
            headers = wire.unpack_headers(body)
        except ValueError:
            return out
        # Recovering-head (log_suspect) DVCs are stored but normally
        # neither count toward the quorum nor donate: an amputated WAL
        # cannot prove what it once acked, so counting its vote breaks the
        # commit-quorum/view-change-quorum intersection argument (VOPR
        # seed 500285: a suspect vote let a view change truncate an op a
        # partitioned member had committed).  The way out of suspicion is
        # repair (_maybe_clear_log_suspect).
        #
        # ONE exception (VOPR seed 400396): when EVERY replica's DVC is
        # present, suspect votes are safe — every possible acker of every
        # op is inside the quorum, and a committed op (quorum-journaled,
        # synced writes survive crashes, the fault atlas forbids corrupting
        # a quorum's copies) cannot have vanished from all of them — so the
        # max-(log_view, op) log still contains all committed history.
        # Without this valve an f=0 pair whose both logs are suspect
        # escalates views forever.
        self.dvc_from.setdefault(view, {})[int(h["replica"])] = {
            "log_view": int(h["log_view"]),
            "op": int(h["op"]),
            "commit": int(h["commit"]),
            "headers": headers,
            "suspect": bool(int(h["log_suspect"])),
            "promotion": int(h["log_suspect"]) == 2,
        }
        my_flag = self._suspect_flag()
        self.dvc_from[view][self.replica] = {
            "log_view": self.log_view,
            "op": self.op,
            "commit": self.commit_min,
            "headers": self._suffix_headers(),
            "suspect": my_flag != 0,
            "promotion": my_flag == 2,
        }
        dvcs = self.dvc_from[view]
        clean_n = sum(1 for d in dvcs.values() if not d.get("suspect"))
        if clean_n >= self.quorum_view_change or (
            len(dvcs) == self.replica_count
        ):
            out.extend(self._install_canonical_log(view))
        return out

    def _install_canonical_log(self, view: int) -> List[Msg]:
        """New primary: adopt the log of the DVC with max (log_view, op)
        (replica.zig primary_set_log_from_do_view_change_messages)."""
        dvcs = self.dvc_from[view]
        clean = {r: d for r, d in dvcs.items() if not d.get("suspect")}
        if len(clean) >= self.quorum_view_change:
            # Normal case: only clean logs select (see on_do_view_change).
            donors = clean
        else:
            # All-replicas-present fallback: every acker is in the quorum,
            # so the best log over ALL DVCs still holds committed history
            # — EXCEPT promotion-suspects: their retired predecessor's
            # journal (with the acks it contributed) was destroyed outside
            # the fault atlas, so the valve's premise does not cover them.
            # A committed op still lives on its commit quorum of REAL
            # voter journals, all of which are in dvcs here.
            assert len(dvcs) == self.replica_count
            donors = {
                r: d for r, d in dvcs.items() if not d.get("promotion")
            }
            if not donors:
                # Every log is a promoted identity: the operator destroyed
                # the entire voting history — refuse to invent a canonical
                # log (safety over liveness; view-change timeouts retry).
                return []
        # Donor selection iterates SORTED items: ties on (log_view, op)
        # used to fall to dict insertion order — DVC *arrival* order — so
        # two replicas in identical protocol states could adopt
        # differently-sourced (content-identical) suffixes, and the tbmc
        # canonical-state hash could not collapse them.  At equal
        # (log_view, op) both logs carry that log_view's canonical suffix,
        # so the lowest-replica tie-break is safe by construction.
        canonical = max(
            sorted(donors.items()),
            key=lambda kv: (kv[1]["log_view"], kv[1]["op"]),
        )[1]
        self.commit_max = max(
            [d["commit"] for d in dvcs.values()] + [self.commit_max]
        )
        out: List[Msg] = []
        target_op = canonical["op"]
        if target_op > self.op_prepare_max:
            # Our WAL ring cannot hold the canonical suffix — our checkpoint
            # lags at least a full ring behind the cluster's head.  Neither
            # option at this altitude is safe: installing unclamped would
            # journal repair fills beyond the ring bound (overwriting live
            # slots), and clamping would truncate possibly-committed
            # canonical ops and finish the view with an invented head.  We
            # cannot lead this view.  Fetch the cluster's latest checkpoint
            # instead (sync handlers drop further view-change traffic while
            # sync_target is set); peers' view-change timeouts elect the
            # next primary meanwhile — abdication by silence, as when a
            # syncing replica receives an SVC.
            return self._start_full_sync()
        by_op = {int(ch["op"]): ch for ch in canonical["headers"]}
        # Same below-window suspicion as the backup's SV install: the new
        # primary's OWN uncommitted headers under the canonical window may
        # be forks of a discarded view.
        self._install_headers(
            target_op, by_op, suspect_below=view > self.log_view
        )

        if self.missing:
            # Stay in view_change; repair bodies then finish (tick retries).
            if self._debug_file is not None:
                self._debug(
                    "vc_missing_bodies", new_view=view,
                    missing=sorted(self.missing)[:12],
                    commit_max=self.commit_max, target=int(target_op),
                )
            self._new_view_pending = view
            out.extend(self._request_missing(dvcs))
            return out
        return out + self._finish_view_change(view)

    def journal_has(self, op: int, checksum: int) -> bool:
        read = self.journal.read_prepare(op)
        return read is not None and wire.header_checksum(read[0]) == checksum

    def _install_headers(
        self, target_op: int, by_op: Dict[int, np.ndarray],
        suspect_below: bool = False,
    ) -> None:
        """Adopt a canonical log suffix (shared by the new primary's DVC
        install and the backup's start_view install): truncate uncommitted
        forks beyond ``target_op``, install the canonical headers, journal
        any matching stashed bodies, and record missing bodies for repair.

        ``suspect_below``: the caller is adopting a log for an ADVANCED
        log_view.  Local uncommitted headers BELOW the installed window
        were certified under the old log and may be forks the view change
        discarded — a stale never-quorumed prepare there chains perfectly
        onto the replica's own old suffix and would commit as soon as
        commit_max catches up (VOPR seed 401021: replica joins view 8 with
        a view-0 register at op 4 that view 1 replaced with a transfer,
        SV window starts above 4, stale register commits => diverging
        op 4 across the cluster).  Raising the verification floor to the
        window start makes the range suspect; the chain walk
        (_extend_verification) evicts non-linking headers and repair
        refetches the canonical ones."""
        # Local invariant: NEVER truncate below our own committed prefix —
        # those ops are executed state; deleting their headers and letting
        # the new view refill the slots would re-commit different ops over
        # an already-applied ledger (nondeterministic divergence).
        target_op = max(target_op, self.commit_min)
        if self.op > target_op:
            for op in [o for o in self.headers if o > target_op]:
                del self.headers[op]
                self.stash.pop(op, None)
            self.op = target_op
        self.missing = {
            op: cs for op, cs in self.missing.items() if op <= target_op
        }
        for op in sorted(by_op):
            if op <= self.commit_min:
                continue
            if op > target_op:
                # Beyond the caller's clamp (the WAL bound, op_prepare_max):
                # installing these would record missing bodies whose fills
                # journal past the ring's safe window.
                continue
            ch = by_op[op]
            checksum = wire.header_checksum(ch)
            mine = self.headers.get(op)
            if mine is not None and wire.header_checksum(mine) == checksum:
                continue
            self.headers[op] = ch
            self.missing.pop(op, None)
            stashed = self.stash.get(op)
            if stashed is not None and (
                wire.header_checksum(stashed[0]) == checksum
            ):
                self.journal.write_prepare(wire.encode(*stashed))
                self.stash.pop(op, None)
                continue
            if not self.journal_has(op, checksum):
                self.missing[op] = checksum
        self.op = max(self.op, target_op)
        head = self.headers.get(self.op)
        if head is not None:
            self.parent_checksum = wire.header_checksum(head)
        # The installed window is quorum-selected canonical content arriving
        # over a source-authenticated SV/DVC: anchor it for certified
        # commits (sparsely + the top, to keep certification walks short).
        for op_a in by_op:
            if self.commit_min < op_a <= target_op and (
                op_a == target_op or op_a % 16 == 0
            ):
                self._note_anchor(
                    op_a, wire.header_checksum(by_op[op_a])
                )
        # The installed window is canonical by construction: lower the
        # verification floor to its CONTIGUOUS-from-head start (never raise
        # it — a narrow SV on an already-verified log must not re-suspect
        # history).  A gapped window (the sender itself had an evicted
        # header under repair) must not vouch for local headers under its
        # gaps — only ops the window actually covers become verified;
        # anything below stays suspect until the chain walk links it.
        if target_op in by_op:
            w = target_op
            while w - 1 in by_op and w - 1 > self.commit_min:
                w -= 1
            w = max(self.commit_min + 1, w)
            self._verify_floor = min(self._verify_floor, w)
            if suspect_below and w > self.commit_min + 1:
                # Log ADVANCED and the window does not reach the commit
                # floor: the uncovered range is suspect (see docstring).
                self._verify_floor = max(self._verify_floor, w)
        self._verify_floor = min(self._verify_floor, self.op + 1)

    def _request_missing(self, dvcs=None) -> List[Msg]:
        """request_prepare for every missing body, spread over peers.

        The starting peer ROTATES per call: a fixed per-op target would ask
        the same replica forever, and that replica's own copy can be
        latently corrupt (found by the VOPR read-fault family) — the healthy
        peer would never be asked and repair would never complete."""
        out: List[Msg] = []
        peers = [r for r in range(self.replica_count) if r != self.replica]
        if not peers:
            return out
        self._repair_rotation = getattr(self, "_repair_rotation", 0) + 1
        for i, (op, checksum) in enumerate(sorted(self.missing.items())):
            peer = peers[(i + self._repair_rotation) % len(peers)]
            req = self._hdr(
                wire.Command.request_prepare,
                prepare_op=op,
                prepare_checksum=checksum,
            )
            out.append((("replica", peer), wire.encode(req)))
        return out

    def _finish_view_change(self, view: int) -> List[Msg]:
        """All canonical bodies journaled: become primary of the new view
        (replica.zig primary_start_view_as_the_new_primary)."""
        assert self.primary_index(view) == self.replica
        # A header gap in [commit_min+1, op] (canonical DVC window narrower
        # than the suffix) must route through repair, not crash the view
        # change (ADVICE round-1): request the gap and finish on a later
        # attempt (the view-change resend timer re-triggers us).
        gap = [
            o for o in range(self.commit_min + 1, self.op + 1)
            if o not in self.headers
        ]
        if gap:
            self._new_view_pending = view  # repair machinery re-finishes
            req = self._hdr(
                wire.Command.request_headers, op_min=gap[0], op_max=gap[-1]
            )
            return self._broadcast(wire.encode(req))
        self.status = NORMAL
        self.view = view
        self.log_view = view
        self._new_view_pending = None
        self._debug("view_normal_primary", new_view=view)
        self._log_suspect = False  # the canonical quorum log is ours now
        self._vc_escalations = 0   # progress: escalation backoff resets
        # Adoption watermark: every canonical body IS journaled here (the
        # gap check above), so the new log_view's log provably extends to
        # self.op — the one moment this fact is cheap and certain.
        self._log_adopted_op = self.op
        self._persist_view()
        self.svc_from.pop(view, None)
        self.dvc_from.pop(view, None)
        # Re-certify the uncommitted suffix in the new view: pipeline entries
        # that commit once backups ack them after start_view.
        self.pipeline.clear()
        for op in range(self.commit_min + 1, self.op + 1):
            h = self.headers[op]
            self.pipeline[op] = PipelineEntry(
                op=op,
                checksum=wire.header_checksum(h),
                client=wire.u128(h, "client"),
                ok_from={self.replica},
            )
        sv = self._hdr(
            wire.Command.start_view,
            op=self.op,
            commit=self.commit_min,
            checkpoint_op=self.op_checkpoint,
        )
        body = wire.pack_headers(self._suffix_headers())
        out = self._broadcast_nodes(wire.encode(sv, body))
        self._maybe_commit_pipeline(out)
        return out

    def on_start_view(self, h: np.ndarray, body: bytes) -> List[Msg]:
        """Backup installs the new view's canonical log
        (replica.zig on_start_view :1702+)."""
        if not self._ingress_auth(h):
            return []
        # A nonce-carrying SV is a response to a request_start_view: accept
        # it only if it answers OUR outstanding request (unsolicited
        # broadcasts carry nonce 0 and pass).
        nonce = wire.u128(h, "nonce")
        if nonce and nonce != getattr(self, "_rsv_nonce", None):
            return []
        if nonce:
            self._rsv_nonce = None
        view = int(h["view"])
        if view < self.view or (view == self.view and self.status == NORMAL):
            return []
        log_advanced = view > getattr(self, "log_view", 0)
        if self.sync_target is not None:
            # Keep fetching; a view change only moves where chunks come from.
            if view > self.view:
                self.view = view
            return []
        try:
            headers = wire.unpack_headers(body)
        except ValueError:
            return []
        out: List[Msg] = []
        target_op = int(h["op"])
        by_op = {int(ch["op"]): ch for ch in headers}

        self.view = view
        self.log_view = view
        self.commit_max = max(self.commit_max, int(h["commit"]))
        self._primary_spoke()
        self.pipeline.clear()
        self._dvc_sent_for = None
        self.svc_from = {v: s for v, s in self.svc_from.items() if v > view}
        # Adoption watermark: the SV header certifies the new log_view's
        # canonical log through target_op.  Persisting it BEFORE our bodies
        # land is deliberate — a crash mid-install must restart suspect
        # (presenting (log_view, short-op) would win canonical selection
        # and truncate committed history: seed 500285).
        self._log_adopted_op = target_op
        self._persist_view()

        # If the cluster's checkpoint is beyond our journal head, peers no
        # longer hold the WAL range we'd need — adopting the canonical head
        # first would falsify the sync trigger and wedge us with
        # unrepairable gaps.  State-sync the snapshot instead.
        sv_checkpoint = int(h["checkpoint_op"])
        if sv_checkpoint > self.op:
            self.status = NORMAL  # transitional; _maybe_start_sync -> SYNCING
            sync = self._maybe_start_sync(sv_checkpoint)
            if sync:
                # Escaping the view change via state sync is progress too:
                # the escalation backoff resets on every NORMAL-entry path.
                self._vc_escalations = 0
                return sync

        self.status = NORMAL
        self._debug("view_normal_backup", new_view=int(h["view"]))
        self._vc_escalations = 0   # progress: escalation backoff resets
        # WAL bound: adopt at most a ring's worth beyond our checkpoint;
        # commits advance the checkpoint and repair fetches the rest.
        self._install_headers(
            min(target_op, self.op_prepare_max), by_op,
            suspect_below=log_advanced,
        )
        # The canonical log just replaced whatever a misdirected write may
        # have clobbered: our log is certified again.
        self._log_suspect = False

        # Ack the uncommitted suffix so the new primary can commit it —
        # but never a SUSPECT header (below the verification floor): it may
        # be a fork of a discarded view, and an ack would vouch for it.
        for op in range(self.commit_min + 1, self.op + 1):
            hh = self.headers.get(op)
            if (
                hh is not None and op not in self.missing
                and op >= self._verify_floor
            ):
                self._append_ok(out, hh)
        out.extend(self._request_missing())
        self._commit_journal(out)
        return out

    def _request_start_view(self, view: int) -> List[Msg]:
        # The nonce pairs the SV response to THIS request so a stale
        # same-view snapshot cannot be installed (message_header.zig
        # StartView.nonce; ADVICE round-1).
        if self.mc_deterministic_nonce:
            # Model-checker mode (sim/mc.py): a prng draw would make two
            # otherwise identical states hash apart, so the nonce is a
            # pure function of (replica, view) — still unique per pairing.
            self._rsv_nonce = ((self.replica + 1) << 32) | (
                view & 0xFFFF_FFFF
            )
        else:
            self._rsv_nonce = self.prng.getrandbits(64)
        req = wire.new_header(
            wire.Command.request_start_view,
            cluster=self.cluster,
            view=view,
            nonce=self._rsv_nonce,
        )
        req["replica"] = self.replica
        return [(("replica", self.primary_index(view)), wire.encode(req))]

    def on_request_start_view(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        if self.status != NORMAL or not self.is_primary:
            return []
        if int(h["view"]) > self.view:
            return []
        sv = self._hdr(
            wire.Command.start_view,
            op=self.op,
            commit=self.commit_min,
            checkpoint_op=self.op_checkpoint,
            nonce=wire.u128(h, "nonce"),
        )
        body_out = wire.pack_headers(self._suffix_headers())
        return [(("replica", int(h["replica"])), wire.encode(sv, body_out))]

    # -- repair (replica.zig :2048-2497) --------------------------------------

    def _repair_gaps(self) -> List[Msg]:
        """Request prepares between our head and the lowest stashed op."""
        if not self.stash:
            return []
        out: List[Msg] = []
        lowest = min(self.stash)
        primary = self.primary_index()
        for op in range(self.op + 1, min(lowest, self.op + 1 + 8)):
            req = self._hdr(
                wire.Command.request_prepare, prepare_op=op, prepare_checksum=0
            )
            out.append((("replica", primary), wire.encode(req)))
        return out

    def on_request_prepare(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        op = int(h["op"]) if "op" in h.dtype.names else int(h["prepare_op"])
        checksum = wire.u128(h, "prepare_checksum")
        read = self.journal.read_prepare(op)
        if read is None or (
            checksum and wire.header_checksum(read[0]) != checksum
        ):
            if checksum and op > self.commit_min and (
                self.journal.never_had(op, checksum)
                # A PROMOTED identity's never_had proves nothing about the
                # RETIRED voter's journal, which may have journaled (and
                # acked) this very op — a nack under the inherited index
                # would let a nack quorum "prove" a committed op never
                # committed (seed 601346: promoted r0's self-nack + one
                # honest nack truncated committed ops 12-13, which view 4
                # refilled).  Until certified, stay silent.
                and self._suspect_flag() != 2
            ):
                # We provably never journaled it: nack, so a view-change
                # primary can prove a globally-lost uncommitted body was
                # never quorum-journaled and truncate it (vsr.zig nacks).
                nack = self._hdr(
                    wire.Command.nack_prepare,
                    prepare_op=op,
                    prepare_checksum=checksum,
                )
                return [(("replica", int(h["replica"])), wire.encode(nack))]
            return []
        ph, pbody = read
        return [(("replica", int(h["replica"])), wire.encode(ph, pbody))]

    def on_nack_prepare(self, h: np.ndarray, body: bytes) -> List[Msg]:
        """A peer provably never journaled a body we're missing.  As the
        new primary of a pending view change, a nack quorum proves the op
        was never quorum-journaled — so it never committed — and the
        canonical suffix truncates at it instead of wedging the view
        change forever (vsr.zig nack protocol; VOPR seed 10133)."""
        if not self._ingress_auth(h):
            return []
        op = int(h["prepare_op"])
        checksum = wire.u128(h, "prepare_checksum")
        if int(h["view"]) != self.view:
            # Stale nack from before our view change (e.g. delayed by a
            # clogged link, sent while repair ran in an older view, and the
            # sender may have journaled the body since): only nacks stamped
            # with OUR view may count toward truncation.
            return []
        if self.missing.get(op) != checksum:
            return []
        self._nacks.setdefault(op, set()).add(int(h["replica"]))
        if not (
            (self.status == VIEW_CHANGE and self._new_view_pending is not None)
            # A recovering-head replica repairing ITSELF may also truncate
            # at a nack quorum: the proof (no commit quorum was ever
            # possible for this op) is role-independent, and truncating the
            # unrepairable suffix is its only path out of suspicion
            # (_maybe_clear_log_suspect) — without it, a cluster whose
            # every voter is suspect escalates views forever (VOPR seed
            # 400396).
            or (getattr(self, "_log_suspect", False) and op > self.commit_min)
        ):
            return []
        # Nack threshold: with n - q_replication + 1 provably-never-had
        # replicas (counting ourselves), fewer than q_replication can ever
        # have journaled it — no commit quorum was possible.
        nackers = set(self._nacks.get(op, ()))
        if self.journal.never_had(op, checksum) and self._suspect_flag() != 2:
            # Same promotion guard as the nack response path: the
            # inherited journal cannot testify for the retired voter's.
            nackers.add(self.replica)
        if len(nackers) < self.replica_count - self.quorum_replication + 1:
            return []
        # Truncate the canonical suffix from the nack-proven op: everything
        # above it chains from it and could never commit past it anyway.
        assert op > self.commit_min
        for x in [x for x in self.headers if x >= op]:
            del self.headers[x]
        for x in [x for x in self.stash if x >= op]:
            del self.stash[x]
        for x in [x for x in self.missing if x >= op]:
            del self.missing[x]
        for x in [x for x in self._nacks if x >= op]:
            del self._nacks[x]
        self.op = op - 1
        head = self.headers.get(self.op)
        self.parent_checksum = (
            wire.header_checksum(head) if head is not None else 0
        )
        self._verify_floor = min(self._verify_floor, self.op + 1)
        if not self.missing:
            self._pending_finish = self._new_view_pending
        return []

    def on_request_headers(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        op_min, op_max = int(h["op_min"]), int(h["op_max"])
        selected = [
            self.headers[o]
            for o in sorted(self.headers)
            if op_min <= o <= op_max
        ]
        k_max = self.config.message_body_size_max // wire.HEADER_SIZE
        selected = selected[:k_max]
        if not selected:
            return []
        reply = self._hdr(wire.Command.headers)
        return [
            (("replica", int(h["replica"])),
             wire.encode(reply, wire.pack_headers(selected)))
        ]

    def on_headers(self, h: np.ndarray, body: bytes) -> List[Msg]:
        """Merge repair headers: adopt chained extensions of our log."""
        if not self._ingress_auth(h):
            return []
        try:
            headers = wire.unpack_headers(body)
        except ValueError:
            return []
        out: List[Msg] = []
        # Gap fill (descending, so each adoption chain-validates against the
        # already-known next header): headers below our op that a narrow DVC
        # window left missing during a view change (ADVICE round-1).  Bodies
        # may already be local (stash/journal) — mirror _install_headers.
        for ch in sorted(headers, key=lambda x: -int(x["op"])):
            op = int(ch["op"])
            if self.commit_min < op <= self.op and op not in self.headers:
                nxt = self.headers.get(op + 1)
                checksum = wire.header_checksum(ch)
                if nxt is not None and wire.u128(nxt, "parent") == checksum:
                    self.headers[op] = ch
                    stashed = self.stash.get(op)
                    if stashed is not None and (
                        wire.header_checksum(stashed[0]) == checksum
                    ):
                        self.journal.write_prepare(wire.encode(*stashed))
                        self.stash.pop(op, None)
                        self._repipeline(op, ch)
                    elif not self.journal_has(op, checksum):
                        self.missing[op] = checksum
                    else:
                        self._repipeline(op, ch)
        # Anchor-certified cover of the response (byzantine domain): ops
        # whose header matches a SOURCE-AUTHENTICATED anchor, extended
        # downward through the response's own parent links.  Only this
        # certified set may testify against our journaled head — a forged
        # headers response cannot reproduce an anchored checksum, so it
        # can never evict an honest head (checksums are not MACs; a single
        # unauthenticated frame must not pick repair targets).
        certified: set = set()
        if self.ingress_verify:
            by_op = {int(ch["op"]): ch for ch in headers}
            for a in sorted(by_op, reverse=True):
                if a in certified:
                    continue
                if self._anchors.get(a) != wire.header_checksum(by_op[a]):
                    continue
                if not self._anchor_trusted(a, self._anchors[a]):
                    # Byzantine-primary defense: an anchor without a
                    # replication quorum of MAC-verified votes certifies
                    # nothing — it may be the adversary's own forged
                    # heartbeat vouching for its own forged headers.
                    continue
                k = a
                while k in by_op:
                    certified.add(k)
                    below = by_op.get(k - 1)
                    if below is None or wire.header_checksum(below) != (
                        wire.u128(by_op[k], "parent")
                    ):
                        break
                    k -= 1
        for ch in sorted(headers, key=lambda x: int(x["op"])):
            op = int(ch["op"])
            if op > self.op_prepare_max:
                break  # WAL bound: cannot take bodies this far ahead yet
            if (
                self.ingress_verify
                and op == self.op + 1
                and op in certified
                and wire.u128(ch, "parent") != self.parent_checksum
                and self.op > self.commit_min
                and not self.is_primary
                and self.op not in self.pipeline
            ):
                # The ANCHORED canonical suffix chains from a different
                # checksum for our uncommitted head than we journaled: our
                # head is a fork (a forged variant slipped into the ring),
                # and without eviction suffix adoption would wedge forever
                # — the byzantine ring tail's repair responses never link
                # onto a forged head.  The parent named by a certified
                # header IS canonical, so the checksum-matched refetch is
                # satisfiable by any honest peer.
                self.byzantine_detections += 1
                if _obs.enabled:
                    _obs.counter("byzantine.equivocation_detected").inc()
                self._debug(
                    "headers_head_fork_evicted", op=self.op,
                )
                self._evict_fork(self.op, wire.u128(ch, "parent"))
                out.extend(self._request_missing())
                break  # re-adopt on the next repair round, head-first
            if op == self.op + 1 and wire.u128(ch, "parent") == (
                self.parent_checksum
            ):
                if self.ingress_verify and op not in certified:
                    # PR 6 gap, closed: a single unauthenticated headers
                    # frame could still PROPOSE repair targets — extending
                    # our head and pinning `missing[op]` to a checksum no
                    # honest peer can serve.  Repair-target selection now
                    # routes exclusively through the anchor-certified set;
                    # an uncertified extension waits for the next commit
                    # heartbeat to anchor it (one heartbeat of latency in
                    # honest runs, never a wedge).
                    if _obs.enabled:
                        _obs.counter(
                            "byzantine.rejected.uncertified_extension"
                        ).inc()
                    continue
                self.headers[op] = ch
                self.missing[op] = wire.header_checksum(ch)
                self.op = op
                self.parent_checksum = wire.header_checksum(ch)
        out.extend(self._request_missing())
        return out

    def _fill_missing(self, h: np.ndarray, body: bytes) -> None:
        op = int(h["op"])
        self.journal.write_prepare(wire.encode(h, body))
        # Install the header too: a fork evicted by the commit-checksum
        # anchor (_evict_fork) left only the `missing` entry — the fill is
        # what restores the canonical header.  (For the ordinary
        # missing-body case the header is already this one: checksum
        # identity covers every header byte.)
        self.headers[op] = h
        if op == self.op:
            # Refilled the HEAD: re-anchor the chain tip or the next fresh
            # prepare would be checked against the evicted fork's checksum.
            self.parent_checksum = wire.header_checksum(h)
        del self.missing[op]
        self._nacks.pop(op, None)
        # Downward cascade: the canonical fill names its parent's checksum.
        # A predecessor that does not match is a forged ancestor
        # (equivocated into our chain before the anchor caught it): evict
        # it and repair by the now-known canonical checksum, all the way
        # down until the chain meets honest history.
        if self.ingress_verify and op - 1 > self.commit_min:
            below = self.headers.get(op - 1)
            parent = wire.u128(h, "parent")
            if below is not None and wire.header_checksum(below) != parent \
                    and self._anchor_trusted(op - 1, parent):
                self.byzantine_detections += 1
                if _obs.enabled:
                    _obs.counter("byzantine.equivocation_detected").inc()
                self._debug("chain_fork_evicted", op=op - 1)
                self._evict_fork(op - 1, parent)
        self._repipeline(op, h)
        self._repair_timeout.reset(self._ticks)  # repair progressing
        if getattr(self, "_new_view_pending", None) is not None and (
            not self.missing
        ):
            # All repairs done: finish becoming primary.
            pending = self._new_view_pending
            self._pending_finish = pending

    # -- peer block repair (grid_blocks_missing.zig's role) -------------------
    #
    # A replica that finds its checkpoint FILES (manifest / base snapshot /
    # delta runs) corrupt or missing at open does not discard its state:
    # each file is content-addressed by a checksum pinned from above (the
    # superblock pins the manifest, the manifest pins base + runs), so the
    # replica fetches exactly the damaged files from peers, chunk by chunk,
    # verifies them against the pinned checksums, and then opens normally.
    # Only if no peer can serve the bytes (peers checkpointed past us and
    # GC'd, or histories diverged) does it fall back to full state sync.

    def _enter_block_repair(self, damage, cold_paths=None) -> None:
        self._init_clock()
        self.status = RECOVERING
        self._recovering_since = self._ticks
        self._block_repair = {
            "queue": list(damage),      # [(kind, ident, checksum), ...]
            "buf": bytearray(),         # bytes of queue[0] fetched so far
            "peer": self._next_peer(self.replica),
            "attempts": 0,              # timed-out requests since progress
            "requested": False,
            # Cold entries are addressed by checksum; this maps each to the
            # relative file name the fetched bytes install under.
            "cold_paths": dict(cold_paths or {}),
            # Fire the first request on the very next tick, not after a
            # full resend interval.
            "last_req": self._ticks - BLOCK_REPAIR_RESEND,
        }

    def _next_peer(self, p: int) -> int:
        p = (p + 1) % self.replica_count
        if p == self.replica:
            p = (p + 1) % self.replica_count
        return p

    def _request_block(self) -> List[Msg]:
        br = self._block_repair
        kind, ident, expect = br["queue"][0]
        req = self._hdr(
            wire.Command.request_blocks,
            block_kind=_BLOCK_KIND_CODE[kind],
            block_id=ident,
            block_checksum=expect,
            offset=len(br["buf"]),
        )
        br["requested"] = True
        br["last_req"] = self._ticks
        return [(("replica", br["peer"]), wire.encode(req))]

    def _tick_block_repair(self) -> List[Msg]:
        br = self._block_repair
        if self._ticks - br["last_req"] < BLOCK_REPAIR_RESEND:
            return []
        if br["requested"]:
            # The outstanding request timed out: rotate peers and restart
            # the current file (a different peer's chunks must align).
            br["attempts"] += 1
            br["peer"] = self._next_peer(br["peer"])
            br["buf"] = bytearray()
            if br["attempts"] >= 3 * self.replica_count:
                return self._block_repair_fallback()
        return self._request_block()

    def on_request_blocks(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        kind = _BLOCK_KIND_NAME.get(int(h["block_kind"]))
        if kind is None:
            return []
        expect = wire.u128(h, "block_checksum")
        offset = int(h["offset"])
        if kind == "cold":
            path = self.machine.cold.locate_by_checksum(expect)
        else:
            path = self.forest.locate_block(kind, int(h["block_id"]), expect)
        if path is None:
            return []
        try:
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                total = f.tell()
                if offset >= total:
                    return []
                f.seek(offset)
                chunk = f.read(self.config.message_body_size_max)
        except OSError:
            return []
        resp = self._hdr(
            wire.Command.block,
            block_kind=int(h["block_kind"]),
            block_id=int(h["block_id"]),
            block_checksum=expect,
            offset=offset,
            total=total,
        )
        return [(("replica", int(h["replica"])), wire.encode(resp, chunk))]

    def on_block(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        br = self._block_repair
        if br is None and self._cold_fetch is not None:
            return self._on_cold_block(h, body)
        if br is None or not br["queue"]:
            return []
        kind, ident, expect = br["queue"][0]
        if (
            int(h["block_kind"]) != _BLOCK_KIND_CODE[kind]
            or wire.u128(h, "block_checksum") != expect
        ):
            return []  # stale response for a file we already finished
        if int(h["offset"]) != len(br["buf"]):
            return self._request_block()
        br["buf"].extend(body)
        br["attempts"] = 0
        if len(br["buf"]) < int(h["total"]):
            return self._request_block()
        if kind == "cold":
            rel = br["cold_paths"].get(expect)
            installed = rel is not None and self.machine.cold.install_file(
                rel, expect, bytes(br["buf"])
            )
        else:
            installed = self.forest.repair_block(
                kind, ident, expect, bytes(br["buf"])
            )
        if not installed:
            # Bytes don't hash to the pinned checksum (corrupt/malicious
            # peer): retry the whole file from the next peer.
            br["buf"] = bytearray()
            br["peer"] = self._next_peer(br["peer"])
            return self._request_block()
        br["queue"].pop(0)
        br["buf"] = bytearray()
        self.blocks_repaired += 1
        if br["queue"]:
            return self._request_block()
        return self._finish_block_repair()

    def _finish_block_repair(self) -> List[Msg]:
        """All queued files repaired: re-verify and open.  A repaired
        manifest may reveal more damage (its base/runs were unknowable
        while it was corrupt) — requeue and keep going."""
        try:
            recovery = self._open_durable_state()
        except ForestDamage as err:
            br = self._block_repair
            br["queue"] = list(err.damage)
            # A repaired forest may reveal COLD damage next (or vice
            # versa): the path map must follow the new queue, or the
            # receiver can never install the fetched bytes and livelocks
            # re-requesting the same file.
            br["cold_paths"] = dict(getattr(err, "cold_paths", None) or {})
            br["buf"] = bytearray()
            br["attempts"] = 0
            return self._request_block()
        self._block_repair = None
        self._post_open(recovery)
        if self.status == RECOVERING:
            return self._request_start_view(self.view)
        return []

    def _block_repair_fallback(self) -> List[Msg]:
        """No peer holds our damaged files: discard the local checkpoint
        and fetch the cluster's latest full snapshot (state sync)."""
        self._block_repair = None
        self.journal.recover()  # journal rings are independent of the forest
        return self._start_full_sync()

    # -- state sync (vsr/sync.zig) --------------------------------------------

    def _start_full_sync(self) -> List[Msg]:
        """Enter state sync targeting the cluster's LATEST checkpoint
        (checkpoint_op 0 = whatever the responder has).  Single entry point
        for every full-sync trigger — block-repair fallback, lagging-primary
        abdication, hostile-manifest restart — so sync-entry invariants
        (abandoning a pending view finish, resetting the fetch buffer) hold
        on every path."""
        self._sync_peer = self._next_peer(
            self._sync_peer if self._sync_peer is not None else self.replica
        )
        return self._enter_sync(0)

    def _enter_sync(self, checkpoint_op: int, *, refresh: bool = False) -> List[Msg]:
        """The ONLY sync-entry point (targeted or latest): sync-entry
        invariants hold on every path — notably abandoning any pending view
        finish, or _finish_view_change(stale view) would regress self.view
        after the sync installs.

        Picks the transport: Merkle-anchored incremental catch-up
        (docs/state_sync.md) when this replica runs commitments and is not
        forced full; the byte-exact full-checkpoint transfer otherwise.
        ``refresh=True`` (a checkpoint-refresh restart, on_commit) keeps
        the resend/progress clocks UNTOUCHED so a dead pinned responder is
        still rotated away from even while refreshes keep arriving."""
        self._new_view_pending = None
        self._pending_finish = None
        self.status = SYNCING
        self.sync_buffer = bytearray()
        self._sync_local = None
        prev = self.sync_target if refresh else None
        if not refresh:
            self._last_sync_req = self._ticks
            self._sync_progress = self._ticks
        if prev is not None and prev.get("mode", "full") == "full":
            # A fallback (or an initial full choice) is STICKY for the
            # whole sync episode: a refresh must not re-enter the roots
            # flow — among merkle-off peers under a sustained flood that
            # would reset the unanswered-rounds budget every refresh and
            # livelock the rejoin (the refresh twin of the stranded-sync
            # wedge).
            self.sync_target = {
                "checkpoint_op": checkpoint_op, "total": None,
                "mode": "full",
            }
            return self._request_sync_chunk()
        if self._sync_incremental_wanted():
            self.sync_target = {
                "checkpoint_op": checkpoint_op, "total": None,
                "mode": "roots",
                # Attempt/failure budgets survive refreshes for the same
                # reason the full choice does: each unanswered round must
                # COUNT, however often the cluster checkpoints.
                "roots_attempts": (
                    prev.get("roots_attempts", 0) if prev else 0
                ),
                "verify_failures": (
                    prev.get("verify_failures", 0) if prev else 0
                ),
                "descend_attempts": (
                    prev.get("descend_attempts", 0) if prev else 0
                ),
            }
            return self._request_sync_roots()
        self.sync_target = {
            "checkpoint_op": checkpoint_op, "total": None, "mode": "full",
        }
        return self._request_sync_chunk()

    def _sync_incremental_wanted(self) -> bool:
        """Attempt the incremental path iff this replica runs Merkle
        commitments (the np trees need the leaf contract armed cluster-
        wide) and nothing forces the proven full transfer."""
        if self.sync_mode_force == "full":
            return False
        return bool(getattr(self.machine, "merkle_enabled", False))

    def _maybe_start_sync(self, primary_checkpoint_op: int) -> List[Msg]:
        """If the primary's checkpoint is beyond our journal *head*, our WAL
        no longer overlaps the cluster's and ordinary repair cannot catch us
        up: fetch the checkpoint snapshot.  (A backup merely lagging in
        commits — head >= the checkpoint — repairs via the WAL instead.)

        Second trigger, commit-floor starvation: a replica whose NEXT
        commit (commit_min+1) sits at or below the cluster's checkpoint and
        is header-gapped, missing, or under the verification floor may be
        permanently unrepairable — peers prune headers below their
        checkpoint (_prune_headers) and recycle those WAL slots, so chain
        repair can have nobody left to answer (VOPR seed 400816: a
        restarted replica with a damaged WAL prefix wedges at commit 0
        while the cluster checkpoints past it).  Repair gets a grace of
        _FLOOR_STALL_SYNC heartbeats; genuine progress resets the
        counter."""
        if self.sync_target is not None:
            return []
        nxt = self.commit_min + 1
        if primary_checkpoint_op >= nxt and primary_checkpoint_op > 0 and (
            self.headers.get(nxt) is None
            or nxt in self.missing
            or nxt < self._verify_floor
        ):
            self._floor_stall += 1
            if self._floor_stall >= _FLOOR_STALL_SYNC:
                self._floor_stall = 0
                self._debug(
                    "floor_stall_sync", commit_min=self.commit_min,
                    cluster_checkpoint=primary_checkpoint_op,
                )
                return self._enter_sync(primary_checkpoint_op)
        else:
            self._floor_stall = 0
        if primary_checkpoint_op <= self.op:
            return []
        return self._enter_sync(primary_checkpoint_op)

    def _request_sync_chunk(self) -> List[Msg]:
        req = self._hdr(
            wire.Command.request_sync_checkpoint,
            checkpoint_op=self.sync_target["checkpoint_op"],
            offset=len(self.sync_buffer),
        )
        target = (
            self._sync_peer if self._sync_peer is not None
            else self.primary_index()
        )
        return [(("replica", target), wire.encode(req))]

    def on_request_sync_checkpoint(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        checkpoint_op = int(h["checkpoint_op"])
        offset = int(h["offset"])
        # checkpoint_op 0 = "whatever is latest" (block-repair fallback:
        # the requester's own checkpoint is unusable, any current one will do).
        if checkpoint_op == 0:
            checkpoint_op = self.op_checkpoint
        if checkpoint_op != self.op_checkpoint or self.op_checkpoint == 0:
            return []
        try:
            # Materialized once per checkpoint op (forest caches the file);
            # each chunk request seeks and reads only its window, so a full
            # sync costs O(total) responder IO, not O(total^2/chunk).
            path, file_checksum = self.forest.materialize_file(
                self.op_checkpoint
            )
            with open(path, "rb") as f:
                f.seek(0, os.SEEK_END)
                total = f.tell()
                if offset >= total:
                    return []
                f.seek(offset)
                chunk = f.read(self.config.message_body_size_max)
        except (OSError, AssertionError):
            return []
        resp = self._hdr(
            wire.Command.sync_checkpoint,
            checkpoint_op=self.op_checkpoint,
            offset=offset,
            total=total,
            file_checksum=file_checksum,
            commit_max=self.commit_min,
        )
        return [(("replica", int(h["replica"])), wire.encode(resp, chunk))]

    def on_sync_checkpoint(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        if self.sync_target is None:
            return []
        if self.sync_target.get("mode", "full") != "full":
            # A stale full-path chunk (e.g. from before an incremental
            # retry) must not pollute the descent state.
            return []
        if self._cold_fetch is not None:
            # Snapshot already fully fetched; a late/duplicate chunk must
            # not re-trigger the install (it would reset the in-progress
            # cold-run fetch and livelock).
            return []
        checkpoint_op = int(h["checkpoint_op"])
        if self.sync_target["checkpoint_op"] == 0 and not self.sync_buffer:
            # "Latest" request: pin to whichever checkpoint answered first.
            self.sync_target["checkpoint_op"] = checkpoint_op
        if checkpoint_op != self.sync_target["checkpoint_op"]:
            return []
        if int(h["offset"]) != len(self.sync_buffer):
            return self._request_sync_chunk()
        self.sync_buffer.extend(body)
        self.sync_stats["bytes_full"] += len(body)
        if _obs.enabled:
            _obs.counter("sync.bytes_full").inc(len(body))
        self.sync_target["total"] = int(h["total"])
        self.sync_target["file_checksum"] = wire.u128(h, "file_checksum")
        self.sync_target["commit_max"] = int(h["commit_max"])
        if len(self.sync_buffer) < self.sync_target["total"]:
            self._last_sync_req = self._ticks
            self._sync_progress = self._ticks
            return self._request_sync_chunk()
        return self._install_sync_checkpoint()

    def _sync_responder(self) -> int:
        return (
            self._sync_peer if self._sync_peer is not None
            else self.primary_index()
        )

    # -- Merkle-anchored incremental catch-up (docs/state_sync.md) ------------
    #
    # Requester flow: request_sync_roots -> (verify top frontiers) ->
    # batched binary descent over DIVERGING interior nodes only
    # (request_sync_subtree kind=descend; each children pair verified
    # against its already-verified parent) -> diverging LEAF rows fetched
    # in budget-sized batches (kind=rows; each row re-hashed against its
    # verified leaf) -> append-only history tail (kind=history) -> the
    # reconstructed state must hash to the responder's advertised
    # whole-state checksum before installing through the SAME tail the
    # full path uses (_install_sync_state).  Any verification failure
    # rotates the responder and re-requests; any structural mismatch
    # (capacity/schema/cold/divergence threshold) degrades to the proven
    # full-checkpoint transfer — a mixed-version cluster never wedges.

    def _sync_rotate_peer(self) -> None:
        self._sync_peer = self._next_peer(
            self._sync_peer if self._sync_peer is not None
            else self.primary_index()
        )

    def _sync_obs(self, name: str, n: int = 1) -> None:
        if _obs.enabled:
            _obs.counter(name).inc(n)

    def _sync_pack_for(self, op: int):
        """Responder-side per-checkpoint pack (canonical arrays + trees +
        install gates), built once and cached until the checkpoint moves."""
        from . import statesync

        cached = self._sync_pack_cache
        if cached is not None and cached.op == op:
            return cached
        try:
            arrays, meta = self.forest.canonical_arrays(op)
        except (OSError, RuntimeError, AssertionError, ValueError, KeyError):
            return None
        pack = statesync.SyncPack(op, arrays, meta)
        self._sync_pack_cache = pack
        return pack

    def _request_sync_roots(self) -> List[Msg]:
        req = self._hdr(
            wire.Command.request_sync_roots,
            checkpoint_op=self.sync_target["checkpoint_op"],
        )
        return [(("replica", self._sync_responder()), wire.encode(req))]

    def on_request_sync_roots(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        if self.op_checkpoint == 0 or not getattr(
            self.machine, "merkle_enabled", False
        ):
            # Merkle-off responders stay silent: the requester counts the
            # unanswered rounds and degrades to the full path, exactly as
            # it does for a pre-sync-roots peer (version skew).
            return []
        want = int(h["checkpoint_op"])
        if want and want != self.op_checkpoint:
            return []
        # The state-sync summary is checkpoint-derived: capture already ran
        # behind the settle barrier (machine.merkle_canonical_roots drains
        # the TB_MERKLE_ASYNC commitment lane before the roots are read),
        # so a deferred-lane backlog on THIS replica can never skew the
        # roots a rejoining peer descends against.  Consensus commits are
        # per-op besides, keeping peer forests byte-identical —
        # docs/commitments.md composition sections.
        pack = self._sync_pack_for(self.op_checkpoint)
        if pack is None:
            return []
        if len(pack.roots_body) > self.config.message_body_size_max:
            # Pathological summary (e.g. an enormous session table): stay
            # silent rather than ship an oversized frame; the requester
            # falls back to the chunked full transfer.
            return []
        resp = self._hdr(
            wire.Command.sync_roots,
            checkpoint_op=pack.op,
            commit_max=self.commit_min,
            ledger_digest=pack.digest,
            state_checksum=pack.state_checksum,
        )
        return [(("replica", int(h["replica"])),
                 wire.encode(resp, pack.roots_body))]

    def on_sync_roots(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        from . import checkpoint as ckpt_mod
        from . import statesync

        target = self.sync_target
        if target is None or target.get("mode") != "roots":
            return []
        checkpoint_op = int(h["checkpoint_op"])
        if target["checkpoint_op"] == 0:
            target["checkpoint_op"] = checkpoint_op
        if checkpoint_op != target["checkpoint_op"]:
            return []
        info = statesync.unpack_roots(body)
        if info is None:
            # Malformed or forged summary (top frontier not folding to the
            # stated roots): reject-and-refetch from a rotated peer.
            return self._sync_verify_failed("roots")
        self.sync_stats["bytes_incremental"] += len(body)
        self._sync_obs("sync.bytes_incremental", len(body))
        self._sync_progress = self._ticks
        # Structural gates: anything the descent cannot reconcile routes
        # to the byte-exact full transfer (docs/state_sync.md fallback
        # matrix) instead of wedging or installing garbage.
        if info["meta"].get("machine", {}).get("cold_manifest"):
            return self._sync_fallback("cold_manifest")
        arrays = ckpt_mod.ledger_to_arrays(self.machine.checkpoint_ledger())
        if statesync.schema(arrays) != info["schema"]:
            return self._sync_fallback("schema")
        for pad in statesync.PADS:
            if statesync.pad_capacity(arrays, pad) != (
                info["pads"][pad]["capacity"]
            ):
                return self._sync_fallback("capacity")
        hist_keys = statesync.history_keys(arrays)
        local_hist = int(arrays["history/count"])
        if local_hist > info["history_count"]:
            return self._sync_fallback("history_regression")
        # Compare our trees' top frontiers against the verified summary:
        # clean subtrees are skipped wholesale, diverging positions seed
        # the descent queues (leaf positions go straight to row fetch).
        trees = statesync.build_trees(arrays)
        want: Dict[str, Dict[int, int]] = {}
        diff: Dict[str, list] = {}
        rows_needed: Dict[str, list] = {}
        diverging = 0
        for pad in statesync.PADS:
            cap = info["pads"][pad]["capacity"]
            depth = statesync.top_depth(cap)
            theirs = info["pads"][pad]["top"]
            mine = statesync.frontier(trees[pad], depth)
            base = 1 << depth
            want[pad] = {}
            diff[pad] = []
            rows_needed[pad] = []
            for i in range(len(theirs)):
                tv = int(theirs[i])
                if tv == int(mine[i]):
                    continue
                diverging += 1
                pos = base + i
                want[pad][pos] = tv
                if base == cap:  # the top frontier IS the leaf level
                    rows_needed[pad].append(pos - cap)
                else:
                    diff[pad].append(pos)
        # What a full transfer of this state would ship (the responder
        # materializes DENSE arrays): the descent aborts to the full path
        # the moment its own projected bill exceeds the divergence
        # threshold's share of this — cold starts and long absences
        # degrade after a few cheap interior rounds instead of shipping
        # the whole ledger twice, row by row.
        full_est = sum(
            info["pads"][pad]["capacity"]
            * statesync.row_bytes(arrays, pad)
            for pad in statesync.PADS
        ) + info["history_count"] * statesync.history_row_bytes(arrays)
        self._sync_local = {
            "arrays": arrays,
            "trees": trees,
            "info": info,
            "want": want,
            "diff": diff,
            "rows_needed": rows_needed,
            "row_patches": {pad: [] for pad in statesync.PADS},
            "history": {
                "start": local_hist,
                "next": local_hist,
                "total": info["history_count"],
                "chunks": [],
            },
            "hist_keys": hist_keys,
            "outstanding": None,
            "bytes": len(body),
            "full_est": full_est,
        }
        target["mode"] = "descend"
        target["commit_max"] = int(h["commit_max"])
        target["ledger_digest"] = int(h["ledger_digest"])
        target["state_checksum"] = wire.u128(h, "state_checksum")
        self._debug(
            "sync_roots", checkpoint_op=checkpoint_op,
            diverging_top=diverging, full_est=full_est,
        )
        return self._sync_request_next()

    def _sync_batch_limits(self) -> Tuple[int, int]:
        """(descend nodes per request, history rows per request) under the
        message body budget (requests carry 8 B/node, replies 16 B/node)."""
        budget = self.config.message_body_size_max
        return max(1, budget // 16), budget

    def _sync_request_next(self) -> List[Msg]:
        """Issue the next batched request of the descent, or finalize.
        Work items are consumed only when their VERIFIED reply arrives, so
        a rotation retransmits the same batch to the next peer."""
        from . import statesync
        from .checksum import checksum as _checksum

        sl = self._sync_local
        if sl is None:
            return self._sync_fallback("lost_state")
        target = self.sync_target
        ckpt = target["checkpoint_op"]
        nodes_max, budget = self._sync_batch_limits()
        # Projected bill so far: session bytes + the rows already known
        # diverging + a floor for the interior still to resolve.  Crossing
        # the threshold's share of the full-transfer estimate means the
        # descent cannot win — degrade before shipping the ledger twice.
        projected = sl["bytes"] + sum(
            len(sl["rows_needed"][pad])
            * statesync.row_bytes(sl["arrays"], pad)
            for pad in statesync.PADS
        ) + 32 * sum(len(sl["diff"][pad]) for pad in statesync.PADS)
        if projected > self.sync_divergence_max * sl["full_est"]:
            return self._sync_fallback("divergence")
        for pad_i, pad in enumerate(statesync.PADS):
            if sl["diff"][pad]:
                nodes = np.asarray(
                    sl["diff"][pad][:nodes_max], dtype="<u8"
                )
                payload = nodes.tobytes()
                sl["outstanding"] = {
                    "pad": pad_i, "kind": wire.SYNC_DESCEND,
                    "list": nodes, "count": len(nodes), "start": 0,
                    "list_checksum": _checksum(payload) & ((1 << 64) - 1),
                }
                req = self._hdr(
                    wire.Command.request_sync_subtree,
                    checkpoint_op=ckpt, count=len(nodes), pad=pad_i,
                    kind=wire.SYNC_DESCEND,
                )
                return [(("replica", self._sync_responder()),
                         wire.encode(req, payload))]
        for pad_i, pad in enumerate(statesync.PADS):
            if sl["rows_needed"][pad]:
                per_row = statesync.row_bytes(sl["arrays"], pad)
                rows_max = max(1, budget // max(1, per_row))
                slots = np.asarray(
                    sorted(sl["rows_needed"][pad][:rows_max]), dtype="<u8"
                )
                payload = slots.tobytes()
                sl["outstanding"] = {
                    "pad": pad_i, "kind": wire.SYNC_ROWS,
                    "list": slots, "count": len(slots), "start": 0,
                    "list_checksum": _checksum(payload) & ((1 << 64) - 1),
                }
                req = self._hdr(
                    wire.Command.request_sync_subtree,
                    checkpoint_op=ckpt, count=len(slots), pad=pad_i,
                    kind=wire.SYNC_ROWS,
                )
                return [(("replica", self._sync_responder()),
                         wire.encode(req, payload))]
        hist = sl["history"]
        if hist["next"] < hist["total"]:
            per_row = statesync.history_row_bytes(sl["arrays"])
            count = max(1, budget // per_row)
            sl["outstanding"] = {
                "pad": statesync.HISTORY_PAD, "kind": wire.SYNC_HISTORY,
                "list": None, "count": count, "start": hist["next"],
                "list_checksum": 0,
            }
            req = self._hdr(
                wire.Command.request_sync_subtree,
                checkpoint_op=ckpt, count=count, pad=statesync.HISTORY_PAD,
                kind=wire.SYNC_HISTORY, start=hist["next"],
            )
            return [(("replica", self._sync_responder()),
                     wire.encode(req))]
        return self._sync_finalize()

    def on_request_sync_subtree(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        from . import statesync
        from .checksum import checksum as _checksum

        if self.op_checkpoint == 0 or not getattr(
            self.machine, "merkle_enabled", False
        ):
            return []
        if int(h["checkpoint_op"]) != self.op_checkpoint:
            return []
        pack = self._sync_pack_for(self.op_checkpoint)
        if pack is None:
            return []
        kind = int(h["kind"])
        pad_i = int(h["pad"])
        budget = self.config.message_body_size_max
        requester = ("replica", int(h["replica"]))
        if kind == wire.SYNC_HISTORY:
            start = int(h["start"])
            total = int(pack.arrays["history/count"])
            per_row = statesync.history_row_bytes(pack.arrays)
            count = min(
                max(1, int(h["count"])), max(1, budget // per_row),
                max(0, total - start),
            )
            payload = statesync.pack_history(pack.arrays, start, count)
            resp = self._hdr(
                wire.Command.sync_subtree,
                checkpoint_op=pack.op, start=start, total=total,
                count=count, pad=statesync.HISTORY_PAD,
                kind=wire.SYNC_HISTORY, list_checksum=0,
            )
            return [(requester, wire.encode(resp, payload))]
        if pad_i >= len(statesync.PADS) or kind not in (
            wire.SYNC_DESCEND, wire.SYNC_ROWS
        ):
            return []
        pad = statesync.PADS[pad_i]
        cap = statesync.pad_capacity(pack.arrays, pad)
        if len(body) % 8 != 0:
            return []  # malformed node/slot list
        items = np.frombuffer(body, dtype="<u8")
        if len(items) != int(h["count"]) or len(items) == 0:
            return []
        list_checksum = _checksum(body) & ((1 << 64) - 1)
        if kind == wire.SYNC_DESCEND:
            if len(items) > budget // 16 or int(items.max()) >= cap or (
                int(items.min()) < 1
            ):
                return []
            payload = statesync.children(pack.trees[pad], items).tobytes()
        else:
            if int(items.max()) >= cap:
                return []
            payload = statesync.pack_rows(pack.arrays, pad, items)
            if len(payload) > budget:
                return []  # malformed over-budget request
        resp = self._hdr(
            wire.Command.sync_subtree,
            checkpoint_op=pack.op, count=len(items), pad=pad_i, kind=kind,
            list_checksum=list_checksum,
        )
        return [(requester, wire.encode(resp, payload))]

    def on_sync_subtree(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        from . import statesync

        target = self.sync_target
        sl = self._sync_local
        if target is None or target.get("mode") != "descend" or sl is None:
            return []
        if int(h["checkpoint_op"]) != target["checkpoint_op"]:
            return []
        out = sl["outstanding"]
        if out is None:
            return []
        if int(h["pad"]) != out["pad"] or int(h["kind"]) != out["kind"]:
            return []  # stale reply for an earlier request
        if int(h["list_checksum"]) != out["list_checksum"]:
            return []  # a delayed duplicate answering a DIFFERENT list
        if out["kind"] != wire.SYNC_HISTORY and (
            int(h["count"]) != out["count"]
        ):
            return []  # history replies may clamp count; others may not
        kind = out["kind"]
        self.sync_stats["bytes_incremental"] += len(body)
        sl["bytes"] += len(body)
        self._sync_obs("sync.bytes_incremental", len(body))
        self._sync_progress = self._ticks
        self._last_sync_req = self._ticks
        target["descend_attempts"] = 0  # progress re-arms the budget
        if kind == wire.SYNC_DESCEND:
            pad = statesync.PADS[out["pad"]]
            nodes = out["list"]
            if len(body) != 16 * len(nodes):
                # Malformed/truncated children list (incl. non-multiple-
                # of-8 bodies np.frombuffer would raise on): a lying
                # chunk, not a crash.
                return self._sync_verify_failed("children_shape")
            values = np.frombuffer(body, dtype="<u8")
            if self.sync_verify and not statesync.verify_children(
                values, nodes, sl["want"][pad]
            ):
                return self._sync_verify_failed("children")
            tree = sl["trees"][pad]
            cap = sl["info"]["pads"][pad]["capacity"]
            # Consume the batch, enqueue only DIVERGING children.
            del sl["diff"][pad][: len(nodes)]
            for i, node in enumerate(nodes):
                for side in (0, 1):
                    child = 2 * int(node) + side
                    theirs = int(values[2 * i + side])
                    if theirs == int(tree[child]):
                        continue
                    sl["want"][pad][child] = theirs
                    if child >= cap:
                        sl["rows_needed"][pad].append(child - cap)
                    else:
                        sl["diff"][pad].append(child)
            sl["outstanding"] = None
            return self._sync_request_next()
        if kind == wire.SYNC_ROWS:
            pad = statesync.PADS[out["pad"]]
            slots = out["list"]
            cap = sl["info"]["pads"][pad]["capacity"]
            rows = statesync.unpack_rows(sl["arrays"], pad, slots, body)
            if rows is None:
                return self._sync_verify_failed("rows_shape")
            if self.sync_verify and not statesync.verify_rows(
                rows, pad, slots, sl["want"][pad], cap
            ):
                return self._sync_verify_failed("rows")
            served = set(int(s) for s in slots)
            sl["rows_needed"][pad] = [
                s for s in sl["rows_needed"][pad] if s not in served
            ]
            sl["row_patches"][pad].append((slots, rows))
            self.sync_stats["subtrees_shipped"] += 1
            self.sync_stats["rows_installed"] += len(slots)
            self._sync_obs("sync.subtrees_shipped")
            self._sync_obs("sync.rows_installed", len(slots))
            sl["outstanding"] = None
            return self._sync_request_next()
        # SYNC_HISTORY
        hist = sl["history"]
        start = int(h["start"])
        count = int(h["count"])
        if start != hist["next"]:
            return []
        if int(h["total"]) != hist["total"]:
            # The responder's history length contradicts the verified
            # summary: treat as a lying/stale chunk.
            return self._sync_verify_failed("history_total")
        if count <= 0 or start + count > hist["total"]:
            # A forged count past the verified total would blow the
            # bounded install slice at finalize — reject it here.
            return self._sync_verify_failed("history_shape")
        chunk = statesync.unpack_history(sl["arrays"], count, body)
        if chunk is None:
            return self._sync_verify_failed("history_shape")
        hist["chunks"].append((start, count, chunk))
        hist["next"] = start + count
        sl["outstanding"] = None
        return self._sync_request_next()

    def _sync_verify_failed(self, what: str) -> List[Msg]:
        """A lying or bit-flipped chunk: never installed — reject, count,
        rotate to the next peer, and retransmit the SAME batch (work is
        consumed only on verified replies).  Persistent failure degrades
        to the full transfer."""
        target = self.sync_target
        self.sync_stats["chunk_retries"] += 1
        self._sync_obs("sync.chunk_retries")
        self._debug("sync_chunk_rejected", what=what)
        target["verify_failures"] = target.get("verify_failures", 0) + 1
        if target["verify_failures"] > SYNC_VERIFY_FAILURES:
            return self._sync_fallback("verify_failures")
        self._sync_rotate_peer()
        if target.get("mode") == "descend" and self._sync_local is not None:
            self._sync_local["outstanding"] = None
            return self._sync_request_next()
        return self._request_sync_roots()

    def _sync_fallback(self, reason: str) -> List[Msg]:
        """Degrade to the byte-exact full-checkpoint transfer (the choice
        is logged and counted; docs/state_sync.md fallback matrix)."""
        self.sync_stats["fallbacks"] += 1
        self._sync_obs("sync.fallbacks")
        self._sync_obs(f"sync.fallback.{reason}")
        self._debug("sync_fallback", reason=reason)
        op = self.sync_target["checkpoint_op"] if self.sync_target else 0
        self._sync_local = None
        self.sync_target = {
            "checkpoint_op": op, "total": None, "mode": "full",
        }
        self.sync_buffer = bytearray()
        self._last_sync_req = self._ticks
        self._sync_progress = self._ticks
        return self._request_sync_chunk()

    def _sync_finalize(self) -> List[Msg]:
        """Descent drained: reconstruct the responder's checkpoint state
        from our own state + the verified patches, gate on the whole-state
        checksum, serialize our own checkpoint blob, and install through
        the same tail as the full path."""
        from . import checkpoint as ckpt_mod
        from . import statesync

        sl = self._sync_local
        target = self.sync_target
        op = target["checkpoint_op"]
        info = sl["info"]
        arrays = {
            k: np.array(v, copy=True) for k, v in sl["arrays"].items()
        }
        for pad in statesync.PADS:
            for slots, rows in sl["row_patches"][pad]:
                idx = slots.astype(np.int64)
                for key, vals in rows.items():
                    arrays[key][idx] = vals
            arrays[f"{pad}/count"] = np.array(info["pads"][pad]["count"])
            arrays[f"{pad}/probe_overflow"] = np.array(
                info["pads"][pad]["probe_overflow"]
            )
        # History: the responder's capacity + our verified prefix + the
        # fetched append-only tail.
        hist = sl["history"]
        hcap = info["history_capacity"]
        for key in sl["hist_keys"]:
            old = sl["arrays"][key]
            grown = np.zeros((hcap,) + old.shape[1:], dtype=old.dtype)
            keep = min(hist["start"], hcap, old.shape[0])
            grown[:keep] = old[:keep]
            arrays[key] = grown
        for start, count, chunk in hist["chunks"]:
            for key, vals in chunk.items():
                arrays[key][start:start + count] = vals
        arrays["history/count"] = np.array(
            np.uint64(hist["total"])
        )
        if self.sync_verify:
            got = statesync.arrays_checksum(arrays)
            if got != target.get("state_checksum"):
                # The tree's covered columns could not explain the whole
                # divergence (or a bug/liar slipped through): NEVER
                # install — fetch the byte-exact blob instead.
                return self._sync_fallback("state_checksum")
        ledger = ckpt_mod.arrays_to_ledger(arrays)
        meta = info["meta"]
        _path, file_checksum = ckpt_mod.save_arrays(
            self.data_path, op, ckpt_mod.sparsify_arrays(arrays), meta
        )
        self.sync_stats["mode"] = "incremental"
        self._sync_obs("sync.mode.incremental")
        self._debug(
            "sync_incremental_install", checkpoint_op=op,
            bytes=self.sync_stats["bytes_incremental"],
            rows=self.sync_stats["rows_installed"],
        )
        return self._install_sync_state(
            ledger, meta, op, file_checksum, target.get("commit_max", op)
        )

    def _request_cold_chunk(self) -> List[Msg]:
        cf = self._cold_fetch
        _basename, checksum = cf["queue"][0]
        req = self._hdr(
            wire.Command.request_blocks,
            block_kind=wire.BLOCK_KIND_COLD,
            block_id=0,
            block_checksum=checksum,
            offset=len(cf["buf"]),
        )
        return [(("replica", self._sync_responder()), wire.encode(req))]

    def _on_cold_block(self, h: np.ndarray, body: bytes) -> List[Msg]:
        cf = self._cold_fetch
        if not cf["queue"] or int(h["block_kind"]) != wire.BLOCK_KIND_COLD:
            return []
        basename, checksum = cf["queue"][0]
        if wire.u128(h, "block_checksum") != checksum:
            return []
        if int(h["offset"]) != len(cf["buf"]):
            return self._request_cold_chunk()
        cf["buf"].extend(body)
        cf["attempts"] = 0
        # Progress resets the sync resend timer, or the tick would wipe an
        # in-flight multi-chunk transfer every SYNC_RESEND ticks.
        self._last_sync_req = self._ticks
        self._sync_progress = self._ticks
        if len(cf["buf"]) < int(h["total"]):
            return self._request_cold_chunk()
        if not self.machine.cold.install_file(
            basename, checksum, bytes(cf["buf"])
        ):
            cf["buf"] = bytearray()
            return self._request_cold_chunk()
        cf["queue"].pop(0)
        cf["buf"] = bytearray()
        if cf["queue"]:
            return self._request_cold_chunk()
        # All spill files present: complete the deferred install.
        self._cold_fetch = None
        return self._install_sync_checkpoint()

    def _install_sync_checkpoint(self) -> List[Msg]:
        """Install a fully-fetched checkpoint snapshot and rejoin."""
        from ..utils.fs import atomic_write

        target = self.sync_target
        op = target["checkpoint_op"]
        path = checkpoint_mod.path_for(self.data_path, op)
        # Durably in place BEFORE the superblock/manifest reference its
        # checksum — a crash in between must find the full blob on disk.
        atomic_write(path, bytes(self.sync_buffer))
        try:
            ledger, meta = checkpoint_mod.load(
                self.data_path, op, target["file_checksum"]
            )
        except RuntimeError:
            # Corrupt/raced snapshot: restart the fetch from scratch.
            self.sync_buffer = bytearray()
            self._last_sync_req = self._ticks
            return self._request_sync_chunk()
        # Cold tier: the checkpoint's cold_manifest names spill files LOCAL
        # to the responder — fetch (by checksum) any we lack before the
        # install can complete (re-entered once the fetch drains).
        cold_manifest = meta["machine"].get("cold_manifest", [])
        if cold_manifest and self.machine.cold.directory:
            try:
                damage = self.machine.cold.verify_manifest(cold_manifest)
            except ValueError:
                # Malicious/corrupt manifest (path-traversing entry): restart
                # the sync at whatever-is-latest from the NEXT responder.
                # Re-pinning the hostile peer's checkpoint_op would drop
                # every honest responder's reply (they serve only their own
                # checkpoint) and livelock the fetch.
                return self._start_full_sync()
            if damage:
                self._cold_fetch = {
                    "queue": damage,        # [(basename, checksum), ...]
                    "buf": bytearray(),
                    "attempts": 0,
                }
                self._last_sync_req = self._ticks
                return self._request_cold_chunk()
        self._cold_fetch = None
        self.sync_stats["mode"] = "full"
        self._sync_obs("sync.mode.full")
        return self._install_sync_state(
            ledger, meta, op, target["file_checksum"],
            target.get("commit_max", op),
        )

    def _install_sync_state(
        self, ledger, meta: dict, op: int, file_checksum: int,
        commit_max: int,
    ) -> List[Msg]:
        """The shared install tail of BOTH sync transports (full blob and
        incremental reconstruction): swap machine state, adopt sessions,
        reset the log around the snapshot, seal the superblock, rejoin.
        May raise loudly (DeviceStateUnrecoverable) when the snapshot is
        unservable in this machine mode — e.g. a cold-tier manifest at a
        sharded rejoiner — rather than wedging silently."""
        # A background checkpoint still in flight refers to the pre-sync
        # ledger; land it BEFORE the snapshot replaces machine/forest state
        # (its anchor then loses the _superblock_install merge below).
        self._checkpoint_drain()
        self.machine.ledger = ledger
        self.machine.restore_host_state(meta["machine"])
        self.sessions = {
            int(client_hex, 16): Session(
                client=int(client_hex, 16),
                session=s["session"],
                request=s["request"],
                reply_bytes=b"",
                slot=s["slot"],
            )
            for client_hex, s in meta.get("sessions", {}).items()
        }
        self.op_checkpoint = op
        self.commit_min = op
        self.commit_max = max(self.commit_max, commit_max)
        self.op = op
        self.headers = {}
        self.stash.clear()
        self.missing.clear()
        self.parent_checksum = 0
        self._verify_floor = op + 1  # nothing above the snapshot known yet
        self._log_suspect = False    # snapshot replaced the clobbered WAL
        # The snapshot (committed state through op) IS our log now; the
        # old adoption watermark referred to a WAL the sync replaced.
        self._log_adopted_op = op
        manifest_checksum = self.forest.adopt_base(
            ledger, meta, op, file_checksum
        )
        state = SuperBlockState(
            cluster=self.cluster,
            replica=self.replica,
            replica_count=self.replica_count,
            standby_count=self.standby_count,  # membership rides every write
            primary_offset=self._primary_offset,
            view=self.view,
            log_view=self.log_view,
            commit_min=self.commit_min,
            commit_max=self.commit_max,
            log_adopted_op=self._log_adopted_op,
            op_checkpoint=op,
            checkpoint_file_checksum=file_checksum,
            ledger_digest=self.machine.digest(),
            prepare_timestamp=self.machine.prepare_timestamp,
            commit_timestamp=self.machine.commit_timestamp,
            manifest_checksum=manifest_checksum,
        )
        state = self._superblock_install(state)
        self._sb_state = state
        self.forest.gc()
        self.sync_target = None
        self.sync_buffer = bytearray()
        self._sync_local = None
        self._sync_peer = None
        # Any view finish deferred before the sync refers to pre-snapshot
        # state; resuming it would regress the view.  Rejoin fresh.
        self._new_view_pending = None
        self.status = RECOVERING
        self._recovering_since = self._ticks
        return self._request_start_view(self.view)

    # -- clock ----------------------------------------------------------------

    def on_ping(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        pong = self._hdr(
            wire.Command.pong,
            ping_timestamp_monotonic=int(h["ping_timestamp_monotonic"]),
            pong_timestamp_wall=self._realtime(),
        )
        out = [(("replica", int(h["replica"])), wire.encode(pong))]
        # A RECOVERING replica learns newer views from ping headers: its
        # request_start_view targets the primary of ITS view, so in a
        # QUIESCENT cluster (no prepares flowing to bump it) a restart
        # into a stale view wedged forever — the view-change escape valve
        # is voters-only, so a restarted STANDBY never recovered (round-5
        # standby VOPR find, seed 13: standby stuck 'recovering' at view 3
        # under a view-4 cluster).  Adopt the view and re-aim the RSV.
        if self.status == RECOVERING and int(h["view"]) > self.view:
            self.view = int(h["view"])
            self._persist_view()
            out.extend(self._request_start_view(self.view))
        return out

    def on_pong(self, h: np.ndarray, body: bytes) -> List[Msg]:
        if not self._ingress_auth(h):
            return []
        ping_mono = int(h["ping_timestamp_monotonic"])
        if int(h["replica"]) < self.replica_count:
            # Standby clocks never affect cluster time (replica.zig:1274).
            self.clock.learn(
                int(h["replica"]), ping_mono, int(h["pong_timestamp_wall"])
            )
        # Feed the retry timeouts' RTT estimate (vsr.zig:593-634).
        self.rtt.sample(
            (self._monotonic() - ping_mono) / getattr(self, "tick_ns", TICK_NS)
        )
        # A pong from the current primary is liveness evidence — this is
        # what stands down a suspicion probe (see tick()'s two-stage
        # primary timeout).
        if (
            self.status == NORMAL
            and not self.is_primary
            and self._probe_sent_at is not None
            and int(h["replica"]) == self.primary_index()
        ):
            self._primary_spoke(real=False)
        return []

    # -- tick (timeouts; vsr.zig:543-712) -------------------------------------

    def tick(self) -> List[Msg]:
        self._ticks += 1
        out: List[Msg] = []
        if self.clock is not None:
            self.clock.tick()
        if self.blackbox is not None:
            # One ring append per tick: the recorder's heartbeat row —
            # op/commit watermarks and queue depths, the numbers a
            # postmortem reads first.
            self.blackbox.record(
                "tick", t=self._ticks, view=self.view, status=self.status,
                op=self.op, commit=self.commit_min,
                stash=len(self.stash), missing=len(self.missing),
                pipeline=len(self.pipeline),
            )
        if self.replica_count == 1:
            return out

        # Event-loop starvation guard: if OUR tick loop just slept through
        # several tick periods (host overload, GC, scheduler preemption on a
        # shared core), every liveness observation in that gap is stale —
        # the primary may have spoken while we weren't listening.  Refresh
        # the primary-liveness clock instead of campaigning on evidence
        # gathered while we ourselves were asleep (the reference's clock
        # code treats monotonic jumps with the same suspicion,
        # clock.zig monotonic discipline).  tick_ns is stamped by the TCP
        # bus (net/cluster_bus.py); the VOPR virtual clock leaves it unset
        # and keeps full control of liveness timing.
        tick_ns = getattr(self, "tick_ns", None)
        if tick_ns:
            now = self._monotonic()
            last = self._last_tick_mono
            self._last_tick_mono = now
            if last is not None and now - last > 4 * tick_ns:
                # Stale evidence: discount exactly the slept-through gap
                # from the silence clock (WITHOUT feeding the gap EWMA —
                # the gap was ours, not the primary's) and stand down any
                # probe raised on pre-sleep observations.  Advancing by the
                # gap, not resetting to now, keeps failover live: a backup
                # with RECURRING stalls (commit chunks, GC) would otherwise
                # re-arm the full budget on every stall and never elect a
                # replacement for a genuinely dead primary.
                slept = int((now - last) / tick_ns)
                self._last_primary_word = min(
                    self._ticks, self._last_primary_word + slept
                )
                self._probe_sent_at = None
                self._debug(
                    "tick_starved", gap_ms=round((now - last) / 1e6, 1)
                )

        # A repaired recovering-head log may rejoin view changes.
        self._maybe_clear_log_suspect()

        # Deferred view-change completion after repairs.
        if getattr(self, "_pending_finish", None) is not None:
            view = self._pending_finish
            self._pending_finish = None
            if self.status == VIEW_CHANGE and not self.missing:
                out.extend(self._finish_view_change(view))

        if self._ticks - self._last_ping >= PING_INTERVAL:
            self._last_ping = self._ticks
            ping = self._hdr(
                wire.Command.ping,
                checkpoint_op=self.op_checkpoint,
                ping_timestamp_monotonic=self.clock.ping_timestamp(),
            )
            out.extend(self._broadcast_nodes(wire.encode(ping)))

        if self._block_repair is not None:
            out.extend(self._tick_block_repair())
            return out

        if self.sync_target is not None:
            # A sync in flight is the only way forward regardless of what
            # status a concurrent view change left us in — resume it rather
            # than stranding the half-fetched snapshot.
            self.status = SYNCING
            if self._ticks - self._last_sync_req >= SYNC_RESEND:
                self._last_sync_req = self._ticks
                mode = self.sync_target.get("mode", "full")
                if self._cold_fetch is not None:
                    cf = self._cold_fetch
                    cf["attempts"] += 1
                    if cf["attempts"] >= 3 * self.replica_count:
                        # No reachable replica serves these cold runs
                        # (GC'd past this checkpoint): restart the sync at
                        # whatever is latest instead of waiting forever.
                        self._cold_fetch = None
                        self.sync_target = {
                            "checkpoint_op": 0, "total": None,
                            "mode": "full",
                        }
                        self.sync_buffer = bytearray()
                        if self._sync_peer is not None:
                            self._sync_peer = self._next_peer(self._sync_peer)
                        out.extend(self._request_sync_chunk())
                    else:
                        if self._sync_peer is not None:
                            self._sync_peer = self._next_peer(self._sync_peer)
                        cf["buf"] = bytearray()
                        out.extend(self._request_cold_chunk())
                    return out
                # Sync-PROGRESS stall (no payload accepted for a full
                # resend interval — distinct from the resend clock, which
                # checkpoint-refreshes legitimately restart): the current
                # responder is dead or pruned past our target — rotate.
                if self._ticks - self._sync_progress >= SYNC_RESEND:
                    if self._sync_peer is not None:
                        # Explicit-peer sync (block-repair fallback, or an
                        # earlier rotation): a silent responder means we
                        # guessed wrong — rotate.
                        self._sync_peer = self._next_peer(self._sync_peer)
                    else:
                        # Targeted sync whose default responder (the
                        # primary) went silent for a full resend interval:
                        # rotate through peers from here on.  Every replica
                        # at the target checkpoint serves sync, and a
                        # syncing replica abstains from view changes — so a
                        # DEAD primary would otherwise wedge both this
                        # replica (polling a corpse forever) and the
                        # cluster (one abstainer can break the view-change
                        # quorum).  Found by the overload fault kind: a
                        # flood-lagged replica synced exactly when the
                        # primary died.  Seed the rotation PAST the silent
                        # primary (seeding from self.replica can land right
                        # back on the corpse and burn another full resend
                        # interval of the election budget).
                        self._sync_peer = self._next_peer(
                            self.primary_index()
                        )
                    # Stalled long enough that the rotation clock must
                    # restart with the new responder.
                    self._sync_progress = self._ticks
                if mode == "roots":
                    t = self.sync_target
                    t["roots_attempts"] = t.get("roots_attempts", 0) + 1
                    if t["roots_attempts"] > SYNC_ROOTS_ATTEMPTS * max(
                        1, self.replica_count - 1
                    ):
                        # Nobody speaks sync_roots (merkle-off peers,
                        # version skew): the proven full transfer.
                        out.extend(self._sync_fallback("unsupported"))
                    else:
                        out.extend(self._request_sync_roots())
                elif mode == "descend":
                    t = self.sync_target
                    t["descend_attempts"] = t.get("descend_attempts", 0) + 1
                    if t["descend_attempts"] > SYNC_ROOTS_ATTEMPTS * max(
                        1, self.replica_count - 1
                    ):
                        # The roots responder vanished mid-descent and no
                        # peer serves subtrees (e.g. the only other
                        # merkle-on replica died): the full transfer is
                        # still served by everyone — take it instead of
                        # rotating forever.
                        out.extend(self._sync_fallback("unresponsive"))
                    elif self._sync_local is None:
                        out.extend(self._sync_fallback("lost_state"))
                    else:
                        self._sync_local["outstanding"] = None
                        out.extend(self._sync_request_next())
                else:
                    out.extend(self._request_sync_chunk())
            return out

        if self.status == NORMAL and self.is_primary:
            # Commit-stall abdication: a primary that journals prepares but
            # cannot EXECUTE them (e.g. restarted with an unrepairable WAL
            # prefix whose headers the cluster has pruned — VOPR seed
            # 400816) wedges the whole cluster while looking alive: its
            # prepares keep resetting every backup's liveness clock.  If
            # commit_min hasn't advanced for PRIMARY_ABDICATE ticks while
            # committable work exists, step down — the next view's primary
            # commits from its intact chain, and this replica's floor-
            # stall sync (see _maybe_start_sync) heals it as a backup.
            if self.commit_max > self.commit_min or self.pipeline:
                if self.commit_min == self._abdicate_commit_mark:
                    self._abdicate_ticks += 1
                else:
                    self._abdicate_commit_mark = self.commit_min
                    self._abdicate_ticks = 0
                if self._abdicate_ticks >= PRIMARY_ABDICATE:
                    self._abdicate_ticks = 0
                    self._debug(
                        "primary_abdicate", commit_min=self.commit_min,
                        commit_max=self.commit_max,
                    )
                    out.extend(self._begin_view_change(self.view + 1))
                    return out
            else:
                self._abdicate_ticks = 0
            if self._ticks - self._last_commit_sent >= COMMIT_HEARTBEAT:
                self._last_commit_sent = self._ticks
                # commit_checksum anchors the heartbeat to the CONTENT of
                # the committed head, not just its number: backups verify
                # it against their own header for that op, so a Byzantine
                # peer equivocating prepare bodies is detected before the
                # forged op ever executes (see on_commit).  0 when the
                # header is gone (pruned below a checkpoint) — legacy
                # frames decode the same way, so the field is skippable.
                head = self.headers.get(self.commit_min)
                commit = self._hdr(
                    wire.Command.commit,
                    commit=self.commit_min,
                    commit_checksum=(
                        wire.header_checksum(head) if head is not None else 0
                    ),
                    checkpoint_op=self.op_checkpoint,
                    timestamp_monotonic=self.clock.ping_timestamp(),
                )
                out.extend(self._broadcast_nodes(wire.encode(commit)))
            if self.pipeline and self._prepare_timeout.fired(self._ticks):
                # Quorumed-but-uncommitted entries can linger if the commit
                # attempt at ack time stalled on a repairable local fault;
                # retry the pipeline commit before resending.
                self._maybe_commit_pipeline(out)
                # Timeout fallback: re-broadcast unquorumed prepares to all
                # backups (the ring is the fast path, this is the safety
                # net).  Op-sorted, not insertion-ordered: _repipeline
                # re-inserts repaired mid-suffix entries out of order, and
                # resend emission order must be a function of protocol
                # state, not arrival history (tbmc canonical hashing).
                for entry in [
                    self.pipeline[o] for o in sorted(self.pipeline)
                ]:
                    if len(entry.ok_from) >= self.quorum_replication:
                        continue
                    read = self.journal.read_prepare(entry.op)
                    if read is None or (
                        wire.header_checksum(read[0]) != entry.checksum
                    ):
                        # OUR copy is unreadable (latent fault on the slot).
                        # Repair it from any backup that journaled it.
                        self.missing.setdefault(entry.op, entry.checksum)
                        entry.repair_rounds += 1
                        if entry.repair_rounds >= 3 * max(
                            1, self.replica_count - 1
                        ):
                            # Peers can't supply it either: abdicate.  The
                            # view change's nack protocol then proves the
                            # body was never quorum-journaled and truncates
                            # it (VOPR seed 10133) — or repairs it if some
                            # replica does hold it.
                            out.extend(
                                self._begin_view_change(self.view + 1)
                            )
                            break
                        continue
                    message = wire.encode(read[0], read[1])
                    for r in range(self.replica_count):
                        if r != self.replica and r not in entry.ok_from:
                            out.append((("replica", r), message))
            if (self.missing or self.stash or self._header_gaps()) and (
                self._repair_timeout.fired(self._ticks)
            ):
                # The primary repairs too: its own journal copy of a
                # committed-elsewhere op can be latently corrupt (found by
                # the VOPR read-fault family; commit would stall forever).
                out.extend(self._request_missing())
                out.extend(self._repair_gaps())
                gaps = self._header_gaps()
                if gaps:
                    # Header gaps at the PRIMARY (e.g. _extend_verification
                    # evicted a stale below-window fork after a restart+
                    # view-win): fetch canonical headers from the backups —
                    # without this the commit floor never clears.
                    req = self._hdr(
                        wire.Command.request_headers,
                        op_min=gaps[0], op_max=gaps[-1],
                    )
                    out.extend(self._broadcast(wire.encode(req)))

        elif self.status == NORMAL:
            # Backup: watch for a dead primary.  Standbys observe but never
            # call elections (they are not in the view-change quorum).
            # Two-stage suspicion (reference: RTT-adaptive timeouts,
            # vsr.zig:543-712): the silence budget adapts to the observed
            # inter-word gap, and the first firing sends a direct ping —
            # a busy-but-alive primary (long fsync, scheduler preemption)
            # answers from its IO loop and the election is avoided.  Only
            # a probe that ALSO goes unanswered starts the view change.
            silent = self._ticks - max(self._last_primary_word, 0)
            budget = min(
                max(NORMAL_HEARTBEAT,
                    int(self._primary_gap_ewma * PRIMARY_GAP_MULT)),
                PRIMARY_BUDGET_CAP,
            ) + self._heartbeat_jitter
            if not self.is_standby and silent >= budget:
                if self._probe_sent_at is None:
                    self._probe_sent_at = self._ticks
                    self._debug("primary_probe", silent_ticks=silent)
                    probe = self._hdr(
                        wire.Command.ping,
                        checkpoint_op=self.op_checkpoint,
                        ping_timestamp_monotonic=self.clock.ping_timestamp(),
                    )
                    out.append(
                        (("replica", self.primary_index()),
                         wire.encode(probe))
                    )
                elif self._ticks - self._probe_sent_at >= PROBE_GRACE:
                    self._debug(
                        "primary_timeout",
                        silent_ticks=silent,
                        probe_ticks=self._ticks - self._probe_sent_at,
                    )
                    self._last_primary_word = self._ticks
                    self._probe_sent_at = None
                    out.extend(self._begin_view_change(self.view + 1))
            # Repair runs INDEPENDENTLY of the suspicion state machine (its
            # own timeout, vsr.zig repair_timeout): a pending probe must not
            # starve gap fill — repairs may be exactly what un-wedges the
            # commit path.  (Re-check NORMAL: the campaign above may have
            # moved us to VIEW_CHANGE this tick.)
            if self.status == NORMAL and (
                self.missing or self.stash or self._header_gaps()
                or self.commit_max > self.op
            ) and self._repair_timeout.fired(self._ticks):
                out.extend(self._request_missing())
                out.extend(self._repair_gaps())
                # Header gaps: request by op with checksum 0 ("whatever you
                # have chained there"); adoption verifies the parent chain.
                primary = self.primary_index()
                for op in self._header_gaps():
                    req = self._hdr(
                        wire.Command.request_prepare,
                        prepare_op=op,
                        prepare_checksum=0,
                    )
                    out.append((("replica", primary), wire.encode(req)))
                if self.commit_max > self.op:
                    # Missing log SUFFIX (commit heartbeats got ahead of our
                    # head, e.g. the tail prepare was lost repeatedly): fetch
                    # the suffix headers; bodies repair via `missing`.
                    req = self._hdr(
                        wire.Command.request_headers,
                        op_min=self.op + 1,
                        op_max=self.commit_max,
                    )
                    out.append((("replica", primary), wire.encode(req)))

        elif self.status == VIEW_CHANGE:
            # Escalation BACKS OFF exponentially: a fixed window phase-
            # locks against repair — seed 700883 escalated through 300+
            # views because the lost-body nack-truncation round trip
            # (request_prepare -> nack quorum) took longer than one
            # window, and every escalation reset the repair from scratch.
            # Doubling the window per consecutive escalation (capped 16x)
            # guarantees the window eventually exceeds any bounded repair
            # RTT.  Deterministic (no prng draw: pinned seeds replay).
            window = VIEW_CHANGE_ESCALATE << min(self._vc_escalations, 4)
            if self._ticks - self._vc_started >= window:
                self._vc_escalations += 1
                out.extend(self._begin_view_change(self.view + 1))
            elif self._vc_timeout.fired(self._ticks):
                svc = self._hdr(wire.Command.start_view_change)
                out.extend(self._broadcast(wire.encode(svc)))
                if self._dvc_sent_for == self.view and (
                    self.primary_index() != self.replica
                ):
                    out.extend(self._send_dvc())
                if self.missing:
                    out.extend(self._request_missing())
                elif self._new_view_pending is not None:
                    # Header-gap finish attempt: re-checks the gap, either
                    # completing the view change or re-requesting headers
                    # (a lost headers response must not wedge us until
                    # escalation).
                    out.extend(
                        self._finish_view_change(self._new_view_pending)
                    )

        elif self.status == RECOVERING:
            if self._rsv_timeout.fired(self._ticks):
                out.extend(self._request_start_view(self.view))
                # If nobody answers (total cluster restart), force a view
                # change so the cluster re-certifies its log.  Time base is
                # entry into RECOVERING, not process age — a replica that
                # re-enters late (post-sync) must give the live primary a
                # chance to answer first.
                if not self.is_standby and (
                    self._ticks - self._recovering_since
                    >= NORMAL_HEARTBEAT + self._heartbeat_jitter
                ):
                    out.extend(self._begin_view_change(self.view + 1))

        return out

    # -- protocol-state capsule (sim/mc.py; docs/tbmc.md) ---------------------
    #
    # snapshot()/restore() capture EVERY field the consensus state machine
    # reads: a cluster step becomes a pure function of (capsule, event).
    # The ledger is folded to its digest — a capsule restores protocol
    # state bit-identically, and either the machine supports mc_snapshot/
    # mc_restore (the model checker's DigestMachine) or restore() asserts
    # the live ledger already sits at the capsule's digest (the production
    # TpuStateMachine: protocol state travels, executed state does not).
    # This is also the exact state surface a MAC/signature layer must
    # cover (ROADMAP item 4).

    _MC_SCALARS = (
        "cluster", "replica", "replica_count", "standby_count",
        "_primary_offset", "_boot_replica_count",
        "view", "log_view", "status", "op", "commit_min", "commit_max",
        "op_checkpoint", "parent_checksum", "_verify_floor", "_log_suspect",
        "_log_adopted_op", "byzantine_detections", "_dvc_sent_for",
        "_new_view_pending", "_pending_finish", "_sync_peer", "_rsv_nonce",
        "_repair_rotation", "commit_budget", "commit_budget_stopped",
        "overload_control", "ingress_verify", "auth_strict",
        "blocks_repaired",
    )
    # Pure-time counters and retry-arm state: behavior-relevant only
    # through WHICH timers are due — which the model checker replaces with
    # explicit mc_fire events — so mc.py excludes this group from the
    # canonical state hash (symmetric interleavings collapse) while the
    # capsule still round-trips it bit-identically.
    _MC_TIME = (
        "_ticks", "_last_ping", "_last_commit_sent", "_last_primary_word",
        "_primary_gap_ewma", "_probe_sent_at", "_pong_standdowns",
        "_floor_stall", "_abdicate_commit_mark", "_abdicate_ticks",
        "_vc_started", "_vc_escalations", "_last_sync_req",
        "_sync_progress",
        "_heartbeat_jitter", "_recovering_since", "_last_tick_mono",
    )
    _MC_CONTAINERS = (
        "headers", "stash", "missing", "_nacks", "_anchors", "_ack_certs",
        "pipeline", "svc_from", "dvc_from", "sessions", "sync_target",
        "_block_repair", "_cold_fetch", "_sb_state",
    )
    _MC_TIMEOUTS = (
        "_prepare_timeout", "_vc_timeout", "_rsv_timeout", "_repair_timeout",
    )
    # Lazily-created attributes (e.g. _repair_rotation) must restore to
    # ABSENT, not None — their getattr defaults are load-bearing.
    # Deliberately NOT in the capsule: _sync_local/_sync_pack_cache (bulk
    # numpy descent state, reconstructible — a restored-elsewhere replica
    # mid-descent degrades to the full transfer via the lost_state
    # fallback) and sync_stats (pure accounting, read by no protocol
    # decision).  Same-instance round trips (snapshot_interpose) keep
    # them as live attributes either way.
    _MC_MISSING = "__mc_missing__"

    def snapshot(self) -> dict:
        """Deep-copied protocol-state capsule; see section docstring."""
        import copy

        machine = self.machine
        if hasattr(machine, "mc_snapshot"):
            machine_cap = machine.mc_snapshot()
        else:
            machine_cap = {
                "folded_digest": machine.digest(),
                "prepare_timestamp": machine.prepare_timestamp,
                "commit_timestamp": machine.commit_timestamp,
            }
        clock_cap = None
        if self.clock is not None:
            clock_cap = {
                "samples": copy.deepcopy(self.clock.samples),
                "epoch_start_monotonic": self.clock.epoch_start_monotonic,
                "offset_ns": self.clock.offset_ns,
                "synchronized": self.clock._synchronized,
            }
        missing = self._MC_MISSING
        return {
            "scalars": {
                k: getattr(self, k, missing) for k in self._MC_SCALARS
            },
            "time": {k: getattr(self, k, missing) for k in self._MC_TIME},
            "containers": {
                k: copy.deepcopy(getattr(self, k, None))
                for k in self._MC_CONTAINERS
            },
            "sync_buffer": bytes(self.sync_buffer),
            "timeouts": {
                k: (t.attempts, t._last, t._interval)
                for k in self._MC_TIMEOUTS
                for t in (getattr(self, k),)
            },
            "rtt": self.rtt.estimate,
            "prng": self.prng.getstate(),
            # The SuperBlock OBJECT's in-memory state, not just the
            # replica's _sb_state cache: checkpoint() bumps sequence from
            # ``superblock.state``, so leaving it out made the next
            # view-persist's sequence a function of EXPLORATION HISTORY
            # (how many installs ever ran on this instance), not of the
            # restored state — a canonical-hash dedup killer the model
            # checker surfaced as a state-space explosion.
            "superblock": copy.deepcopy(self.superblock.state),
            "clock": clock_cap,
            "machine": machine_cap,
        }

    def restore(self, capsule: dict) -> None:
        """Reinstate a snapshot() capsule bit-identically (the capsule is
        deep-copied on the way in, so it stays reusable).  Works on the
        live instance or a freshly constructed one (the model checker's
        restart-into-state path); with a machine that cannot restore
        folded ledger state, the live digest must already match."""
        import copy

        # Order matters on a fresh instance: identity scalars first (the
        # clock needs replica/replica_count), then the clock rebuild
        # (_init_clock draws jitter from the prng), then the time fields
        # and prng state, which overwrite whatever the rebuild drew.
        missing = self._MC_MISSING

        def put(k, v):
            if v is missing or (isinstance(v, str) and v == missing):
                if hasattr(self, k):
                    delattr(self, k)
            else:
                setattr(self, k, v)

        for k, v in capsule["scalars"].items():
            put(k, v)
        clock_cap = capsule["clock"]
        if clock_cap is not None:
            if self.clock is None:
                self._init_clock()
            self.clock.replica_count = self.replica_count
            self.clock.replica = self.replica
            self.clock.samples = copy.deepcopy(clock_cap["samples"])
            self.clock.epoch_start_monotonic = (
                clock_cap["epoch_start_monotonic"]
            )
            self.clock.offset_ns = clock_cap["offset_ns"]
            self.clock._synchronized = clock_cap["synchronized"]
            self.time_ns = self._primary_now
        for k, v in capsule["time"].items():
            put(k, v)
        for k, v in capsule["containers"].items():
            put(k, copy.deepcopy(v))
        self.sync_buffer = bytearray(capsule["sync_buffer"])
        self.prng.setstate(capsule["prng"])
        for k, (attempts, last, interval) in capsule["timeouts"].items():
            t = getattr(self, k)
            t.attempts, t._last, t._interval = attempts, last, interval
        self.rtt.estimate = capsule["rtt"]
        self.superblock.state = copy.deepcopy(capsule["superblock"])
        machine_cap = capsule["machine"]
        if hasattr(self.machine, "mc_restore"):
            self.machine.mc_restore(machine_cap)
        else:
            live = self.machine.digest()
            want = machine_cap["folded_digest"]
            if live != want:
                raise RuntimeError(
                    "capsule folds the ledger to its digest: restore() "
                    f"needs the live ledger at {want:#x}, found {live:#x} "
                    "(docs/tbmc.md — executed state does not travel)"
                )
            self.machine.prepare_timestamp = machine_cap["prepare_timestamp"]
            self.machine.commit_timestamp = machine_cap["commit_timestamp"]

    # -- explicit timeout events (sim/mc.py) ----------------------------------

    MC_TIMEOUT_KINDS = (
        "commit_hb", "prepare", "repair", "suspect",
        "vc_resend", "vc_escalate", "rsv", "recover_campaign",
    )

    def mc_enabled_timeouts(self) -> List[str]:
        """Timeout kinds that could act in the current status — the model
        checker's enumerable timer alphabet (virtual time is abstracted:
        WHICH timer fires is the exploration dimension, not when)."""
        kinds: List[str] = []
        if self.replica_count == 1 or self.clock is None:
            return kinds
        repairable = bool(
            self.missing or self.stash or self._header_gaps()
        )
        if self.status == NORMAL and self.is_primary:
            kinds.append("commit_hb")
            if self.pipeline:
                kinds.append("prepare")
            if repairable:
                kinds.append("repair")
        elif self.status == NORMAL:
            if not self.is_standby:
                kinds.append("suspect")
            if repairable or self.commit_max > self.op:
                kinds.append("repair")
        elif self.status == VIEW_CHANGE:
            kinds.extend(("vc_resend", "vc_escalate"))
        elif self.status == RECOVERING:
            kinds.append("rsv")
            if not self.is_standby:
                kinds.append("recover_campaign")
        return kinds

    def mc_fire(self, kind: str) -> List[Msg]:
        """Force exactly the named timer due and run one tick() — every
        other timer is quieted, so the tick's output is a deterministic
        function of the protocol capsule and ``kind`` alone."""
        assert kind in self.MC_TIMEOUT_KINDS, kind
        # Virtual time leaps between model-checker events; the exact span
        # is irrelevant (every timer below is re-armed explicitly).
        self._ticks += 1000
        t = self._ticks + 1  # the value tick() observes after increment

        def due(tm) -> None:
            tm._last = t - max(1, tm._interval)

        for name in self._MC_TIMEOUTS:
            getattr(self, name)._last = t  # quiet
        self._last_ping = t
        self._last_commit_sent = t
        self._last_primary_word = t
        self._probe_sent_at = None
        self._recovering_since = t
        self._vc_started = t
        if kind == "commit_hb":
            self._last_commit_sent = t - COMMIT_HEARTBEAT
        elif kind == "prepare":
            due(self._prepare_timeout)
        elif kind == "repair":
            due(self._repair_timeout)
        elif kind == "suspect":
            # Fold the two-stage suspicion (silence budget + unanswered
            # probe) into one campaign event.  The +1000 leap above keeps
            # t comfortably past the largest possible budget, so the
            # silence window is always satisfiable without clamping to 0.
            self._last_primary_word = t - (
                PRIMARY_BUDGET_CAP + NORMAL_HEARTBEAT
                + self._heartbeat_jitter + 1
            )
            self._probe_sent_at = t - PROBE_GRACE
        elif kind == "vc_resend":
            due(self._vc_timeout)
        elif kind == "vc_escalate":
            self._vc_started = t - (
                VIEW_CHANGE_ESCALATE << min(self._vc_escalations, 4)
            )
        elif kind == "rsv":
            due(self._rsv_timeout)
        elif kind == "recover_campaign":
            due(self._rsv_timeout)
            self._recovering_since = t - (
                NORMAL_HEARTBEAT + self._heartbeat_jitter
            )
        return self.tick()
