"""tbmc: exhaustive small-scope model checker for the VSR consensus +
certified-commit protocol (docs/tbmc.md).

The VOPR (sim/vopr.py) samples the protocol by *random* seeded schedules;
this module checks it *exhaustively* at small scopes: every legal
interleaving of delivery / drop / crash / restart / partition / timeout /
client / forged-frame events is enumerated against the safety invariants,
with any violation emitted as a deterministic, replayable JSON schedule
(``vopr --replay-schedule``).

Three layers:

- **EXTRACT** — the cluster step is a pure function of (canonical state,
  event): ``VsrReplica.snapshot()/restore()`` (vsr/consensus.py) capture
  the protocol-state capsule per replica (ledger folded to its digest),
  ``SimCluster.dispatch()`` delivers exactly one frame, ``mc_fire()``
  fires exactly one named timer, and ``FifoNet`` (sim/network.py) makes
  the network an explicit per-link FIFO whose cross-link interleaving is
  the exploration dimension.  The state machine is ``DigestMachine`` — a
  digest-chain stand-in whose timestamps mirror the real machine's
  ``prepare()`` exactly (they ride in prepare headers), so the production
  consensus code runs unmodified.
- **EXPLORE** — DFS over all interleavings with canonical state hashing
  (symmetric interleavings collapse; pure-time counters, retry-arm state
  and prng internals are excluded — mc_fire makes firing independent of
  them), sleep-set partial-order reduction over a conservative
  conflict relation, and depth / view / budget bounds plus a state cap.
- **REPLAY** — a violation dumps the exact event schedule as JSON; the
  same ``McCluster.apply_event`` path re-executes it bit-identically
  (``replay_schedule``), asserting the recorded violation and canonical
  state key reproduce.

Invariants, checked after every event:

- **agreement** — no two replicas ever commit different prepares at the
  same op number (committed identity = prepare header checksum, which
  covers the body via checksum_body); restarted replicas re-committing
  must reproduce their own recorded identities (crash-replay
  determinism).
- **quorum_journal** — a committed prepare is journaled, byte-verified,
  on at least ``quorum_replication`` replicas' WALs (dead replicas'
  storage included).
- **certified_commit** — a backup executes only content that
  parent-chains to a source-authenticated anchor (the byzantine-domain
  defense, independently re-verified here so the ``anchor_certify``
  mutation is caught by the checker, not by the gate it disables).
- **view_monotonic** — a live replica's view never regresses.
- **reply validity / coherence** — one reply identity per client request
  ever, and every accepted reply is backed by a committed prepare with
  matching (client, request).

MUTATION PROOF (tools/mc_smoke.py): each seeded protocol mutation —
``not_primary`` (primary-origin ingress check skipped),
``anchor_certify`` (certified commits compiled out), ``vc_quorum``
(view-change quorum off by one) — provably yields a counterexample
within its scope, while the unmutated tree is exhaustively clean: the
same passes-with-defenses / fails-without discipline every fault domain
already pins.

Determinism note: storage rng state is excluded from the canonical hash —
sound because fault probabilities are 0 here and ``crash_budget <= 1``
means the single crash's torn-write draws always start from the seeded
initial rng state.
"""

from __future__ import annotations

import copy
import dataclasses
import hashlib
import json
import tempfile
import time
from typing import Dict, FrozenSet, List, Optional, Tuple

import numpy as np

from .. import types
from ..config import ClusterConfig
from ..obs.metrics import registry as _obs
from ..vsr import wire
from ..vsr.consensus import NORMAL, quorums
from ..vsr.journal import Journal
from .cluster import SimCluster
from .network import FifoNet

# Tiny cluster format: 1 KiB messages (768 B bodies: one 128 B account
# event, three headers per DVC/SV window — enough for the 2-op scope),
# 32 WAL slots, checkpoint interval 19 (never reached at scope depth).
MC_CONFIG = ClusterConfig(
    message_size_max=1024,
    journal_slot_count=32,
    lsm_batch_multiple=8,
    pipeline_prepare_queue_max=4,
    clients_max=4,
)

MUTATIONS = (
    "not_primary", "anchor_certify", "vc_quorum",
    # Auth-layer knockouts (vsr/auth.py + consensus._ingress_auth /
    # _note_ack / _ack_certified — the byzantine-primary scope's proof
    # subjects, tools/auth_smoke.py):
    "mac_skip",       # _ingress_auth accepts every frame unverified
    "key_confusion",  # MAC accepted if it verifies under ANY node's key
    "cert_downgrade", # backup execution skips the ack-certificate gate
    "equiv_dedup",    # conflicting prepares adopted + re-acked; one-vote-
                      # per-op certificate dedup removed
    # Reconfiguration knockout (docs/reconfiguration.md): view-change
    # quorum sized from the membership the process booted with, ignoring
    # committed reconfigure ops — after a 3+1 -> 4+0 promotion the stale
    # VC quorum (2 of 4) stops intersecting replication quorums.
    "reconfig_stale_quorum",
)

Event = Tuple  # flat tuples of str/int — JSON round-trippable


class McViolation(AssertionError):
    """A safety invariant failed; carries the machine-readable kind."""

    def __init__(self, kind: str, detail: str) -> None:
        super().__init__(f"{kind}: {detail}")
        self.kind = kind
        self.detail = detail


@dataclasses.dataclass(frozen=True)
class McScope:
    """Exploration bounds — the 'small scope' of the small-scope claim."""

    n_replicas: int = 3
    n_clients: int = 1
    ops_per_client: int = 2
    crash_budget: int = 1
    byz_budget: int = 0
    drop_budget: int = 0
    partition_budget: int = 0
    timeout_budget: int = 4
    # Wire-auth scope (vsr/auth.py): every replica armed with the
    # deterministic cluster keychain in STRICT mode — source-authenticated
    # frames must carry a valid origin MAC, and backups execute only
    # certificate-covered ops.
    auth: bool = False
    # Byzantine-PRIMARY adversary (docs/tbmc.md): ``byzp_budget`` forged-
    # frame events from the replica holding seat ``byzp_replica`` (seat 0
    # = the bootstrap primary).  The adversary's internal state stays
    # honest; each event injects one frame CONSTRUCTIBLE from its own key
    # material and journal — equivocating prepares, own-or-claimed forged
    # votes, fork-anchoring commits, fork-serving headers/SVs, forged
    # sync replies.  It never holds another node's key: frames claiming a
    # peer identity carry the adversary's own-key MAC (the key_confusion
    # bait) and must die at _ingress_auth when defenses are on.
    byzp_budget: int = 0
    byzp_replica: int = 0
    # Slow-timer scope assumption: timers fire only at QUIESCENT states
    # (no deliverable frame anywhere) — a consensus tick (~10 ms) is
    # orders of magnitude slower than a link delivery, so racing a timer
    # against an in-flight frame explores schedules real deployments
    # cannot produce.  False widens the scope to fully-racy timers (the
    # mutation hunts use it; docs/tbmc.md discusses the soundness
    # trade).
    timeout_quiescent_only: bool = True
    # Optional restriction of the timer alphabet (None = every kind in
    # VsrReplica.MC_TIMEOUT_KINDS): a targeted hunt scopes down to the
    # kinds its scenario needs — the unmutated control runs the SAME
    # restricted scope, so the passes/fails discipline is preserved.
    timeout_kinds: Optional[Tuple[str, ...]] = None
    # Reconfiguration scope (docs/reconfiguration.md): ``n_standbys``
    # non-voting stream consumers at indexes [n_replicas, n_replicas +
    # n_standbys); ``reconfig`` prepends a promote-everything membership
    # op (reconfigure to n_replicas + n_standbys voters, 0 standbys) to
    # client 0's script, so the flip interleaves with the scope's crash /
    # timeout / drop alphabet during exploration.
    n_standbys: int = 0
    reconfig: bool = False
    client_sends: int = 1       # sends per request (1 = no resends)
    max_view: int = 2           # states beyond are bound-pruned
    depth_max: int = 24
    max_states: int = 120_000
    seed: int = 0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, data: dict) -> "McScope":
        if data.get("timeout_kinds") is not None:
            data = dict(data, timeout_kinds=tuple(data["timeout_kinds"]))
        return cls(**data)


# -- the digest-chain state machine ------------------------------------------


class _ColdStub:
    """Cold-tier surface the consensus layer touches; always empty."""

    directory = None
    garbage: list = []

    def locate_by_checksum(self, checksum):
        return None

    def verify_manifest(self, manifest):
        return []

    def install_file(self, *a, **k):
        return False


class DigestMachine:
    """Protocol-faithful state-machine stand-in for model checking.

    Op effects fold into a running digest chain (digest' = H(digest, op
    bytes)); ``prepare()`` mirrors TpuStateMachine.prepare exactly, so
    the timestamps that ride in prepare headers — and therefore every
    header checksum the protocol compares — match the real machine's.
    The whole ledger is this digest: snapshot/restore is three ints.
    """

    def __init__(self, ledger_config=None, batch_lanes=0, spill_dir=None,
                 hot_transfers_capacity_max=None, host_engine=False,
                 **_ignored) -> None:
        self.prepare_timestamp = 0
        self.commit_timestamp = 0
        self._digest = 0xD16E57_C4A1  # arbitrary nonzero chain seed
        self.scrub_interval = 0
        self.merkle_enabled = False
        self.merkle_armed = False
        self.scrub_armed = False
        self.scrub_paranoid = False
        self.retry_tick_s = 0
        self.shards = 0
        self.pipeline_depth = 1
        self.GROUP_K = 1
        self.ledger = None
        self.cold = _ColdStub()

    # -- the surface consensus/replica actually touch ------------------------

    def commitment_root(self) -> int:
        return 0  # no commitments in the folded-digest stand-in

    def prepare(self, operation: str, count: int,
                wall_clock_ns: int = 0) -> int:
        # Byte-for-byte the real machine's timestamp assignment
        # (machine.py prepare, state_machine.zig:503-512).
        if wall_clock_ns > self.prepare_timestamp:
            self.prepare_timestamp = wall_clock_ns
        if operation in ("create_accounts", "create_transfers"):
            self.prepare_timestamp += count
        return self.prepare_timestamp

    def _fold(self, *parts: bytes) -> None:
        h = hashlib.blake2b(digest_size=16)
        h.update(self._digest.to_bytes(16, "little"))
        for p in parts:
            h.update(p)
        self._digest = int.from_bytes(h.digest(), "little")

    def commit_batch(self, kind: str, batch, timestamp: int):
        batch = np.asarray(batch)
        self._fold(kind.encode(), batch.tobytes(),
                   int(timestamp).to_bytes(8, "little"))
        if timestamp > self.commit_timestamp:
            self.commit_timestamp = timestamp
        return np.zeros(0, dtype=types.EVENT_RESULT_DTYPE)

    def lookup_accounts(self, ids):
        return np.zeros(0, dtype=types.ACCOUNT_DTYPE)

    def lookup_transfers(self, ids):
        return np.zeros(0, dtype=types.TRANSFER_DTYPE)

    def get_proof(self, ident, kind="accounts"):
        return b""

    def get_account_transfers(self, filt):
        return np.zeros(0, dtype=types.TRANSFER_DTYPE)

    def get_account_history(self, filt):
        return np.zeros(0, dtype=types.TRANSFER_DTYPE)

    def digest(self) -> int:
        return self._digest

    def scrub_arm(self) -> bool:
        return False

    def warmup(self) -> None:
        pass

    def host_state(self) -> dict:
        return {}

    def _maybe_evict_between_batches(self) -> None:
        pass

    # -- capsule --------------------------------------------------------------

    def mc_snapshot(self) -> dict:
        return {
            "digest": self._digest,
            "prepare_timestamp": self.prepare_timestamp,
            "commit_timestamp": self.commit_timestamp,
        }

    def mc_restore(self, cap: dict) -> None:
        self._digest = cap["digest"]
        self.prepare_timestamp = cap["prepare_timestamp"]
        self.commit_timestamp = cap["commit_timestamp"]


# -- the deterministic client -------------------------------------------------


class McClient:
    """Minimal deterministic client: a scripted op list, one in-flight
    request, explicit send events (the checker chooses targets and
    resends).  Registration happens during bootstrap."""

    def __init__(self, client_id: int, cluster_id: int,
                 ops: List[Tuple[wire.Operation, bytes]], harness) -> None:
        self.client_id = client_id
        self.cluster_id = cluster_id
        self.ops = list(ops)
        self.harness = harness
        self.session = 0
        self.request_number = 0
        self.parent = 0
        self.next_op = 0
        self.inflight: Optional[dict] = None
        self.evicted = False
        # request number -> (op, body checksum): the coherence oracle.
        self.reply_log: Dict[int, Tuple[int, int]] = {}

    def build_send(self, target: int) -> bytes:
        """Create-or-resend the current request; returns the frame."""
        if self.inflight is None:
            if self.session == 0:
                operation: wire.Operation = wire.Operation.register
                body = b""
            else:
                operation, body = self.ops[self.next_op]
            h = wire.new_header(
                wire.Command.request,
                cluster=self.cluster_id,
                client=self.client_id,
                request=self.request_number,
                parent=self.parent,
                session=self.session,
                operation=int(operation),
            )
            message = wire.encode(h, body)
            checksum = wire.header_checksum(wire.decode_header(message)[0])
            self.inflight = {
                "message": message,
                "checksum": checksum,
                "operation": int(operation),
                "sends": 0,
            }
        self.inflight["sends"] += 1
        return self.inflight["message"]

    def on_message(self, h: np.ndarray, command: wire.Command,
                   body: bytes, now: int) -> None:
        if command == wire.Command.eviction:
            self.evicted = True
            self.inflight = None
            return
        if command != wire.Command.reply:
            return
        request_n = int(h["request"])
        identity = (int(h["op"]), wire.u128(h, "checksum_body"))
        seen = self.reply_log.get(request_n)
        if seen is not None and seen != identity:
            raise McViolation(
                "reply_coherence",
                f"client {self.client_id:#x}: two reply identities for "
                f"request {request_n}: {seen} vs {identity}",
            )
        self.reply_log[request_n] = identity
        if self.inflight is None:
            return
        if wire.u128(h, "request_checksum") != self.inflight["checksum"]:
            return  # stale reply
        self.harness.on_reply_accepted(self.client_id, h)
        if self.inflight["operation"] == int(wire.Operation.register):
            self.session = int(h["op"])
            self.request_number = 1
        else:
            self.next_op += 1
            self.request_number += 1
        self.parent = self.inflight["checksum"]
        self.inflight = None

    def snapshot(self) -> dict:
        return {
            "session": self.session,
            "request_number": self.request_number,
            "parent": self.parent,
            "next_op": self.next_op,
            "inflight": copy.deepcopy(self.inflight),
            "evicted": self.evicted,
            "reply_log": dict(self.reply_log),
        }

    def restore(self, cap: dict) -> None:
        self.session = cap["session"]
        self.request_number = cap["request_number"]
        self.parent = cap["parent"]
        self.next_op = cap["next_op"]
        self.inflight = copy.deepcopy(cap["inflight"])
        self.evicted = cap["evicted"]
        self.reply_log = dict(cap["reply_log"])


class _McSimCluster(SimCluster):
    """SimCluster whose replicas (including restart-created ones) carry
    the armed mutation set and the mc-deterministic RSV nonce."""

    def __init__(self, *args, mc_mutations: frozenset = frozenset(),
                 **kwargs) -> None:
        # Set BEFORE super().__init__: the base constructor builds the
        # initial replicas through _make_replica below.
        self.mc_mutations = mc_mutations
        super().__init__(*args, **kwargs)

    def _make_replica(self, i: int):
        replica = super()._make_replica(i)
        replica.mc_mutations = self.mc_mutations
        replica.mc_deterministic_nonce = True
        return replica


# -- canonical state encoding -------------------------------------------------


def _enc(update, obj) -> None:
    """Deterministic tagged encoding of capsule-shaped values."""
    if obj is None:
        update(b"N;")
    elif isinstance(obj, bool):
        update(b"B1;" if obj else b"B0;")
    elif isinstance(obj, int):
        update(b"I" + str(obj).encode() + b";")
    elif isinstance(obj, float):
        update(b"F" + repr(obj).encode() + b";")
    elif isinstance(obj, str):
        update(b"S" + obj.encode() + b";")
    elif isinstance(obj, (bytes, bytearray, memoryview)):
        update(b"Y")
        update(bytes(obj))
        update(b";")
    elif isinstance(obj, (np.ndarray, np.void)):
        update(b"A")
        update(obj.tobytes())
        update(b";")
    elif isinstance(obj, np.generic):
        _enc(update, obj.item())
    elif isinstance(obj, (list, tuple)):
        update(b"L")
        for x in obj:
            _enc(update, x)
        update(b"l")
    elif isinstance(obj, (set, frozenset)):
        _enc(update, sorted(obj, key=repr))
    elif isinstance(obj, dict):
        update(b"D")
        for k in sorted(obj, key=repr):
            _enc(update, k)
            _enc(update, obj[k])
        update(b"d")
    elif dataclasses.is_dataclass(obj):
        _enc(update, dataclasses.astuple(obj))
    else:
        update(repr(obj).encode())


# -- the harness: cluster + events + invariants -------------------------------


class McCluster:
    """The model checker's executable cluster: the production consensus
    code (via SimCluster) over FifoNet + DigestMachine, with explicit
    per-event application, full snapshot/restore, canonical hashing, and
    the invariant scan.  ``apply_event`` is shared verbatim by the
    explorer and ``replay_schedule`` — replay identity by construction."""

    def __init__(self, scope: McScope, workdir: str,
                 mutations: Tuple[str, ...] = ()) -> None:
        for m in mutations:
            assert m in MUTATIONS, f"unknown mutation {m!r}"
        self.scope = scope
        self.mutations = tuple(mutations)
        self.net = FifoNet()
        self.net.drop_if = self._blocked
        self.cluster = _McSimCluster(
            workdir,
            n_replicas=scope.n_replicas,
            n_standbys=scope.n_standbys,
            n_clients=0,
            seed=scope.seed,
            config=MC_CONFIG,
            net=self.net,
            hash_log=False,
            audit=False,
            machine_factory=DigestMachine,
            mc_mutations=frozenset(mutations),
            auth=(
                {"strict": True, "seed": scope.seed} if scope.auth else None
            ),
        )
        self.clients: Dict[int, McClient] = {}
        for j in range(scope.n_clients):
            cid = (1009 * (j + 1)) | 1
            ops = []
            if scope.reconfig and j == 0:
                # The membership op rides client 0 FIRST: the promotion
                # commits early, and every later op / fault event
                # exercises the post-flip quorums.
                ops.append((
                    wire.Operation.reconfigure,
                    wire.reconfigure_body(
                        scope.n_replicas + scope.n_standbys, 0
                    ),
                ))
            for k in range(scope.ops_per_client):
                acc = np.zeros(1, dtype=types.ACCOUNT_DTYPE)
                acc["id_lo"] = 1000 * (j + 1) + k + 1
                acc["ledger"] = 1
                acc["code"] = 1
                ops.append((wire.Operation.create_accounts, acc.tobytes()))
            client = McClient(cid, self.cluster.cluster_id, ops, self)
            self.clients[cid] = client
            # Registered into the cluster so SimCluster.dispatch routes
            # reply frames through the same decode path as replica frames.
            self.cluster.clients[cid] = client
        self.budgets = {
            "crash": scope.crash_budget,
            "byz": scope.byz_budget,
            "byzp": scope.byzp_budget,
            "drop": scope.drop_budget,
            "partition": scope.partition_budget,
            "timeout": scope.timeout_budget,
        }
        self.partition: Optional[int] = None  # isolated replica index
        # Last client-carrying prepare delivered to each replica — the
        # forged-frame event's raw material (ByzantineActor's role).
        self.material: Dict[int, bytes] = {}
        # op -> (header checksum, client, request): the committed record.
        self.canon: Dict[int, Tuple[int, int, int]] = {}
        # Per replica-index commit log (survives crash/restart): the
        # crash-replay determinism oracle.
        self.committed: Dict[int, Dict[int, int]] = {
            i: {} for i in range(self.cluster.total)
        }
        self.watermark: Dict[int, int] = {
            i: 0 for i in range(self.cluster.total)
        }
        self.view_seen: Dict[int, int] = {}
        self.checking = False
        # Identity map from live replica state to the capsule part it
        # currently equals (None = unknown/diverged): restore() skips
        # replicas whose target part IS the live one — with parts shared
        # by reference across the explorer's frames, a DFS restore
        # usually touches one replica, not all of them.
        self._live_parts: Optional[List] = None

    # -- partitions -----------------------------------------------------------

    def _blocked(self, src, dst) -> bool:
        p = self.partition
        if p is None:
            return False
        if src[0] == "replica" and dst[0] == "replica":
            return (src[1] == p) != (dst[1] == p)
        return False

    # -- bootstrap ------------------------------------------------------------

    def bootstrap(self, max_ticks: int = 800) -> None:
        """Run concrete virtual time — full immediate delivery — until the
        cluster is NORMAL, clock-synchronized, registered, and quiescent.
        Exploration then starts from this root with time FROZEN (timer
        behavior becomes the explicit mc_fire event alphabet)."""
        cl = self.cluster
        for _ in range(max_ticks):
            cl.t += 1
            for i in range(cl.total):
                if cl.alive[i]:
                    cl.tick_replica(i)
            self._drain()
            for cid in sorted(self.clients):
                c = self.clients[cid]
                if c.session == 0 and c.inflight is None:
                    self.net.send(("client", cid), ("replica", 0),
                                  c.build_send(0), cl.t)
            self._drain()
            if self._quiescent():
                break
        else:
            raise RuntimeError("mc bootstrap did not reach quiescence")
        # Flush bootstrap's unsynced writes NOW: apply_event syncs after
        # every event, so the root must already satisfy "pending is
        # empty" or the first event would change UNTOUCHED replicas'
        # storage images and break the incremental-hash contract.
        for st in cl.storages:
            if st.pending:
                st.sync()
        self.checking = True
        self._scan_invariants()

    def _drain(self) -> None:
        guard = 0
        while self.net.in_flight:
            src, dst = self.net.busy_links()[0]
            message = self.net.pop(src, dst)
            self._note_material(dst, message)
            self.cluster.dispatch(src, dst, message)
            guard += 1
            assert guard < 200_000, "bootstrap delivery did not quiesce"

    def _quiescent(self) -> bool:
        cl = self.cluster
        live = [r for r, a in zip(cl.replicas, cl.alive) if a]
        if len(live) != cl.total:
            return False
        if any(r.status != NORMAL for r in live):
            return False
        if len({r.view for r in live}) != 1:
            return False
        if len({r.commit_min for r in live}) != 1:
            return False
        if any(r.clock.realtime_synchronized is None for r in live):
            return False
        if any(c.session == 0 or c.inflight is not None
               for c in self.clients.values()):
            return False
        return self.net.in_flight == 0

    # -- events ---------------------------------------------------------------

    def enabled_events(self) -> List[Event]:
        cl = self.cluster
        ev: List[Event] = []
        for (src, dst) in self.net.busy_links():
            if dst[0] == "replica":
                if not cl.alive[dst[1]] or self._blocked(src, dst):
                    continue
            ev.append(("deliver", src[0], src[1], dst[0], dst[1]))
            if self.budgets["drop"] > 0:
                ev.append(("drop", src[0], src[1], dst[0], dst[1]))
        deliverable = bool(ev)
        if self.budgets["timeout"] > 0 and not (
            self.scope.timeout_quiescent_only and deliverable
        ):
            allowed = self.scope.timeout_kinds
            for i in range(cl.total):
                if not cl.alive[i]:
                    continue
                for kind in cl.replicas[i].mc_enabled_timeouts():
                    if allowed is None or kind in allowed:
                        ev.append(("timeout", i, kind))
        for cid in sorted(self.clients):
            c = self.clients[cid]
            if c.evicted:
                continue
            fresh = c.inflight is None and c.next_op < len(c.ops)
            resend = (
                c.inflight is not None
                and c.inflight["sends"] < self.scope.client_sends
            )
            if fresh or resend:
                for t in range(cl.n):
                    if cl.alive[t]:
                        ev.append(("client", cid, t))
        if self.budgets["crash"] > 0:
            live = sum(1 for a in cl.alive if a)
            if live > 1:  # never kill the last replica
                for i in range(cl.total):
                    if cl.alive[i]:
                        ev.append(("crash", i))
        for i in range(cl.total):
            if not cl.alive[i]:
                ev.append(("restart", i))
        if self.budgets["byz"] > 0:
            for i in range(cl.total):
                if cl.alive[i] and i in self.material:
                    for v in range(cl.n):
                        if v != i and cl.alive[v]:
                            ev.append(("byz", i, v))
        if self.budgets["byzp"] > 0 and self._byzp_fork() is not None:
            b = self.scope.byzp_replica
            for v in range(cl.n):
                if v == b or not cl.alive[v]:
                    continue
                for sub in ("equiv_prepare", "anchor_commit",
                            "fork_headers", "fork_sv", "forge_sync"):
                    ev.append(("byzp", sub, v))
                for claim in range(cl.n):
                    if claim != v:
                        ev.append(("byzp", "forge_ok", claim, v))
        if self.budgets["partition"] > 0 and self.partition is None:
            for i in range(cl.n):
                ev.append(("partition", i))
        if self.partition is not None:
            ev.append(("heal",))
        return sorted(ev, key=self._event_order)

    # Fault-first deterministic exploration order: budgeted fault events
    # sort before progress events, so the DFS descends into
    # budget-spent-early subtrees (small: once the fuel is gone the tree
    # is pure delivery) before the much larger happy-path-first ones —
    # fault-induced counterexamples surface early instead of after the
    # full fault-free tree.
    _KIND_ORDER = {
        "byzp": 0, "byz": 1, "drop": 2, "partition": 3, "heal": 4,
        "crash": 5, "restart": 6, "timeout": 7, "client": 8, "deliver": 9,
    }

    @classmethod
    def _event_order(cls, event: Event):
        return (cls._KIND_ORDER[event[0]], event[1:])

    def apply_event(self, event: Event) -> None:
        """Apply ONE event to the live state, then scan the invariants.
        Raises McViolation on a safety failure.  Pure function of
        (restored state, event) — the replay contract."""
        kind = event[0]
        cl = self.cluster
        # Invalidate BEFORE mutating: a McViolation can fire mid-event
        # (reply coherence inside dispatch), and the live-parts identity
        # map must never claim a half-mutated replica equals its part.
        if self._live_parts is not None:
            for i in self.touched_replicas(event):
                self._live_parts[i] = None
        if kind == "deliver":
            src, dst = (event[1], event[2]), (event[3], event[4])
            message = self.net.pop(src, dst)
            self._note_material(dst, message)
            cl.dispatch(src, dst, message)
        elif kind == "drop":
            self.budgets["drop"] -= 1
            self.net.pop((event[1], event[2]), (event[3], event[4]))
        elif kind == "timeout":
            self.budgets["timeout"] -= 1
            i = event[1]
            out = cl.replicas[i].mc_fire(event[2])
            cl._route(("replica", i), out)
        elif kind == "client":
            cid, target = event[1], event[2]
            message = self.clients[cid].build_send(target)
            self.net.send(("client", cid), ("replica", target), message,
                          cl.t)
        elif kind == "crash":
            self.budgets["crash"] -= 1
            i = event[1]
            cl.crash(i)
            self.watermark[i] = 0
            self.view_seen.pop(i, None)
            self.material.pop(i, None)
        elif kind == "restart":
            cl.restart(event[1])
        elif kind == "byz":
            self.budgets["byz"] -= 1
            self._apply_byz(event[1], event[2])
        elif kind == "byzp":
            self.budgets["byzp"] -= 1
            self._apply_byzp(event)
        elif kind == "partition":
            self.budgets["partition"] -= 1
            self.partition = event[1]
        elif kind == "heal":
            self.partition = None
        else:
            raise ValueError(f"unknown event {event!r}")
        # Every write durable at event granularity: crash-time torn
        # writes are the storage adversary's domain (VOPR), not this
        # scope's — and unsynced client-reply writes would otherwise
        # make the canonical hash order-dependent (pending lists differ
        # by which event last happened to fsync).
        for st in cl.storages:
            if st.pending:
                st.sync()
        self._scan_invariants()

    @staticmethod
    def touched_replicas(event: Event) -> Tuple[int, ...]:
        """Replica indices whose in-memory/storage state the event can
        mutate — every other replica's capsule part and canonical blob
        carry over unchanged (the incremental snapshot/hash fast path).
        Handlers only ever mutate their own replica (emissions go to the
        net, which lives in the always-recomputed tail)."""
        kind = event[0]
        if kind == "deliver" and event[3] == "replica":
            return (event[4],)
        if kind in ("timeout", "crash", "restart"):
            return (event[1],)
        return ()

    def _note_material(self, dst, message: bytes) -> None:
        # Only tracked while the forged-frame event is armed in the
        # SCOPE (never the live budget — behavior must not depend on the
        # budget value, or budget-dominance dedup would be unsound):
        # otherwise the capsule would distinguish states by which prepare
        # happened to arrive last — a canonical-hash dedup killer with no
        # behavioral meaning.
        if self.scope.byz_budget == 0:
            return
        if dst[0] != "replica" or len(message) <= wire.HEADER_SIZE:
            return
        try:
            h, command = wire.decode_header(message[: wire.HEADER_SIZE])
        except ValueError:
            return
        if command == wire.Command.prepare and wire.u128(h, "client"):
            self.material[dst[1]] = message

    def _apply_byz(self, i: int, victim: int) -> None:
        """One forged-frame injection from replica ``i``: an equivocated
        prepare (body flipped, checksums recomputed, the primary's origin
        header kept — fully valid on the wire) plus a forged commit
        heartbeat under ``i``'s own identity anchoring the forged
        checksum.  With defenses on, the prepare may journal but can
        never execute (no authentic anchor) and the forged commit is
        rejected by the primary-origin check; the ``not_primary`` and
        ``anchor_certify`` mutations each make one half bite."""
        message = self.material[i]
        h, _, body = wire.decode(message)
        evil_body = bytes([body[0] ^ 1]) + body[1:]
        evil = wire.encode(h.copy(), evil_body)
        evil_h, _ = wire.decode_header(evil)
        r = self.cluster.replicas[i]
        forged = wire.new_header(
            wire.Command.commit,
            cluster=self.cluster.cluster_id,
            view=r.view,
            commit=int(h["op"]),
            commit_checksum=wire.header_checksum(evil_h),
            checkpoint_op=0,
            timestamp_monotonic=0,
        )
        forged["replica"] = i
        self.net.send(("replica", i), ("replica", victim), evil,
                      self.cluster.t)
        self.net.send(("replica", i), ("replica", victim),
                      wire.encode(forged), self.cluster.t)

    # -- Byzantine-PRIMARY action set (scope.byzp_budget) ----------------------

    def _byzp_fork(self) -> Optional[Tuple[int, bytes]]:
        """The adversary's deterministic fork: its highest journaled
        client-carrying prepare, body's first byte flipped, checksums
        recomputed — fully wire-valid, and a prepare legitimately carries
        the preparing primary's origin (the seat the adversary holds).
        Pure function of the adversary's own capsule state, so the
        canonical hash needs no extra forged-material tracking."""
        b = self.scope.byzp_replica
        cl = self.cluster
        if not cl.alive[b]:
            return None
        r = cl.replicas[b]
        for op in sorted(r.headers, reverse=True):
            if not wire.u128(r.headers[op], "client"):
                continue
            read = Journal(cl.storages[b]).read_prepare(op)
            if read is None:
                continue
            hh, body = read
            if not body:
                continue
            evil = wire.encode(hh.copy(), bytes([body[0] ^ 1]) + body[1:])
            return op, evil
        return None

    def _apply_byzp(self, event: Event) -> None:
        """Inject ONE Byzantine-primary forged frame.  Every frame is
        constructible from the adversary's own key + journal (vsr/auth.py
        threat model): own-identity frames carry LEGAL MACs; frames
        claiming a peer identity (forge_ok with claim != adversary) carry
        the adversary's own-key MAC — accepted only under the
        ``mac_skip``/``key_confusion`` knockouts, never with defenses on."""
        sub, victim = event[1], event[-1]
        b = self.scope.byzp_replica
        cl = self.cluster
        r = cl.replicas[b]
        keychain = cl.auth_keychain
        op, evil = self._byzp_fork()
        evil_h, _ = wire.decode_header(evil)
        fork_checksum = wire.header_checksum(evil_h)

        def stamped(h, body=b""):
            frame = wire.encode(h, body)
            if keychain is None:
                return frame
            # Own key ALWAYS — the adversary holds no other; for claimed
            # peer identities this is exactly the key_confusion bait.
            return wire.stamp_mac(
                frame, keychain.mac(b, frame[: wire.HEADER_SIZE])
            )

        if sub == "equiv_prepare":
            # Conflicting prepare for an op the honest broadcast already
            # carries — prepares are relayed (never MAC'd), so this is
            # wire-legal as-is.
            frame = evil
        elif sub == "forge_ok":
            claim = event[2]
            ok = wire.new_header(
                wire.Command.prepare_ok,
                cluster=cl.cluster_id,
                view=r.view,
                parent=wire.u128(evil_h, "parent"),
                prepare_checksum=fork_checksum,
                client=wire.u128(evil_h, "client"),
                op=op,
                commit=r.commit_min,
                timestamp=int(evil_h["timestamp"]),
                request=int(evil_h["request"]),
                operation=int(evil_h["operation"]),
            )
            ok["replica"] = claim
            frame = stamped(ok)
        elif sub == "anchor_commit":
            # Fork-anchoring commit heartbeat under the adversary's OWN
            # identity — legal while it holds the primary seat of its
            # view; the cert_downgrade knockout's bait.
            forged = wire.new_header(
                wire.Command.commit,
                cluster=cl.cluster_id,
                view=r.view,
                commit=op,
                commit_checksum=fork_checksum,
                checkpoint_op=0,
                timestamp_monotonic=0,
            )
            forged["replica"] = b
            frame = stamped(forged)
        elif sub == "fork_headers":
            # Fork-serving repair response (the PR 6 gap's probe): a
            # single authenticated headers frame proposing the fork as a
            # repair target — certification must come from anchors, never
            # from the response alone.
            hdr = wire.new_header(wire.Command.headers,
                                  cluster=cl.cluster_id, view=r.view)
            hdr["replica"] = b
            frame = stamped(hdr, wire.pack_headers([evil_h]))
        elif sub == "fork_sv":
            # Equivocating start_view for the adversary's OWN view (the
            # only view whose SVs pass the primary-origin check), serving
            # the fork as the canonical head.
            sv = wire.new_header(
                wire.Command.start_view,
                cluster=cl.cluster_id,
                view=r.view,
                op=op,
                commit=r.commit_min,
                checkpoint_op=r.op_checkpoint,
            )
            sv["replica"] = b
            frame = stamped(sv, wire.pack_headers([evil_h]))
        elif sub == "forge_sync":
            # Forged sync summary under own identity: empty body — the
            # victim's structural gates must reject it without wedging.
            roots = wire.new_header(
                wire.Command.sync_roots,
                cluster=cl.cluster_id, view=r.view, checkpoint_op=op,
            )
            roots["replica"] = b
            frame = stamped(roots)
        else:
            raise ValueError(f"unknown byzp subkind {sub!r}")
        self.net.send(("replica", b), ("replica", victim), frame,
                      self.cluster.t)

    # -- invariants -----------------------------------------------------------

    def on_reply_accepted(self, cid: int, h: np.ndarray) -> None:
        if not self.checking:
            return
        op = int(h["op"])
        rec = self.canon.get(op)
        if rec is None:
            raise McViolation(
                "reply_unbacked",
                f"client {cid:#x} accepted a reply for op {op} that no "
                "replica ever committed",
            )
        _checksum, client, request = rec
        if client != cid or request != int(h["request"]):
            raise McViolation(
                "reply_mismatch",
                f"reply for op {op} claims (client {cid:#x}, request "
                f"{int(h['request'])}) but op {op} committed (client "
                f"{client:#x}, request {request})",
            )

    def _scan_invariants(self) -> None:
        if not self.checking:
            return
        cl = self.cluster
        q_replication = quorums(cl.n)[0]
        fresh: List[Tuple[int, int, int, bool]] = []
        for i in range(cl.total):
            if not cl.alive[i]:
                continue
            r = cl.replicas[i]
            for op in range(self.watermark[i] + 1, r.commit_min + 1):
                h = r.headers.get(op)
                if h is None:
                    continue  # pruned below a checkpoint (out of scope)
                checksum = wire.header_checksum(h)
                prev = self.canon.get(op)
                if prev is not None and prev[0] != checksum:
                    raise McViolation(
                        "agreement",
                        f"replica {i} committed {checksum:#x} at op {op}; "
                        f"the cluster previously committed {prev[0]:#x} "
                        "there",
                    )
                self.canon.setdefault(op, (
                    checksum, wire.u128(h, "client"), int(h["request"]),
                ))
                own = self.committed[i].get(op)
                if own is not None and own != checksum:
                    raise McViolation(
                        "replay_divergence",
                        f"replica {i} re-committed op {op} as "
                        f"{checksum:#x} after recording {own:#x}",
                    )
                self.committed[i][op] = checksum
                fresh.append((i, op, checksum, r.is_primary))
            self.watermark[i] = r.commit_min
            v = r.view
            prev_view = self.view_seen.get(i)
            if prev_view is not None and v < prev_view:
                raise McViolation(
                    "view_regress",
                    f"replica {i} regressed view {prev_view} -> {v}",
                )
            self.view_seen[i] = v
        for (i, op, checksum, was_primary) in fresh:
            holders = 0
            for k in range(cl.total):
                read = Journal(cl.storages[k]).read_prepare(op)
                if read is not None and (
                    wire.header_checksum(read[0]) == checksum
                ):
                    holders += 1
            if holders < q_replication:
                raise McViolation(
                    "quorum_journal",
                    f"op {op} committed by replica {i} but its prepare "
                    f"{checksum:#x} is journaled on only {holders} < "
                    f"{q_replication} replicas",
                )
            r = cl.replicas[i]
            if (
                not was_primary and r is not None and r.status == NORMAL
                and r.replica_count > 1 and r.ingress_verify
                and not self._anchored(r, op, checksum)
            ):
                raise McViolation(
                    "certified_commit",
                    f"backup {i} executed op {op} ({checksum:#x}) without "
                    "a source-authenticated anchor chain",
                )

    def _anchored(self, r, op: int, checksum: int) -> bool:
        """Independent re-verification of the certified-commit walk: some
        anchor at a >= op must match its header and parent-chain down to
        exactly ``checksum`` at ``op``."""
        for a in sorted(o for o in r._anchors if o >= op):
            h = r.headers.get(a)
            if h is None or wire.header_checksum(h) != r._anchors[a]:
                continue
            k, ok = a, True
            while k > op:
                below = r.headers.get(k - 1)
                if below is None or wire.header_checksum(below) != (
                    wire.u128(r.headers[k], "parent")
                ):
                    ok = False
                    break
                k -= 1
            if ok and wire.header_checksum(r.headers[op]) == checksum:
                return True
        return False

    # -- capsule + canonical hash ---------------------------------------------

    def _replica_part(self, i: int) -> dict:
        """Replica ``i``'s slice of the cluster capsule.  Parts are
        treated as IMMUTABLE once taken (restore deep-copies on the way
        in), so untouched parts are shared by reference across the
        explorer's frames — the incremental-snapshot fast path."""
        cl = self.cluster
        st = cl.storages[i]
        return {
            "alive": cl.alive[i],
            "replica": cl.replicas[i].snapshot() if cl.alive[i] else None,
            "buf": bytes(st.buf),
            "pending": [(o, b) for o, b in st.pending],
            "rng": st.rng.getstate(),
        }

    def snapshot(self, parent: Optional[dict] = None,
                 touched: Tuple[int, ...] = ()) -> dict:
        """Full capsule, or — given the ``parent`` capsule this state was
        reached from and the event's touched replicas — an incremental
        one sharing every untouched replica part by reference."""
        cl = self.cluster
        if parent is None:
            parts = [self._replica_part(i) for i in range(cl.total)]
        else:
            parts = list(parent["parts"])
            for i in touched:
                parts[i] = self._replica_part(i)
        self._live_parts = list(parts)
        return {
            "t": cl.t,
            "parts": parts,
            "net": self.net.snapshot(),
            "clients": {cid: c.snapshot() for cid, c in self.clients.items()},
            "budgets": dict(self.budgets),
            "partition": self.partition,
            "material": dict(self.material),
            "canon": dict(self.canon),
            "committed": {i: dict(m) for i, m in self.committed.items()},
            "watermark": dict(self.watermark),
            "view_seen": dict(self.view_seen),
        }

    def restore(self, cap: dict) -> None:
        cl = self.cluster
        cl.t = cap["t"]
        live = self._live_parts
        for i in range(cl.total):
            part = cap["parts"][i]
            if live is not None and live[i] is part:
                continue  # live state already equals this part (identity)
            st = cl.storages[i]
            st.buf[:] = part["buf"]
            st.pending = list(part["pending"])
            st.rng.setstate(part["rng"])
            if part["alive"]:
                if cl.replicas[i] is None:
                    cl.replicas[i] = cl._make_replica(i)
                cl.replicas[i].restore(part["replica"])
                cl.alive[i] = True
            else:
                cl.replicas[i] = None
                cl.alive[i] = False
        self._live_parts = list(cap["parts"])
        self.net.restore(cap["net"])
        for cid, c in self.clients.items():
            c.restore(cap["clients"][cid])
        self.budgets = dict(cap["budgets"])
        self.partition = cap["partition"]
        self.material = dict(cap["material"])
        self.canon = dict(cap["canon"])
        self.committed = {i: dict(m) for i, m in cap["committed"].items()}
        self.watermark = dict(cap["watermark"])
        self.view_seen = dict(cap["view_seen"])

    def canon_blob(self, i: int) -> bytes:
        """Replica ``i``'s canonical-state digest: protocol capsule fields
        (time/retry/prng groups excluded — see module docstring) plus the
        storage image."""
        cl = self.cluster
        h = hashlib.blake2b(digest_size=16)
        h.update(b"1" if cl.alive[i] else b"0")
        if cl.alive[i]:
            _enc(h.update, self._replica_canonical(cl.replicas[i]))
        h.update(bytes(cl.storages[i].buf))
        _enc(h.update, cl.storages[i].pending)
        return h.digest()

    def canonical_key(self, parts: Optional[List[bytes]] = None) -> bytes:
        """Canonical state hash: symmetric interleavings reaching the
        same protocol state collapse.  ``parts`` (from canon_parts /
        updated incrementally by the explorer) skips re-encoding
        untouched replicas."""
        if parts is None:
            parts = self.canon_parts()
        h = hashlib.blake2b(digest_size=20)
        for i, blob in enumerate(parts):
            h.update(b"R%d" % i)
            h.update(blob)
        _enc(h.update, {
            "net": {k: v for k, v in self.net.links.items()},
            "clients": {c: self.clients[c].snapshot()
                        for c in sorted(self.clients)},
            "partition": self.partition,
            "material": self.material,
            "canon": self.canon,
            "committed": self.committed,
            "watermark": self.watermark,
            "view_seen": self.view_seen,
        })
        return h.digest()

    def canon_parts(self) -> List[bytes]:
        return [self.canon_blob(i) for i in range(self.cluster.total)]

    _BUDGET_ORDER = ("byz", "byzp", "crash", "drop", "partition", "timeout")

    def budget_vector(self) -> Tuple[int, ...]:
        """Remaining budgets, fixed order.  Kept OUT of canonical_key:
        the explorer dedups by dominance instead — a revisit with
        pointwise-less fuel (and less remaining depth) can only reach a
        subset of what the recorded visit already covered."""
        return tuple(self.budgets[k] for k in self._BUDGET_ORDER)

    @staticmethod
    def _replica_canonical(r) -> dict:
        scalars = {k: getattr(r, k, None) for k in r._MC_SCALARS}
        scalars["_repair_rotation"] = (
            (scalars.get("_repair_rotation") or 0)
            % max(1, r.replica_count - 1)
        )
        out = {
            "scalars": scalars,
            "containers": {
                k: getattr(r, k, None) for k in r._MC_CONTAINERS
            },
            "sync_buffer": bytes(r.sync_buffer),
            "machine": (
                r.machine.digest(), r.machine.prepare_timestamp,
                r.machine.commit_timestamp,
            ),
        }
        if r.clock is not None:
            out["clock"] = (
                sorted(r.clock.samples.items()), r.clock.offset_ns,
                r.clock._synchronized,
            )
        return out

    # -- POR independence ------------------------------------------------------

    @staticmethod
    def _agent(event: Event):
        kind = event[0]
        if kind in ("deliver", "drop"):
            if event[3] == "replica":
                return ("replica", event[4])
            return ("clientstate", event[4])
        if kind in ("timeout", "crash", "restart", "byz"):
            return ("replica", event[1])
        if kind == "client":
            return ("clientstate", event[1])
        return ("net",)

    _BUDGET_OF = {"drop": "drop", "timeout": "timeout", "crash": "crash",
                  "byz": "byz", "byzp": "byzp", "partition": "partition"}

    @staticmethod
    def _link_src(event):
        """The source process of the link a deliver/drop pops from."""
        if event[0] in ("deliver", "drop"):
            return (event[1], event[2])
        return None

    @staticmethod
    def _emitter(event):
        """The process whose OUTGOING links the event can append to (its
        handler emits frames).  Needed because FifoNet coalescing makes
        append-tail NOT commute with pop-head on the same link: whether
        an emitted frame is absorbed depends on whether its byte-twin is
        still queued — which popping that link changes."""
        kind = event[0]
        if kind == "deliver" and event[3] == "replica":
            return ("replica", event[4])
        if kind in ("timeout", "restart", "byz"):
            return ("replica", event[1])
        if kind == "client":
            return ("client", event[1])
        return None

    @classmethod
    def independent(cls, a: Event, b: Event, budgets: Dict[str, int]) -> bool:
        """Conservative Mazurkiewicz independence: disjoint touched
        agents, no contended budget, and no emit-into-a-link vs
        pop-that-link pair (coalescing, see _emitter).  Partition toggles
        conflict with everything (they flip global deliverability)."""
        if a[0] in ("partition", "heal") or b[0] in ("partition", "heal"):
            return False
        if a[0] == "byzp" or b[0] == "byzp":
            # The forged frame is DERIVED from the adversary's live state
            # (journal head) and lands on a link any deliver can pop —
            # conservatively dependent with everything.
            return False
        if cls._agent(a) == cls._agent(b):
            return False
        la, lb = cls._link_src(a), cls._link_src(b)
        if la is not None and la == cls._emitter(b):
            return False
        if lb is not None and lb == cls._emitter(a):
            return False
        key = cls._BUDGET_OF.get(a[0])
        if key is not None and key == cls._BUDGET_OF.get(b[0]) and (
            budgets.get(key, 0) < 2
        ):
            return False
        return True


# -- the explorer -------------------------------------------------------------


@dataclasses.dataclass
class McReport:
    scope: McScope
    mutations: Tuple[str, ...]
    exhaustive: bool = False
    states: int = 0
    deduped: int = 0
    por_pruned: int = 0
    bound_pruned: int = 0
    stack_peak: int = 0
    elapsed_s: float = 0.0
    violation: Optional[dict] = None
    schedule: Optional[List[Event]] = None
    state_key: Optional[str] = None

    def counterexample(self) -> dict:
        """The replayable JSON counterexample (docs/tbmc.md)."""
        assert self.violation is not None and self.schedule is not None
        return {
            "version": 1,
            "scope": self.scope.to_json(),
            "mutations": list(self.mutations),
            "schedule": [list(e) for e in self.schedule],
            "violation": self.violation,
            "state_key": self.state_key,
        }


class ModelChecker:
    """DFS with sleep-set POR, canonical-state dedup, and scope bounds
    over McCluster.  Stops at the first violation (first down the
    deterministic fault-first exploration order) or runs the scope
    exhaustively.

    ``prefix``: an optional pinned event schedule applied after
    bootstrap; exploration is then exhaustive FROM that reachable state
    (a guided hunt: deep scenarios whose interesting branching starts
    late pin the deterministic part and explore the rest).  The
    counterexample schedule includes the prefix, so replay stays
    end-to-end; the passes/fails discipline requires running the
    unmutated control with the SAME prefix and scope."""

    def __init__(self, scope: McScope, mutations: Tuple[str, ...] = (),
                 prefix: Tuple[Event, ...] = (), por: bool = True) -> None:
        self.scope = scope
        self.mutations = tuple(mutations)
        self.prefix = tuple(tuple(e) for e in prefix)
        # ``por=False`` disables the sleep-set reduction (dedup stays):
        # the soundness spot-check in tests/test_mc.py runs small scopes
        # both ways and asserts identical clean/violation verdicts.
        self.por = por

    def run(self, workdir: Optional[str] = None) -> McReport:
        if workdir is None:
            with tempfile.TemporaryDirectory() as d:
                return self._run(d)
        return self._run(workdir)

    def _run(self, workdir: str) -> McReport:
        t0 = time.monotonic()  # tblint: ignore[nondet] wall report only
        scope = self.scope
        report = McReport(scope=scope, mutations=self.mutations)
        harness = McCluster(scope, workdir, self.mutations)
        harness.bootstrap()
        for k, event in enumerate(self.prefix):
            try:
                harness.apply_event(event)
            except McViolation as violation:
                report.states = k + 1
                report.violation = {
                    "kind": violation.kind,
                    "detail": violation.detail,
                }
                report.schedule = list(self.prefix[: k + 1])
                report.state_key = harness.canonical_key().hex()
                report.elapsed_s = round(
                    time.monotonic() - t0,  # tblint: ignore[nondet] wall
                    3,
                )
                return report
        root_parts = harness.canon_parts()
        root_key = harness.canonical_key(root_parts)
        # visited: canonical key -> (budget vector, remaining depth,
        # sleep set) triples already fully explored.  A revisit is
        # skippable only under DOMINANCE: some recorded visit had at
        # least as much of every budget, at least as much remaining
        # depth, and a sleep set that is a subset of ours (so it explored
        # a superset of our events) — everything reachable from here was
        # reachable there.
        visited: Dict[bytes, List[Tuple]] = {
            root_key: [(harness.budget_vector(), scope.depth_max,
                        frozenset())]
        }
        root = {
            "capsule": harness.snapshot(),
            "parts": root_parts,
            "depth": 0,
            "sleep": frozenset(),
            "events": harness.enabled_events(),
            "idx": 0,
            "explored": [],
            "via": None,
        }
        stack = [root]
        capped = False
        while stack:
            frame = stack[-1]
            if frame["idx"] >= len(frame["events"]):
                stack.pop()
                continue
            event = frame["events"][frame["idx"]]
            frame["idx"] += 1
            if event in frame["sleep"]:
                report.por_pruned += 1
                continue
            if report.states >= scope.max_states:
                capped = True
                break
            harness.restore(frame["capsule"])
            parent_budgets = dict(harness.budgets)
            try:
                harness.apply_event(event)
            except McViolation as violation:
                report.states += 1
                report.violation = {
                    "kind": violation.kind,
                    "detail": violation.detail,
                }
                report.schedule = list(self.prefix) + [
                    f["via"] for f in stack if f["via"] is not None
                ] + [event]
                report.state_key = harness.canonical_key().hex()
                break
            report.states += 1
            child_sleep = frozenset(
                z for z in frame["sleep"] | set(frame["explored"])
                if McCluster.independent(z, event, parent_budgets)
            ) if self.por else frozenset()
            frame["explored"].append(event)
            over_view = any(
                a and r.view > scope.max_view
                for r, a in zip(harness.cluster.replicas,
                                harness.cluster.alive)
            )
            if over_view or frame["depth"] + 1 >= scope.depth_max:
                report.bound_pruned += 1
                continue
            # Incremental canonical hash: only the event's touched
            # replicas re-encode; every other per-replica blob carries
            # over from the parent frame (touched_replicas contract).
            touched = McCluster.touched_replicas(event)
            child_parts = list(frame["parts"])
            for i in touched:
                child_parts[i] = harness.canon_blob(i)
            key = harness.canonical_key(child_parts)
            child_budget = harness.budget_vector()
            remaining = scope.depth_max - (frame["depth"] + 1)
            recorded = visited.get(key)
            if recorded is not None and any(
                all(rb >= cb for rb, cb in zip(b, child_budget))
                and d >= remaining and z <= child_sleep
                for (b, d, z) in recorded
            ):
                report.deduped += 1
                continue
            visited.setdefault(key, []).append(
                (child_budget, remaining, child_sleep)
            )
            stack.append({
                "capsule": harness.snapshot(frame["capsule"], touched),
                "parts": child_parts,
                "depth": frame["depth"] + 1,
                "sleep": child_sleep,
                "events": harness.enabled_events(),
                "idx": 0,
                "explored": [],
                "via": event,
            })
            report.stack_peak = max(report.stack_peak, len(stack))
        report.exhaustive = (
            report.violation is None and not capped
        )
        report.elapsed_s = round(
            time.monotonic() - t0, 3  # tblint: ignore[nondet] wall report only
        )
        if _obs.enabled:
            _obs.counter("mc.states_explored").inc(report.states)
            _obs.counter("mc.deduped").inc(report.deduped)
            _obs.counter("mc.por_pruned").inc(report.por_pruned)
            _obs.counter("mc.bound_pruned").inc(report.bound_pruned)
            _obs.gauge("mc.frontier_peak").set(report.stack_peak)
            if report.violation is not None:
                _obs.counter("mc.violations").inc()
        return report


def check(scope: McScope, mutations: Tuple[str, ...] = (),
          workdir: Optional[str] = None,
          prefix: Tuple[Event, ...] = ()) -> McReport:
    """One-call entry: explore ``scope`` (optionally mutated),
    exhaustively from the state the pinned ``prefix`` schedule reaches
    (``depth_max`` bounds the explored suffix, not the prefix)."""
    return ModelChecker(scope, mutations, prefix).run(workdir)


# -- counterexample replay -----------------------------------------------------


def replay_schedule(source) -> dict:
    """Re-execute a counterexample schedule bit-identically.

    ``source``: a path to a counterexample JSON file or the dict itself.
    Rebuilds the exact scope + mutations, replays the event schedule
    through the same ``apply_event`` path the explorer used, and compares
    the reproduced violation and canonical state key against the
    recording.  Returns a result dict with ``reproduced`` (the recorded
    violation fired at the recorded step) and ``identical`` (…and the
    canonical state key matches bit-for-bit)."""
    if isinstance(source, (str, bytes)):
        with open(source) as f:
            data = json.load(f)
    else:
        data = source
    scope = McScope.from_json(data["scope"])
    mutations = tuple(data.get("mutations", ()))
    expected = data.get("violation")
    violation = None
    error = None
    with tempfile.TemporaryDirectory() as workdir:
        harness = McCluster(scope, workdir, mutations)
        harness.bootstrap()
        for step, raw in enumerate(data["schedule"]):
            event = tuple(raw)
            try:
                harness.apply_event(event)
            except McViolation as v:
                violation = {"kind": v.kind, "detail": v.detail}
                if step != len(data["schedule"]) - 1:
                    error = (
                        f"violation fired early at step {step + 1} of "
                        f"{len(data['schedule'])}"
                    )
                break
            except Exception as err:  # noqa: BLE001 — schedule drift IS the finding
                error = f"{type(err).__name__}: {err}"
                break
        state_key = harness.canonical_key().hex()
        # Flight-recorder history of the replayed schedule (the CLI writes
        # one postmortem file per seat next to its JSON verdict).
        blackboxes = {
            box.name: box.dump_text()
            for box in harness.cluster.blackboxes
        }
    reproduced = error is None and violation == expected
    identical = reproduced and state_key == data.get("state_key")
    return {
        "blackboxes": blackboxes,
        "reproduced": reproduced,
        "identical": identical,
        "violation": violation,
        "expected": expected,
        "state_key": state_key,
        "expected_state_key": data.get("state_key"),
        "error": error,
        "steps": len(data["schedule"]),
    }
