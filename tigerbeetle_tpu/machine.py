"""TpuStateMachine: the host-side seam mirroring the reference's StateMachine.

The reference makes the application state machine pluggable behind
``StateMachineType(comptime Storage, comptime config)`` (state_machine.zig:34),
with the contract prepare()/prefetch()/commit() driven by the replica
(replica.zig:3102-3173 commit dispatch).  This class is the TPU-native
implementation of that seam: it owns the device-resident ledger, assigns batch
timestamps like prepare() does (state_machine.zig:503-512), dispatches each
batch to the widest safe device kernel, and compresses dense device result
codes into the wire's (index, result) pairs (only failures are emitted —
state_machine.zig:1051-1073).

Dispatch policy (round 2):
- create_accounts: vectorized kernel, unless the batch combines linked chains
  with intra-batch duplicate ids -> sequential path.
- create_transfers: ALWAYS dispatched to the full vectorized kernel
  (ops/transfer_full.py), which covers pending/post/void two-phase flows,
  intra-batch references, history, and exact overflow checks.  The kernel
  itself decides routing: it returns a flags word, nonzero meaning "nothing
  applied" — either a table must grow (host grows + retries) or the batch is
  genuinely order-dependent (balancing flags, balance-limit accounts, u128
  amounts, deep intra-batch chains) and re-routes to the sequential path.
  There is NO host-side global precondition state: one history/limit account
  in the ledger no longer affects batches that do not reference it
  (VERDICT.md round-1 Weak #3).

The sequential path (ops/scan_path.py) runs the full semantics on device as a
lax.scan and is bit-identical but latency-bound.
"""

from __future__ import annotations

import random as _random
import time as _time
import warnings

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import sanitize as _san
from . import types
from .config import LedgerConfig
from .obs.metrics import registry as _obs
from .obs.txtrace import txtrace
from .ops import index as index_ops
from .ops import merkle as merkle_ops
from .ops import scrub as scrub_ops
from .ops import staging
from .ops import state_machine as sm
from .ops.scrub import (  # re-exported: the replica's fault-domain surface
    DEVICE_FAULT_TYPES, DeviceStateUnrecoverable, SimulatedDeviceFault,
)

_LIMIT_FLAGS = (
    types.AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
    | types.AccountFlags.CREDITS_MUST_NOT_EXCEED_DEBITS
)
# Balance-bound saturation point: past this the fast path stays off and
# further tracking is pointless (and giant Python ints are avoided).
_BOUND_CLAMP = 1 << 127
# Transfer flags that exclude the plain fast-path kernel (P2/P4: two-phase,
# balancing, and linked chains run the fully-general kernel).
_SLOW_TRANSFER_FLAGS = (
    types.TransferFlags.POST_PENDING_TRANSFER
    | types.TransferFlags.VOID_PENDING_TRANSFER
    | types.TransferFlags.BALANCING_DEBIT
    | types.TransferFlags.BALANCING_CREDIT
    | types.TransferFlags.LINKED
)

U64_MAX = (1 << 64) - 1
# Reply rows are 128 B; one 1 MiB message body holds at most this many
# (constants.zig:203-204, state_machine.zig:70-75).
QUERY_ROWS_MAX = ((1 << 20) - 256) // 128


def _group_fast_dispatch_impl(ledger, cols64, cols32, meta):
    """Run the fast commit kernel over the leading batches of a staged
    stack (``staging.stage_group``: ``uint64[rows, 14, lanes]``,
    ``uint32[rows, 5, lanes]``, ``meta = uint64[2, rows]``), one loop step
    per batch the group HOLDS: one device dispatch, batch order preserved,
    ledger threaded through the carry (see
    TpuStateMachine.commit_group_fast).

    The trip count is a run-time value: the loop ends at the first zero
    of the counts (a group's batches lead the stack and none is empty) or
    at ``rows``, so every group length that fits a stack runs the ONE
    program that stack's shapes compile to.  On the chip a step over an
    empty batch costs most of what a full one does (its gathers and
    scatters run over every lane whatever the count, dropped or not: one
    TPU v5 lite, PERF.md section 5), so the rows past the group are not
    run at all: their codes stay the zeros the buffer starts with and are
    never read.

    Besides (ledger, codes) it returns the transfers probe_overflow flag
    widened into a FRESH uint32 buffer (the deferred readback handle must
    be able to fetch it after a later dispatch donates the ledger; riding
    the commit dispatch it costs zero extra syncs) and what the dispatch
    closure's index maintenance needs, so it never slices a staged operand
    on the host and never reads the table back: the stacked id columns
    and, per batch, ``sm.index_keys`` (the stacked account columns and the
    timestamps each trip stored) and ``sm.written_lanes`` (all False for
    the rows past the group).  The staged operands are NOT donated (the
    sharded steps' rule, ``ops/staging.py``)."""
    steps_max = meta.shape[1]
    counts, timestamps = meta[0], meta[1]

    def row(i):
        return staging.unstage(
            types.TRANSFER_DTYPE,
            jax.lax.dynamic_index_in_dim(cols64, i, keepdims=False),
            jax.lax.dynamic_index_in_dim(cols32, i, keepdims=False),
            jax.lax.dynamic_index_in_dim(meta, i, axis=1, keepdims=False),
        )

    def holds_a_batch(carry):
        i = carry[0]
        # counts[i] clamps at the last row when i == steps_max.
        return (i < steps_max) & (counts[i] != 0)

    def step(carry):
        i, led, codes = carry
        with jax.named_scope("tb/group_step"):
            led, row_codes = sm.create_transfers_impl(led, *row(i))
        return i + 1, led, jax.lax.dynamic_update_index_in_dim(
            codes, row_codes, i, 0
        )

    # Result codes are uint32 lanes (a step of another dtype fails the
    # trace at the update below).
    codes = jnp.zeros((steps_max, cols64.shape[2]), jnp.uint32)
    _, ledger, codes = jax.lax.while_loop(
        holds_a_batch, step, (jnp.int32(0), ledger, codes)
    )
    # The whole stack by column name: [rows, lanes] each.
    stacked, _, _ = staging.unstage(
        types.TRANSFER_DTYPE, jnp.moveaxis(cols64, 1, 0),
        jnp.moveaxis(cols32, 1, 0), meta,
    )
    return (
        ledger, codes, ledger.transfers.probe_overflow.astype(jnp.uint32),
        stacked["id_lo"], stacked["id_hi"],
        jax.vmap(sm.index_keys)(stacked, counts, timestamps),
        jax.vmap(sm.written_lanes)(codes, counts),
    )


_group_fast_dispatch = jax.jit(
    _group_fast_dispatch_impl, donate_argnames=("ledger",)
)


def _overflow_any(overflow) -> bool:
    """True if any probe-overflow flag fired.  Accepts the scalar the
    single-device kernels return, the per-shard uint32 lane vector the
    sharded probed step returns, or a tuple of either (one per batch of a
    sharded grouped run)."""
    if isinstance(overflow, (list, tuple)):
        return any(_overflow_any(o) for o in overflow)
    return bool(np.any(np.asarray(overflow)))


def pipeline_depth_default() -> int:
    """Commit-pipeline depth (TB_PIPELINE env; default 2).  Depth 1 (and
    TB_PIPELINE=0, "off") disables deferral entirely — the serving path is
    then bit-for-bit the pre-pipeline blocking path.  Depth >= 2 runs the
    pipelined engine with ONE commit group in flight; deeper values are
    reserved (currently equivalent to 2)."""
    import os

    env = os.environ.get("TB_PIPELINE", "")
    if env.isdigit():
        return max(1, int(env))  # 0 == off == depth 1
    return 2


class DeviceCommitHandle:
    """An in-flight fast-path device commit (one batch or a grouped run).

    ``result`` is either the dispatch's (codes, overflow, id_lo, id_hi)
    device tuple (the dispatch already executed on the calling thread) or
    a Future of one — deferred dispatches run on the machine's single
    dispatch-lane thread, which restores the async-dispatch property on
    backends whose execute blocks the calling thread (XLA-CPU): the
    serving thread stages uploads, journals, and builds replies while the
    lane thread sits in the (GIL-free) device execute.

    ``resolve()`` joins the dispatch, performs the ONE deferred
    device->host readback (result codes + the probe-overflow flag ride
    together), and runs the host bookkeeping that needs the codes —
    result compression and commit-timestamp advance — returning per-batch
    (index, result) lists.  ``join_wait_s`` records how long the join
    blocked (queue wait, not commit work — callers keep it out of the
    commit-stage latency series).

    Handles must be resolved in dispatch order (the commit timestamp and
    index appends are op-ordered); the replica's pipelined commit engine
    enforces that with a FIFO in-flight queue (at most one commit group's
    runs deep).
    """

    __slots__ = ("_machine", "_result", "_stacked", "_counts",
                 "_timestamps", "_resolved", "join_wait_s",
                 "_batches", "_recovered", "_deferred", "_seq")

    def __init__(self, machine, result, counts, timestamps,
                 stacked: bool, batches=None,
                 deferred: bool = False) -> None:
        self._machine = machine
        self._result = result        # (codes, overflow) | Future of one
        self._stacked = stacked      # True: leading per-batch dim
        self._counts = counts
        self._timestamps = timestamps
        self._resolved = False
        self._deferred = deferred    # counted in the machine's in-flight depth
        # The bus's group this run belongs to: resolve() runs inside a
        # LATER group's call, and its spans name this one.
        self._seq = txtrace.group_seq
        self.join_wait_s = 0.0
        # Host-side copies of the dispatched batches: the device fault
        # domain re-dispatches a quarantined run from these after a failed
        # dispatch (machine._recover_inflight); None when the fault domain
        # is off (no retention cost).
        self._batches = batches
        # Per-batch results computed by a recovery re-dispatch; resolve()
        # returns them instead of touching the dead device future.
        self._recovered = None

    def __len__(self) -> int:
        return len(self._counts)

    def discard(self) -> None:
        """Abort path: QUIESCE the dispatch (join it, swallow its error) —
        an orphaned closure left running on the lane would keep mutating
        machine.ledger concurrently with the serving thread after the
        caller dropped this handle."""
        if self._resolved:
            return
        self._resolved = True
        self._machine._deferred_done(self)
        self._machine._inflight_untrack(self)
        if hasattr(self._result, "result"):
            try:
                # The group's failure already propagated via the engine;
                # this join only quiesces the lane.
                self._result.result()
            except BaseException:  # tblint: ignore[swallow] abort quiesce
                pass

    def resolve(self) -> List[List[Tuple[int, int]]]:
        assert not self._resolved, "commit handle resolved twice"
        self._resolved = True
        m = self._machine
        m._deferred_done(self)
        if self._recovered is not None:
            # A device-fault recovery already re-committed this run through
            # the blocking path (machine._recover_inflight): bookkeeping,
            # index appends and mirror application all happened there.
            return self._recovered
        try:
            if hasattr(self._result, "result"):
                t0 = _time.perf_counter()
                # The join of the lane closure: the serving thread waits
                # for the lane thread (its queue and its closure's run).
                with txtrace.stage("dispatch_wait", seq=self._seq):
                    self._result = self._result.result()
                self.join_wait_s = _time.perf_counter() - t0
            codes_dev, overflow_dev = self._result
            codes, overflow = m._d2h_codes(codes_dev, overflow_dev,
                                           stage="readback", seq=self._seq)
        except DEVICE_FAULT_TYPES as err:
            # Dispatch-lane funnel: the dispatch (or its readback) failed —
            # quarantine the in-flight pipeline and re-dispatch every
            # pending run from the authoritative mirror (docs/
            # fault_domains.md).  Raises the original error when the fault
            # domain is disarmed (pre-fault-domain behavior).
            m._device_fault_at_resolve(err)
            assert self._recovered is not None
            return self._recovered
        finally:
            m._inflight_untrack(self)
        if _overflow_any(overflow):
            # Load-factor management keeps this unreachable; losing inserts
            # silently is the one unacceptable outcome, so fail loud (the
            # deferred check fires one resolve later than the blocking
            # path's, but always before any reply is released).
            raise RuntimeError("transfers probe overflow during fast insert")
        if _obs.enabled:
            _obs.counter("pipeline.resolves").inc()
            if m.shards:
                _obs.counter("pipeline.shard.resolves").inc()
        # NOTE: index maintenance already happened inside the dispatch
        # closure (machine._index_append_device) — it is device work that
        # must ride the ledger chain; reading self.ledger HERE could see
        # buffers a later in-flight dispatch already donated.
        out = []
        for j, (count, ts) in enumerate(zip(self._counts, self._timestamps)):
            row = codes[j] if self._stacked else codes
            out.append(m._compress(row, count))
            m._update_commit_timestamp(row, count, ts)
        m._device_fault_streak = 0
        if m.scrub_armed:
            # Advance the scrub cadence in resolve (== op) order.  The
            # merkle forest already advanced INSIDE the dispatch closure
            # (device work must ride the ledger chain); only the mirror
            # replay belongs here.
            m._scrub_commits += len(self._counts)
        if m._scrub_mirror is not None and self._batches is not None:
            # Advance the authoritative mirror in resolve (== op) order;
            # the digest folds at the next scrub point compare against it.
            for b, ts in zip(self._batches, self._timestamps):
                m._mirror_apply("create_transfers", b, ts)
        return out


class TpuStateMachine:
    def __init__(
        self,
        ledger_config: Optional[LedgerConfig] = None,
        batch_lanes: int = 8192,
        force_sequential: bool = False,
        spill_dir: Optional[str] = None,
        hot_transfers_capacity_max: Optional[int] = None,
        host_engine: bool = False,
        shards: Optional[int] = None,
    ) -> None:
        cfg = ledger_config or LedgerConfig()
        self.config = cfg
        self.batch_lanes = batch_lanes
        self.force_sequential = force_sequential
        # Sharded execution mode (docs/sharding.md): the pad SoA lives
        # under a Mesh + NamedSharding(PartitionSpec('shard')) over the
        # account axis and commits dispatch through shard_map
        # (parallel/sharded.py).  ``shards`` None defers to TB_SHARDS; 0 is
        # today's single-device path, bit-identical by construction (not
        # one sharded branch is taken).
        if shards is None:
            import os

            env = os.environ.get("TB_SHARDS", "")
            shards = int(env) if env.isdigit() else 0
        self.shards = 0
        self._shard_mesh = None
        self._shard_steps = None
        self._canon = None            # cached canonical (single-layout) view
        self._ledger_is_sharded = False
        self.shard_lanes_total = 0    # plain-int counters (tests)
        self.shard_lanes_cross = 0
        self.shard_seq_fallbacks = 0
        # Per-shard attempted-insert bounds (accounts/transfers): the
        # global load<=0.5 policy no longer bounds a SHARD's load — hash
        # skew can overfill one cap/n local region while the global count
        # sits under cap/2, and a fast-path probe overflow there is fatal
        # (rows already dropped).  Owners are host-computable (one mix64
        # pass per batch), so growth sizes off the peak shard too.
        self._shard_insert_bounds: dict = {}
        # Online shard split (docs/reconfiguration.md): volatile migration
        # state (None = no split in flight).  Deliberately NOT part of any
        # checkpoint — a crash mid-migration rolls back to serving the old
        # layout and the operator (or VOPR's reconfig fault kind) re-arms.
        self._reshard: Optional[dict] = None
        self.reshard_stats = {
            "splits_started": 0, "splits_completed": 0, "abandons": 0,
            "restarts": 0, "catchup_rounds": 0, "chunks": 0,
            "chunk_retries": 0, "bytes_migrated": 0, "bytes_full": 0,
        }
        if shards >= 2 and host_engine:
            # Sharding runs on the device path.  A process-wide TB_SHARDS
            # env must not take down a host-engine solo server: degrade to
            # the proven single-device path loudly (the
            # DEGRADED_DEVICE_COUNT discipline).  Cold tiering now
            # COMPOSES with sharding (PR 20): the mesh kernels still have
            # no bloom, so tiered transfer commits route through the
            # sequential fallback's canonical window, where the existing
            # host-exact cold resolution applies unchanged.
            warnings.warn(
                f"TB_SHARDS={shards} ignored: "
                "the host engine is the commit authority here",
                RuntimeWarning, stacklevel=2,
            )
            shards = 0
        if shards >= 2:
            assert shards & (shards - 1) == 0, "TB_SHARDS must be a power of 2"
            devs = jax.devices()
            if len(devs) < shards:
                # Asked-for shards that cannot be had are an error: serving
                # single-device instead would be a different deployment
                # than the operator configured.
                raise RuntimeError(
                    f"TB_SHARDS={shards} but only {len(devs)} device(s) "
                    "visible"
                )
            from .parallel import sharded as shard_mod
            from jax.sharding import Mesh

            for cap in (cfg.accounts_capacity, cfg.transfers_capacity,
                        cfg.posted_capacity):
                assert cap % shards == 0, "capacity not shard-divisible"
            self.shards = shards
            self._shard_mesh = Mesh(
                np.array(devs[:shards]), (shard_mod.AXIS,)
            )
            self._shard_steps = shard_mod.machine_steps(
                self._shard_mesh, cfg.jacobi_max_passes
            )
            self._shard_insert_bounds = {
                "accounts": np.zeros(shards, np.int64),
                "transfers": np.zeros(shards, np.int64),
            }
            if _obs.enabled:
                _obs.gauge("sharding.shards").set(shards)
        # Host data-plane mode (host_engine.py): commits run in the native
        # engine over a numpy mirror; the device ledger is materialized
        # lazily for queries/checkpoints/digests.  The mirror is the
        # authority between materializations.
        self._engine = None
        self._host_led = None
        self._device_stale = False
        self._index_stale = False
        if host_engine:
            from .host_engine import HostEngine, HostLedger

            assert not force_sequential, (
                "host engine is already sequential-exact"
            )
            assert hot_transfers_capacity_max is None, (
                "tiering runs on the device path"
            )
            self._host_led = HostLedger(
                cfg.accounts_capacity, cfg.transfers_capacity,
                cfg.posted_capacity, cfg.history_capacity,
            )
            self._engine = HostEngine(self._host_led, cfg.max_probe)
            self._device_stale = True
            self._ledger = None
        elif self._shard_mesh is not None:
            from .parallel import sharded as shard_mod

            self._ledger = shard_mod.make_sharded_ledger(
                self._shard_mesh,
                cfg.accounts_capacity,
                cfg.transfers_capacity,
                cfg.posted_capacity,
                history_capacity=cfg.history_capacity,
            )
            self._ledger_is_sharded = True
        else:
            self._ledger = sm.make_ledger(
                cfg.accounts_capacity,
                cfg.transfers_capacity,
                cfg.posted_capacity,
                cfg.history_capacity,
            )
        self._report_table_bytes(self._ledger)
        self.prepare_timestamp = 0
        self.commit_timestamp = 0
        # Host-side upper bounds on live rows (for growth decisions without
        # device syncs): counts only grow, so bounding by attempted inserts
        # is safe.
        self._accounts_bound = 0
        self._transfers_bound = 0
        self._posted_bound = 0
        self._history_bound = 0
        # Growth hint only (NOT a dispatch precondition): history rows can
        # only ever append if some create_accounts batch requested the flag.
        self._history_accounts_possible = False
        # Fast-path preconditions (ops/state_machine.py P1/P3): once any
        # account carries limit flags, plain batches must run the full
        # kernel; _balance_bound over-approximates every balance field so
        # the overflow ladder provably cannot fire on the fast path.
        self._limit_accounts_possible = False
        self._balance_bound = 0
        # Secondary index for get_account_transfers (ops/index.py): derived
        # state, rebuilt from the table after restore/state-sync.
        self.index = index_ops.TransferIndex(base=batch_lanes)
        # Every index rebuild (incl. the stale fallback inside query) must
        # also cover the cold-tier runs, or restarts drop evicted
        # transfers from query results.
        self.index.extra_rows_provider = (
            lambda: [np.asarray(r) for r in self.cold.runs]
        )
        # General scan composition (ops/scan_builder.py): lazily-built
        # per-field indexes serving union/intersection/difference scans
        # (scan_builder.zig / scan_merge.zig generality).
        from .ops import scan_builder as sb

        self.scans_transfers = sb.ScanSet(
            "transfers", sb.TRANSFER_FIELDS, base=batch_lanes
        )
        self.scans_transfers.extra_rows_provider = (
            lambda: [np.asarray(r) for r in self.cold.runs]
        )
        self.scans_accounts = sb.ScanSet(
            "accounts", sb.ACCOUNT_FIELDS, base=batch_lanes
        )
        # Tiered transfers store (ops/cold.py): hot device window + cold
        # host spill; None spill_dir with no cap = tiering off (everything
        # stays hot).
        from .ops.cold import ColdStore, make_bloom

        self.cold = ColdStore(spill_dir)
        self.hot_transfers_capacity_max = hot_transfers_capacity_max
        # Tiering is driven by the hot-window cap (evictions never trigger
        # without one); a spill_dir alone is just where cold state WOULD
        # live — restore_host_state turns tiering on when a checkpoint's
        # cold_manifest says evictions already happened.
        self._tiering = hot_transfers_capacity_max is not None
        self._bloom_log2 = cfg.bloom_bits_log2
        self._bloom_np = None
        self._bloom_dev = None
        self._bloom_grows = 0
        self._evictions = 0
        # Commit pipeline (docs/commit_pipeline.md): bounded deferred-
        # readback depth (TB_PIPELINE; resolved lazily so tests can set the
        # env per-instance).
        self._pipeline_depth: Optional[int] = None
        # Wave scheduler (TB_WAVES; docs/waves.md), lazy like the depth.
        self._waves_enabled: Optional[bool] = None
        self._lane = None  # FIFO dispatch-lane executor (see _dispatch_lane)
        # TB_SANITIZE=1 (sanitize.py, test/CI-only): trip on post-warmup
        # recompiles in the serving path.  One bool read at init;
        # sanitize-off runs take none of the branches.
        self._sanitize = _san.enabled()
        # jaxenv.compile_count() as of the last known-legitimate compile
        # point (warmup / growth); None until warmup() arms it.
        self._sanitize_compile_base: Optional[int] = None
        # One-readback grace window after a growth rehash: the grown
        # capacity is a new shape class, so the next dispatch's compiles
        # are legitimate — the tripwire re-baselines instead of tripping.
        self._sanitize_grace = False
        # Set alongside: once ANY capacity changed post-warmup, kernel
        # variants not yet exercised at the new capacity may legitimately
        # first-compile much later (e.g. the first two-phase batch after
        # a growth), so strict raising downgrades to warn-until-re-arm.
        self._sanitize_soft = False
        # Device fault domain (ops/scrub.py; docs/fault_domains.md).  Armed
        # by scrub_arm() when scrub_interval > 0: the mirror is the
        # authoritative host twin (ReferenceStateMachine) every committed
        # batch also applies to; scrub points compare its expected digests
        # against the on-device fold, and recovery re-materializes the
        # device ledger from it.  All None/zero by default: scrub-off runs
        # take none of these branches.
        self._scrub_interval: Optional[int] = None  # lazy (TB_SCRUB_INTERVAL)
        self._scrub_mirror = None
        self._scrub_suspect = False
        self._scrub_commits = 0        # create_* commits since the last check
        self._inflight_handles: List[DeviceCommitHandle] = []
        # Deferred dispatches currently in flight on the FIFO lane
        # (submit/resolve both happen on the serving thread): the
        # commit-lane occupancy the pipeline.shard.* series report.
        self._deferred_inflight = 0
        self._injected_device_faults = 0
        self._device_fault_streak = 0  # consecutive failed dispatches
        self.device_fault_limit = 3    # streak that triggers the degrade
        # Jittered exponential re-dispatch backoff (vsr/timeout.py): one
        # tick of backoff sleeps retry_tick_s seconds; the sim pins it to 0
        # (virtual time).  The prng feeds ONLY sleep jitter, never state.
        self.retry_tick_s = 0.01
        self._retry_prng = _random.Random(0x5C12)  # jitter only, never state
        self._retry_timeout = None
        # Merkle commitment tree (ops/merkle.py; docs/commitments.md).
        # TB_MERKLE=1 replaces the scrub check substrate with the on-device
        # incremental forest: per-commit touched-path updates, root-compare
        # checks, client-verifiable proofs; the authoritative mirror is
        # kept only at the TB_SCRUB_INTERVAL=1 paranoid cadence.  All
        # None/False by default: merkle-off runs take none of these
        # branches (bit-identical to pre-merkle behavior).
        self._merkle_enabled: Optional[bool] = None  # lazy (TB_MERKLE)
        self._scrub_paranoid: Optional[bool] = None  # lazy (TB_SCRUB_PARANOID)
        self._merkle_forest = None
        self._merkle_dirty = False
        self._merkle_steps_cache = None
        self._canon_tree = None  # (canon ledger ref, {pad name: np heap})
        # Deferred commitment lane (TB_MERKLE_ASYNC; docs/commitments.md):
        # touched-row records of committed batches whose leaf->root path
        # refresh has not run yet.  Drained by merkle_settle() at every
        # point a maintained root is observed; leaves recompute from
        # CURRENT table content, so one fused settle is bit-identical to
        # the per-commit update sequence.  Empty unless the knob is on.
        self._merkle_async: Optional[bool] = None  # lazy (TB_MERKLE_ASYNC)
        self._merkle_pending: List[Tuple[str, np.ndarray]] = []
        # Plain-int event counters (read by obs/vopr_viz and tests without
        # the global metrics registry).
        self.scrub_checks = 0
        self.scrub_mismatches = 0
        self.merkle_updates = 0
        self.merkle_rebuilds = 0
        self.merkle_mismatches = 0
        self.merkle_settles = 0  # commitment-lane drains (TB_MERKLE_ASYNC)
        self.device_recoveries = 0
        self.degraded_to_host_engine = False
        if self._tiering:
            self._bloom_np = np.zeros(((1 << self._bloom_log2) // 32,), np.uint32)
            self._bloom_dev = make_bloom(self._bloom_log2)

    def _warmup_cold_tier(self) -> None:
        """The tier's own programs, so that none compiles inside a request:
        the rehydration (a batch's lanes) and, once the hot table stands at
        its ceiling (a deployment starts it there: `--cache-transfers-log2`
        = `--hot-transfers-log2-max`), the three programs of an eviction at
        that capacity, the extract at the size class of an eviction under
        the design load.  On the empty table each evicts nothing."""
        from .ops import cold as cold_mod

        empty = np.zeros(0, dtype=types.TRANSFER_DTYPE)
        table, n = cold_mod.rehydrate(
            self.ledger.transfers,
            *staging.stage_batch(empty, self.batch_lanes, 0),
            max_probe=self.config.max_probe,
        )
        self.ledger = self.ledger.replace(transfers=table)
        hot_max = self.hot_transfers_capacity_max
        if hot_max is not None and table.capacity == hot_max:
            num = self._eviction_permille()
            threshold, _, _ = cold_mod.eviction_threshold(table, num, 1000)
            # An eviction at the ceiling finds the table at load 0.5: the
            # size classes of the rows that leave and of those that stay.
            leaving = (hot_max // 2) * num // 1000
            lanes = self.batch_lanes
            packed = cold_mod.extract_evicted(
                table, threshold, cold_mod.size_class(leaving, lanes))
            jax.block_until_ready(  # tblint: ignore[host-sync] warm-up
                (n, packed, cold_mod.drop_evicted(
                    table, threshold,
                    cold_mod.size_class(hot_max // 2 - leaving, lanes),
                ).count)
            )
        if _obs.enabled:
            self._report_cold_gauges()

    def _d2h_codes(self, codes, overflow=None, stage=None, seq=0):
        """The blocking device->host read of a commit's result codes: the
        ONE point every device dispatch funnels through.  Timed
        (``ops.dispatch_wait_us``): device wait against host work.

        ``stage`` names the txtrace stage the read bills to (``seq``: its
        group): only EXPLICITLY staged readbacks bill — the deferred
        resolve passes "readback"; the default funnel already sits inside
        a ``device_execute`` block (commit_batch / the lane closures), and
        billing its wait again would double-count the barrier.

        ``overflow`` (the table's probe_overflow flag) rides the SAME
        device_get, so the per-batch/per-group overflow check costs zero
        extra syncs; when passed, returns (codes, overflow) instead of
        codes alone.

        host-sync: commit barrier — this is the deliberate readback point
        of the deferred commit pipeline (docs/commit_pipeline.md; the
        ``ops.dispatch`` series counts exactly this method)."""
        self._injected_fault_check()
        t0 = _time.perf_counter()
        with txtrace.stage(stage, seq=seq):
            if overflow is None:
                out = jax.device_get(codes)
            else:
                out, overflow = jax.device_get((codes, overflow))
        wait = _time.perf_counter() - t0
        if _obs.enabled:
            _obs.counter("ops.dispatch").inc()
            _obs.histogram("ops.dispatch_wait_us", "us").observe(wait * 1e6)
        if (self._sanitize and self._sanitize_compile_base is not None
                and self._deferred_inflight == 0):
            # Recompile tripwire: every commit funnels through this
            # readback, so a post-warmup compile (PR 10's size-class bug)
            # is caught one dispatch after it happened, with the count.
            # Checked ONLY at pipeline-quiescent readbacks: a still-
            # running lane closure may be mid-growth, with its compile
            # already counted but its grace flag not yet visible — every
            # closure's flags ARE visible here via its resolve() join.
            # (_deferred_inflight is serving-thread-only: submit and
            # resolve both happen there.)
            self._sanitize_recompile_check("serving commit path")
        return out if overflow is None else (out, overflow)

    # -- device fault domain (ops/scrub.py, docs/fault_domains.md) -----------

    @property
    def scrub_interval(self) -> int:
        """Scrub cadence in commit batches (TB_SCRUB_INTERVAL env; the CLI's
        --scrub-interval overrides).  0 = the device fault domain is off —
        no mirror, no checks, no retry: byte-identical to pre-fault-domain
        behavior."""
        if self._scrub_interval is None:
            import os

            env = os.environ.get("TB_SCRUB_INTERVAL", "")
            self._scrub_interval = int(env) if env.isdigit() else 0
        return self._scrub_interval

    @scrub_interval.setter
    def scrub_interval(self, value: int) -> None:
        self._scrub_interval = max(0, int(value))

    @property
    def merkle_enabled(self) -> bool:
        """Merkle commitment mode (TB_MERKLE env; docs/commitments.md).
        Off (the default) is bit-identical pre-merkle behavior: the scrub
        fault domain runs the PR 4 host-mirror discipline unchanged."""
        if self._merkle_enabled is None:
            import os

            self._merkle_enabled = os.environ.get("TB_MERKLE", "") == "1"
        return self._merkle_enabled

    @merkle_enabled.setter
    def merkle_enabled(self, value: bool) -> None:
        self._merkle_enabled = bool(value)

    @property
    def scrub_paranoid(self) -> bool:
        """Merkle mode's mirror retention: keep the authoritative host
        mirror ALONGSIDE the commitment forest (in-process
        re-materialization recovery + semantic authority — the PR 4
        discipline and its ~1.6x replay tax).  Default: exactly at the
        TB_SCRUB_INTERVAL=1 paranoid cadence; TB_SCRUB_PARANOID=0/1 (or
        the setter) overrides — 0 at interval 1 gives the cheapest
        check-ahead-of-every-commit config: root compare only, recovery
        via checkpoint + WAL replay."""
        if self._scrub_paranoid is None:
            import os

            env = os.environ.get("TB_SCRUB_PARANOID", "")
            if env in ("0", "1"):
                return env == "1"
            return self.scrub_interval == 1
        return self._scrub_paranoid

    @scrub_paranoid.setter
    def scrub_paranoid(self, value: Optional[bool]) -> None:
        self._scrub_paranoid = value if value is None else bool(value)

    @property
    def merkle_armed(self) -> bool:
        return self._merkle_forest is not None

    @property
    def scrub_armed(self) -> bool:
        return self._scrub_mirror is not None or self._merkle_forest is not None

    @property
    def scrub_due(self) -> bool:
        # +1: a check runs BEFORE the commit that would complete the
        # window, so interval 1 verifies the at-rest state ahead of EVERY
        # commit (a flip injected between commits is caught before any
        # commit reads it), interval N ahead of every Nth.
        armed = self._merkle_forest is not None or (
            self._scrub_mirror is not None and not self._scrub_suspect
        )
        return armed and self._scrub_commits + 1 >= self.scrub_interval

    def scrub_arm(self) -> bool:
        """Enable the device fault domain from the CURRENT ledger state.
        Callers arm only at VERIFIED points: genesis, a digest-checked
        checkpoint restore + WAL replay, or the end of a recovery.  No-op
        (returns False) in host-engine mode — there the numpy ledger
        already IS the authority — or when scrub_interval is 0.

        Mirror mode (default): seed the authoritative host mirror — every
        committed batch replays into it, checks compare digest folds.
        Merkle mode (TB_MERKLE=1, docs/commitments.md): build the
        on-device commitment forest — commits update touched leaf->root
        paths, checks compare maintained vs recomputed roots, and the
        full mirror is kept ONLY at the TB_SCRUB_INTERVAL=1 paranoid
        cadence (check-ahead-of-every-commit closes the read-before-check
        window the self-referential tree cannot)."""
        if self._engine is not None or self.scrub_interval <= 0:
            self._scrub_mirror = None
            self._merkle_forest = None
            return False
        if self.merkle_enabled:
            self._merkle_rebuild()
            keep_mirror = self.scrub_paranoid
        else:
            self._merkle_forest = None
            keep_mirror = True
        self._scrub_mirror = scrub_ops.model_from_ledger(
            self.ledger,
            cold_rows=[np.asarray(r) for r in self.cold.runs],
            prepare_timestamp=self.prepare_timestamp,
            commit_timestamp=self.commit_timestamp,
        ) if keep_mirror else None
        self._scrub_suspect = False
        self._scrub_commits = 0
        return True

    def scrub_disarm(self) -> None:
        self._scrub_mirror = None
        self._merkle_forest = None
        self._merkle_dirty = False
        self._scrub_suspect = False

    def inject_device_faults(self, n: int = 1) -> None:
        """Arm ``n`` simulated dispatch failures (tests / VOPR schedules):
        the next n device readbacks raise SimulatedDeviceFault through the
        same funnel a real XlaRuntimeError would."""
        self._injected_device_faults += int(n)

    def _injected_fault_check(self) -> None:
        if self._injected_device_faults > 0:
            self._injected_device_faults -= 1
            raise SimulatedDeviceFault("injected device dispatch fault")

    _SDC_COLS = (
        "debits_pending_lo", "debits_posted_lo",
        "credits_pending_lo", "credits_posted_lo",
        "debits_pending_hi", "debits_posted_hi",
        "credits_pending_hi", "credits_posted_hi",
    )

    def inject_sdc_bitflip(self, rng) -> bool:
        """Flip one seeded bit in a live account balance column on device —
        the VOPR's device-SDC fault (tests / sim only).  Returns False when
        no live account exists yet (nothing to corrupt)."""
        if self._engine is not None or self._ledger is None:
            return False
        a = self._ledger.accounts
        live = np.flatnonzero(
            (np.asarray(a.key_lo) != 0) | (np.asarray(a.key_hi) != 0)
        )
        if live.size == 0:
            return False
        slot = int(live[rng.randrange(live.size)])
        col = self._SDC_COLS[rng.randrange(len(self._SDC_COLS))]
        bit = rng.randrange(64)
        arr = a.cols[col]
        cols = dict(a.cols)
        cols[col] = arr.at[slot].set(arr[slot] ^ jnp.uint64(1 << bit))
        self._ledger = self._ledger.replace(accounts=a.replace(cols=cols))
        self._canon = None  # the corruption must be visible to queries too
        return True

    def _inflight_untrack(self, handle) -> None:
        try:
            self._inflight_handles.remove(handle)
        except ValueError:
            pass  # never tracked (fault domain off) or already recovered

    def _deferred_done(self, handle) -> None:
        if handle._deferred:
            handle._deferred = False
            self._deferred_inflight = max(0, self._deferred_inflight - 1)

    def _deferred_submitted(self, lanes: int, owners=None) -> None:
        """Commit-lane occupancy accounting for one deferred dispatch
        (serving thread, at submit).  Under TB_SHARDS the pipeline.shard.*
        series record per-shard lane occupancy: every shard executes every
        deferred batch (replicated dispatch), so ``inflight`` IS the
        per-shard commit-lane depth, and the per-shard lane counters
        (from the host-side owner bincount) expose insert skew."""
        self._deferred_inflight += 1
        if not _obs.enabled:
            return
        if self.shards:
            _obs.counter("pipeline.shard.dispatches").inc()
            _obs.histogram("pipeline.shard.inflight", "handles").observe(
                self._deferred_inflight
            )
            _obs.counter("pipeline.shard.lanes").inc(lanes)
            if owners is not None:
                for s, c in enumerate(owners.tolist()):
                    if c:
                        _obs.counter(f"pipeline.shard.lanes.{s}").inc(c)

    def _mirror_apply(self, operation: str, batch: np.ndarray,
                      timestamp: int) -> None:
        """Advance the authoritative mirror by one committed batch (strict
        commit order — callers are the post-success blocking commit paths
        and FIFO handle resolves).  A mirror application failure marks it
        SUSPECT: scrub checks stand down and any later recovery escalates
        to checkpoint + WAL replay (the replica's recover_device_state)."""
        model = self._scrub_mirror
        if model is None or self._scrub_suspect:
            return
        from .testing import model as M

        try:
            # Batched column-wise conversion (testing/model.py): one C pass
            # per column instead of ~17 numpy scalar reads per event — the
            # dominant term of the scrub mirror tax (BENCH_r05 ~1.6x
            # overhead_vs_off; re-measured in BENCH_r08).
            if operation == "create_accounts":
                events = M.accounts_from_batch(batch)
            else:
                events = M.transfers_from_batch(batch)
            model.execute(operation, int(timestamp), events)
        except Exception:  # noqa: BLE001 — a broken mirror must stand down
            self._scrub_suspect = True
            if _obs.enabled:
                _obs.counter("scrub.mirror_suspect").inc()

    def _guarded_commit(self, operation, batch, timestamp, impl):
        """The dispatch-lane funnel for blocking commits: scrub cadence
        check BEFORE the commit reads device state, dispatch retry with
        jittered exponential backoff on device faults, and the commitment
        substrate (mirror and/or merkle forest) advanced after success.
        Pass-through (zero new branches beyond one armed check) when the
        fault domain is off."""
        if not self.scrub_armed or self._engine is not None or (
            len(batch) == 0
        ):
            return impl(batch, timestamp)
        while True:
            try:
                self._scrub_maybe_check()
                results = impl(batch, timestamp)
                self._device_fault_streak = 0
                break
            except DEVICE_FAULT_TYPES as err:
                recovered = self._on_blocking_device_fault(
                    operation, batch, timestamp, err
                )
                if recovered is not None:
                    return recovered  # degraded: the host engine committed
        self._scrub_commits += 1
        self._mirror_apply(operation, batch, timestamp)
        self._merkle_apply(operation, batch)
        return results

    def _on_blocking_device_fault(self, operation, batch, timestamp, err):
        """One failed blocking dispatch: quarantine + re-materialize + back
        off (caller retries), or — at device_fault_limit consecutive
        failures — degrade to the host engine and commit there.  Returns
        the results when degraded, None when the caller should retry."""
        if _obs.enabled:
            _obs.counter("device_recovery.dispatch_faults").inc()
        self._device_fault_streak += 1
        if self._device_fault_streak >= self.device_fault_limit:
            self._degrade_to_host_engine(err)
            results = self._engine_commit(operation, batch, timestamp)
            self._device_fault_streak = 0
            return results
        self.quarantine()
        self._rematerialize_from_mirror()
        self._retry_backoff()
        self.device_recoveries += 1
        if _obs.enabled:
            _obs.counter("device_recovery.recoveries").inc()
            _obs.counter("device_recovery.redispatches").inc()
        return None

    def _device_fault_at_resolve(self, err) -> None:
        """Deferred-path funnel: the oldest in-flight handle's dispatch (or
        readback) failed.  Quarantine the whole FIFO lane and re-dispatch
        EVERY pending run from the mirror via the blocking path (which owns
        retry/backoff/degrade), storing per-handle results for resolve()."""
        if _obs.enabled:
            _obs.counter("device_recovery.dispatch_faults").inc()
        if self._scrub_mirror is None:
            if self._merkle_forest is not None:
                # Merkle-only mode: no in-process authority to re-dispatch
                # from — escalate to the durable-state rebuild
                # (replica._settle_or_recover aborts the failed group and
                # runs checkpoint + WAL replay) instead of leaking the raw
                # device error into the serving path.
                self._merkle_dirty = True
                raise DeviceStateUnrecoverable(
                    "deferred dispatch failed with no mirror armed "
                    "(merkle mode recovers via checkpoint + WAL replay)"
                ) from err
            raise err
        self._device_fault_streak += 1
        if self._device_fault_streak >= self.device_fault_limit:
            # Let the re-dispatch below run on the host engine directly.
            self._degrade_to_host_engine(err)
        self._retry_backoff()
        self._recover_inflight()

    def _recover_inflight(self) -> None:
        """Quarantine + rebuild from the mirror, then re-commit every
        pending deferred run's batches in FIFO (== op) order through the
        guarded blocking path."""
        pending = list(self._inflight_handles)
        self._inflight_handles = []
        self.quarantine()
        try:
            if self._engine is None:
                self._rematerialize_from_mirror()
            for handle in pending:
                if hasattr(handle._result, "result"):
                    try:
                        handle._result.result()  # quiesce the dead future
                    except BaseException:  # tblint: ignore[swallow] quiesced fault
                        pass
                assert handle._batches is not None, (
                    "deferred handle tracked without batch retention"
                )
                results = [
                    self._commit_create_transfers(b, ts)
                    for b, ts in zip(handle._batches, handle._timestamps)
                ]
                handle._recovered = results
        except BaseException:
            # Recovery itself failed (e.g. escalating to the durable-state
            # rebuild): the not-yet-recovered handles are already
            # untracked — quiesce them; the caller's pipeline abort (or
            # the direct caller) sees the escalation, never a dangling
            # handle.
            for handle in pending:
                if handle._recovered is not None:
                    continue
                if hasattr(handle._result, "result"):
                    try:
                        handle._result.result()
                    except BaseException:  # tblint: ignore[swallow] quiesced fault
                        pass
            raise
        self.device_recoveries += 1
        if _obs.enabled:
            _obs.counter("device_recovery.recoveries").inc()

    def _scrub_maybe_check(self) -> None:
        if not self.scrub_due or self._inflight_handles:
            return
        self.scrub_check()

    def scrub_check(self, boundary: bool = False) -> bool:
        """Integrity check of the at-rest device state.  Mirror mode:
        compare the on-device fold digests (ops/scrub.scrub_digest — ONE
        readback through the commit-barrier funnel) against the mirror's
        expectation.  Merkle mode: compare the maintained commitment
        roots against roots recomputed from the pads (ONE (2, 3) — or
        per-shard (n, 2, 3) — readback; no mirror, no replay).  On
        mismatch: quarantine, re-materialize the device ledger from the
        mirror, and verify the rebuild took; without a mirror (merkle
        cadence > 1) the mismatch escalates directly to the durable-state
        rebuild (DeviceStateUnrecoverable -> replica checkpoint + WAL
        replay).  Returns True when the state was already clean.
        ``boundary`` marks a checkpoint-boundary check (a divergence there
        is a hard integrity violation the capture must never bake in —
        counted separately)."""
        model = self._scrub_mirror
        mirror_armed = model is not None and not self._scrub_suspect
        if self._merkle_forest is None and not mirror_armed:
            return True
        assert not self._inflight_handles, (
            "scrub requires a settled pipeline"
        )
        self._scrub_commits = 0
        self.scrub_checks += 1
        if _obs.enabled:
            _obs.counter("scrub.checks").inc()
        ok = True
        if self._merkle_forest is not None:
            try:
                ok = self._merkle_verify()
            except DEVICE_FAULT_TYPES as err:
                # The verify dispatch itself failed: without a mirror the
                # only recovery substrate is durable state — escalate
                # instead of leaking a raw device error to the serving
                # path (the mirror path below retries via quarantine).
                if _obs.enabled:
                    _obs.counter("device_recovery.dispatch_faults").inc()
                if not mirror_armed:
                    self._merkle_dirty = True
                    raise DeviceStateUnrecoverable(
                        "device fault during merkle verification "
                        "(no mirror armed)"
                    ) from err
                ok = False
        want = scrub_ops.mirror_digests(model) if mirror_armed else None
        if mirror_armed:
            try:
                got = self._scrub_fold_digests()
                ok = ok and (
                    int(got[0]) == want[0] and int(got[2]) == want[2] and (
                        self.cold.count != 0 or int(got[1]) == want[1]
                    )
                )
            except DEVICE_FAULT_TYPES:
                # The scrub dispatch itself failed: same quarantine/rebuild
                # path as a mismatch (the re-digest below is the retry).
                if _obs.enabled:
                    _obs.counter("device_recovery.dispatch_faults").inc()
                ok = False
        if ok:
            return True
        self.scrub_mismatches += 1
        if _obs.enabled:
            _obs.counter("scrub.mismatches").inc()
            if boundary:
                _obs.counter("scrub.boundary_mismatches").inc()
        if not mirror_armed:
            # Merkle-only detection: there is no in-process authority to
            # re-materialize from — route to the fault domain's last
            # resort (replica.recover_device_state: checkpoint + WAL
            # replay, then scrub_arm rebuilds the forest from the
            # recovered state).
            self._merkle_dirty = True
            raise DeviceStateUnrecoverable(
                "merkle root mismatch: device state corrupt and no "
                "authoritative mirror armed (TB_SCRUB_INTERVAL=1 keeps one)"
            )
        self.quarantine()
        self._rematerialize_from_mirror()
        if self._merkle_forest is not None:
            # The re-materialized ledger is a fresh layout: rebuild the
            # forest from it before re-verifying.
            self._merkle_rebuild()
        try:
            got = self._scrub_fold_digests()
        except DEVICE_FAULT_TYPES as err:
            # A second fault during the verification re-digest: escalate
            # to the durable-state rebuild rather than crash the serving
            # path with a raw device error.
            self._scrub_suspect = True
            raise DeviceStateUnrecoverable(
                "device fault during post-recovery scrub verification"
            ) from err
        if int(got[0]) != want[0] or int(got[2]) != want[2] or (
            self.cold.count == 0 and int(got[1]) != want[1]
        ):
            self._scrub_suspect = True
            raise DeviceStateUnrecoverable(
                "scrub mismatch survived re-materialization: mirror suspect"
            )
        self.device_recoveries += 1
        if _obs.enabled:
            _obs.counter("device_recovery.recoveries").inc()
            _obs.counter("device_recovery.scrub").inc()
        return False

    def _scrub_fold_digests(self) -> np.ndarray:
        """The on-device (accounts, transfers, posted) fold triple through
        the commit-barrier funnel (ONE readback).  Under TB_SHARDS the
        readback is the per-shard uint64 lane matrix (n_shards, 3) from
        parallel/sharded.sharded_scrub_digest, summed mod 2^64 into the
        global digests — the folds are wrap-adds over disjoint owner
        partitions, so the sum equals the single-device fold bit for bit
        (and the lanes localize a mismatch to one shard)."""
        if self._ledger_is_sharded:
            lanes = np.asarray(
                self._d2h_codes(self._shard_steps["scrub"](self.ledger))
            )
            if _obs.enabled:
                _obs.counter("sharding.scrub_lane_checks").inc()
            with np.errstate(over="ignore"):
                return lanes.sum(axis=0, dtype=np.uint64)
        return np.asarray(
            self._d2h_codes(scrub_ops.scrub_digest(self.ledger))
        )

    # -- merkle commitment tree (ops/merkle.py, docs/commitments.md) ---------

    def _merkle_steps(self) -> dict:
        """Jitted sharded merkle steps for this mesh (process-wide cache,
        like the commit steps)."""
        if self._merkle_steps_cache is None:
            from .parallel import sharded as shard_mod

            self._merkle_steps_cache = shard_mod.merkle_steps(
                self._shard_mesh
            )
        return self._merkle_steps_cache

    def _merkle_rebuild(self) -> None:
        """Full forest rebuild from the current ledger — O(capacity), paid
        only at arm points and after non-incremental mutations (growth
        rehash, sequential fallback, tier moves, recovery installs).  A
        rebuild resets the detection window: corruption already present in
        the pads is baked into the fresh tree (same semantics as reseeding
        the mirror — arm/rebuild only at verified or just-checked points)."""
        if self._ledger_is_sharded:
            self._merkle_forest = self._merkle_steps()["build"](self._ledger)
        else:
            self._merkle_forest = merkle_ops.build_forest(self.ledger)
        self._merkle_dirty = False
        # A rebuild reads the whole ledger, so it subsumes every queued
        # deferred-lane touch (TB_MERKLE_ASYNC); stale records would only
        # re-touch rows idempotently, but dropping them keeps lag honest.
        self._merkle_pending.clear()
        self.merkle_rebuilds += 1
        if _obs.enabled:
            _obs.counter("merkle.rebuilds").inc()

    def _merkle_rebuild_if_dirty(self) -> bool:
        if self._merkle_forest is None or not self._merkle_dirty:
            return False
        self._merkle_rebuild()
        return True

    def _merkle_mark_dirty(self) -> None:
        if self._merkle_forest is not None:
            self._merkle_dirty = True

    def _merkle_verify(self) -> bool:
        """Maintained roots vs roots recomputed from the pads: ONE
        readback through the commit-barrier funnel ((2, 3) single-device;
        per-shard (n, 2, 3) lanes under TB_SHARDS, which also localize a
        mismatch to one shard)."""
        self.merkle_settle()  # the scrub oracle observes settled roots only
        self._merkle_rebuild_if_dirty()
        if self._ledger_is_sharded:
            lanes = np.asarray(self._d2h_codes(
                self._merkle_steps()["verify"](
                    self._merkle_forest, self._ledger
                )
            ))
            ok = bool((lanes[:, 0, :] == lanes[:, 1, :]).all())
        else:
            lanes = np.asarray(self._d2h_codes(
                merkle_ops.verify_roots(self._merkle_forest, self.ledger)
            ))
            ok = bool((lanes[0] == lanes[1]).all())
        if _obs.enabled:
            _obs.counter("merkle.checks").inc()
        if not ok:
            self.merkle_mismatches += 1
            if _obs.enabled:
                _obs.counter("merkle.mismatches").inc()
        return ok

    _MERKLE_MIN_LANES = 256

    @staticmethod
    def _merkle_pad(lo: np.ndarray, hi: np.ndarray, min_lanes: int):
        """Pad key arrays to power-of-two lane classes (bounded jit
        variants; zero keys resolve as instant probe misses)."""
        n = len(lo)
        lanes = max(min_lanes, 1 << (n - 1).bit_length()) if n else min_lanes
        p_lo = np.zeros(lanes, np.uint64)
        p_hi = np.zeros(lanes, np.uint64)
        p_lo[:n] = lo
        p_hi[:n] = hi
        return jnp.asarray(p_lo), jnp.asarray(p_hi)

    def _merkle_apply(self, operation: str, batch: np.ndarray) -> None:
        """Advance the commitment forest by one committed batch (the
        blocking paths' post-success hook; deferred dispatches call
        _merkle_update_transfers_batches INSIDE their lane closure so the
        device update rides the ledger chain)."""
        if self._merkle_forest is None or len(batch) == 0:
            return
        if self.merkle_async:
            # Deferred commitment lane: record the touched rows and let a
            # settle barrier pay the leaf->root refresh (merkle_settle).
            self._merkle_lane_enqueue(operation, batch)
            return
        if self._merkle_rebuild_if_dirty():
            return  # the rebuild already reflects this batch
        if operation == "create_accounts":
            self._merkle_apply_accounts(batch)
        else:
            self._merkle_update_transfers_batches([batch])

    def _merkle_apply_accounts(self, batch: np.ndarray) -> None:
        with txtrace.stage("merkle_refresh"):
            lo, hi = self._merkle_pad(
                batch["id_lo"].astype(np.uint64),
                batch["id_hi"].astype(np.uint64),
                self._MERKLE_MIN_LANES,
            )
            if self._ledger_is_sharded:
                self._merkle_forest = (
                    self._merkle_steps()["update_accounts"](
                        self._merkle_forest, self._ledger, lo, hi
                    )
                )
            else:
                self._merkle_forest = merkle_ops.update_accounts(
                    self._merkle_forest, self.ledger, lo, hi,
                    max_probe=sm.MAX_PROBE,
                )
        self.merkle_updates += 1
        if _obs.enabled:
            _obs.counter("merkle.updates").inc()

    def _merkle_lane_enqueue(self, operation: str, batch: np.ndarray) -> None:
        """Queue one committed batch's touched-row record on the deferred
        commitment lane (TB_MERKLE_ASYNC).  Batches are immutable after
        commit, so holding the reference is safe; the queue is
        serving-thread-only, like _deferred_inflight."""
        self._merkle_pending.append((operation, batch))
        if _obs.enabled:
            _obs.counter("merkle.lane.deferred_updates").inc()

    def merkle_settle(self) -> None:
        """Settle barrier for the deferred commitment lane: replay every
        queued touched-row record into the maintained forest, restoring
        exactly the per-batch refresh sequence the synchronous path would
        have produced (leaves recompute from current table content, so
        one coalesced update == the batch-at-a-time sequence).  Runs at
        every point a maintained root is observed — scrub check,
        get_proof, reply-root stamping, merkle_roots, checkpoint capture
        (docs/commitments.md) — and MUST run with the dispatch lane idle:
        the touched-path update reads self.ledger, which in-flight lane
        closures swap and donate."""
        if not self._merkle_pending:
            return
        assert self._deferred_inflight == 0, (
            "merkle_settle with the dispatch lane busy — settle barriers "
            "run only at drained points"
        )
        pending, self._merkle_pending = self._merkle_pending, []
        if self._merkle_forest is None:
            return  # disarmed while records were queued: nothing to anchor
        if _obs.enabled:
            _obs.counter("merkle.lane.settle_waits").inc()
            _obs.histogram("merkle.lane.lag_batches", "batches").observe(
                len(pending)
            )
        self.merkle_settles += 1
        if self._merkle_rebuild_if_dirty():
            return  # the O(capacity) rebuild subsumes every queued touch
        for op, batches in merkle_ops.coalesce_touch_records(
            pending, max_rows=self.batch_lanes
        ):
            if op == "create_accounts":
                self._merkle_apply_accounts(batches[0])
            else:
                self._merkle_update_transfers_batches(batches)

    def _merkle_update_transfers_batches(self, batches) -> None:
        """ONE touched-path update covering a run of committed
        create_transfers batches: inserted ids, deduped account sides,
        pending refs (their posted keys and account sides resolve on
        device).  Over-approximation is safe — recomputing an untouched
        leaf writes the identical value."""
        if self._merkle_forest is None:
            return
        if self._merkle_rebuild_if_dirty():
            return
        with txtrace.stage("merkle_refresh"):
            self._merkle_update_transfers_apply(batches)

    def _merkle_update_transfers_apply(self, batches) -> None:
        ids_lo = np.concatenate([b["id_lo"] for b in batches])
        ids_hi = np.concatenate([b["id_hi"] for b in batches])
        dr_lo = np.concatenate([b["debit_account_id_lo"] for b in batches])
        dr_hi = np.concatenate([b["debit_account_id_hi"] for b in batches])
        cr_lo = np.concatenate([b["credit_account_id_lo"] for b in batches])
        cr_hi = np.concatenate([b["credit_account_id_hi"] for b in batches])
        flags = np.concatenate([b["flags"] for b in batches])
        pv = (
            flags & (types.TransferFlags.POST_PENDING_TRANSFER
                     | types.TransferFlags.VOID_PENDING_TRANSFER)
        ) != 0
        # Dedupe the account side (hot accounts repeat heavily under
        # zipfian batches; np.unique is sorted => deterministic).
        acc = np.unique(np.stack([
            np.concatenate([dr_hi, cr_hi]).astype(np.uint64),
            np.concatenate([dr_lo, cr_lo]).astype(np.uint64),
        ], axis=1), axis=0)
        id_lo, id_hi = self._merkle_pad(
            ids_lo.astype(np.uint64), ids_hi.astype(np.uint64),
            self._MERKLE_MIN_LANES,
        )
        acc_lo, acc_hi = self._merkle_pad(
            acc[:, 1], acc[:, 0], self._MERKLE_MIN_LANES
        )
        has_pv = bool(pv.any())
        pend = (
            np.concatenate([b["pending_id_lo"] for b in batches])[pv],
            np.concatenate([b["pending_id_hi"] for b in batches])[pv],
        ) if has_pv else (np.zeros(0, np.uint64), np.zeros(0, np.uint64))
        pend_lo, pend_hi = self._merkle_pad(
            pend[0].astype(np.uint64), pend[1].astype(np.uint64),
            self._MERKLE_MIN_LANES,
        )
        if self._ledger_is_sharded:
            step = self._merkle_steps()[
                "update_transfers_pv" if has_pv else "update_transfers"
            ]
            self._merkle_forest = step(
                self._merkle_forest, self._ledger, id_lo, id_hi,
                acc_lo, acc_hi, pend_lo, pend_hi,
            )
        else:
            self._merkle_forest = merkle_ops.update_transfers(
                self._merkle_forest, self.ledger, id_lo, id_hi,
                acc_lo, acc_hi, pend_lo, pend_hi,
                max_probe=sm.MAX_PROBE, has_postvoid=has_pv,
            )
        self.merkle_updates += 1
        if _obs.enabled:
            _obs.counter("merkle.updates").inc()

    def merkle_roots(self) -> Optional[Tuple[int, int, int]]:
        """The LIVE maintained commitment roots (accounts, transfers,
        posted) — under TB_SHARDS the wrap-sum fold of the per-shard
        subtree roots through the per-shard uint64 readback lanes.  None
        when merkle mode is not armed.  Callers need a settled pipeline
        (the replica settles before checks/checkpoints/queries)."""
        if self._merkle_forest is None:
            return None
        self.merkle_settle()
        self._merkle_rebuild_if_dirty()
        if self._ledger_is_sharded:
            lanes = np.asarray(self._d2h_codes(
                self._merkle_steps()["roots"](self._merkle_forest)
            ))
            with np.errstate(over="ignore"):
                triple = lanes.sum(axis=0, dtype=np.uint64)
        else:
            triple = np.asarray(self._d2h_codes(
                merkle_ops.forest_roots(self._merkle_forest)
            ))
        return (int(triple[0]), int(triple[1]), int(triple[2]))

    def merkle_canonical_roots(self) -> Optional[Tuple[int, int, int]]:
        """Roots over the CANONICAL single-device layout — the
        shard-config-independent commitment checkpoints serialize and
        proofs anchor to (== merkle_roots() when sharding is off and the
        forest is clean)."""
        if self._merkle_forest is None:
            return None
        # Canonical roots derive from the LEDGER, not the maintained
        # forest, so deferred-lane staleness cannot skew them — but
        # checkpoint capture is a root-observation point, so settle the
        # lane here too (when idle) to bound commitment-lane lag.
        if self._merkle_pending and self._deferred_inflight == 0:
            self.merkle_settle()
        return merkle_ops.np_ledger_roots(self._query_ledger())

    def commitment_root(self) -> int:
        """The canonical ACCOUNTS-pad commitment root of the current
        committed state — the audit anchor the replica stamps into every
        reply header (wire.REPLY_DTYPE ``root``; docs/commitments.md) and
        the root client-held account proofs fold to.  0 when commitments
        are not armed (merkle off / host engine), which is also what
        legacy frames decode, so the field is skippable end to end.

        Single-device mode reads the maintained forest root (one scalar
        readback — the single-device layout IS the canonical one).
        Under TB_SHARDS the canonical root lives in the host tree cache
        get_proof maintains; REBUILDING it costs a full unshard plus an
        O(capacity) hash pass, which must never ride the per-reply hot
        path — so sharded replies stamp the root only when the cache is
        already fresh (a get_proof just built it — exactly the reply the
        client cross-checks) and 0 otherwise, which clients skip by
        contract.  Under grouped/pipelined commit the value may reflect
        a commit point slightly AFTER the op being replied to (the lane
        holds the whole wave): the contract is at-or-after, which a
        get_proof reply — always a group boundary, served from settled
        state — meets exactly.

        Under TB_MERKLE_ASYNC the same skippable-0 contract covers a
        backlogged commitment lane: when deferred touch records are
        queued the reply stamps 0 (clients skip it) rather than a stale
        root — per-reply stamping must never pull the lane's work onto
        the serving thread (that would serialize exactly the refresh the
        deferred lane exists to move off the commit stream).  The HARD
        settle barriers — scrub check, checkpoint capture, get_proof,
        state-sync summary — bound the lag and are the points real roots
        are certified; a get_proof reply (the one clients cross-check)
        is always served from settled state."""
        if self._merkle_forest is None or self._engine is not None:
            return 0
        if self._merkle_pending:
            return 0  # lane backlogged: stamp the skippable sentinel
        self._merkle_rebuild_if_dirty()
        if self._ledger_is_sharded:
            # Cache-fresh check WITHOUT touching _query_ledger() (that
            # would itself trigger the O(capacity) unshard per commit).
            canon = self._canon
            cached = self._canon_tree
            if (
                canon is None or cached is None
                or cached[0] is not canon
                or "accounts" not in cached[1]
            ):
                return 0
            return int(cached[1]["accounts"][1])
        # The forest object is swapped wholesale by commit closures (an
        # immutable pytree per batch), so this read sees SOME committed
        # forest, never a torn one.
        return int(np.asarray(self._merkle_forest.accounts[1]))

    def _canon_tree_nodes(self, pad_name: str) -> np.ndarray:
        """The cached canonical host-side tree heap for ``pad_name``
        (shared by sharded get_proof paths and commitment_root),
        invalidated with the canonical view itself."""
        canon = self._query_ledger()
        cached = self._canon_tree
        if cached is None or cached[0] is not canon:
            self._canon_tree = cached = (canon, {})
        nodes = cached[1].get(pad_name)
        if nodes is None:
            nodes = merkle_ops.np_tree(
                merkle_ops.np_table_leaves(getattr(canon, pad_name), pad_name)
            )
            cached[1][pad_name] = nodes
        return nodes

    def get_proof(self, ident: int, kind: str = "accounts") -> Optional[bytes]:
        """Root-anchored Merkle inclusion proof for one row
        (docs/commitments.md proof format), client-verifiable via
        ops.merkle.check_proof.  Kinds:

        - ``accounts``: the account row + sibling path to the canonical
          accounts root (the PR 10 surface, wire-compatible).
        - ``transfers``: the transfer row + path to the transfers root.
          Only hot-pad rows have leaves — a cold-evicted transfer yields
          None (the tree commits to the pads, not the spill).
        - ``posted``: the fulfillment record of PENDING transfer
          ``ident``: the posted pad is keyed by the pending transfer's
          timestamp, which the proof row carries so a client can bind it
          to that transfer's own proof (its row holds id + timestamp).

        None when the row does not exist in the pad or merkle is off."""
        if self._merkle_forest is None or self._engine is not None:
            return None
        if kind not in merkle_ops.PROOF_KINDS:
            raise ValueError(f"unknown proof kind {kind!r}")
        self.merkle_settle()  # proofs anchor to settled roots only
        lo = np.uint64(ident & U64_MAX)
        hi = np.uint64(ident >> 64)
        row_bytes = None
        if kind == "accounts":
            rows = self.lookup_accounts([ident])
            if len(rows) == 0:
                return None
            row_bytes = rows[0].tobytes()
        elif kind == "transfers":
            rows = self.lookup_transfers([ident])
            if len(rows) == 0:
                return None
            row_bytes = rows[0].tobytes()
        else:  # posted: resolve the pending id to its pad key (timestamp)
            rows = self.lookup_transfers([ident])
            if len(rows) == 0:
                return None
            lo = np.uint64(int(rows[0]["timestamp"]))
            hi = np.uint64(0)
        self._merkle_rebuild_if_dirty()
        if self._ledger_is_sharded:
            path = self._canon_proof_path(lo, hi, kind)
            if path is None:
                return None
            slot, siblings, root = path
            table = getattr(self._query_ledger(), kind)
        else:
            from .ops import hash_table as ht

            table = getattr(self.ledger, kind)
            pad = 8  # one size class for the point lookup
            k_lo = np.zeros(pad, np.uint64)
            k_hi = np.zeros(pad, np.uint64)
            k_lo[0], k_hi[0] = lo, hi
            look = ht.lookup(
                table, jnp.asarray(k_lo), jnp.asarray(k_hi), sm.MAX_PROBE
            )
            if not bool(np.asarray(look.found)[0]):
                return None
            slot = int(np.asarray(look.slot)[0])
            levels = max(0, table.capacity.bit_length() - 1)
            _leaf, sib_dev, root_dev = merkle_ops.gather_path(
                self._merkle_forest.pad(kind), jnp.uint64(slot), levels
            )
            siblings = np.asarray(sib_dev)
            root = int(np.asarray(root_dev))
        if kind == "posted":
            prow = np.zeros((), merkle_ops.PROOF_POSTED_DTYPE)
            prow["pending_timestamp"] = lo
            # One-element readback of the pad's value column at the slot.
            prow["fulfillment"] = int(np.asarray(
                table.cols["fulfillment"][slot]
            ))
            row_bytes = prow.tobytes()
        if _obs.enabled:
            _obs.counter("merkle.proofs").inc()
        return merkle_ops.encode_proof(
            row_bytes, slot, siblings, root, kind=kind
        )

    def _canon_proof_path(self, lo: np.uint64, hi: np.uint64,
                          pad_name: str = "accounts"):
        """Proof path from a cached host-side tree over the canonical
        layout of ``pad_name`` (sharded mode: the live per-shard subtrees
        commit to the sharded layout; proofs and checkpoints anchor to
        the canonical one).  The cached heaps — one per pad, built
        lazily — are invalidated with the canonical view itself.
        Returns (slot, siblings, root), or None when the key is absent."""
        nodes = self._canon_tree_nodes(pad_name)
        table = getattr(self._query_ledger(), pad_name)
        cap = len(nodes) // 2
        key_lo = np.asarray(table.key_lo)
        key_hi = np.asarray(table.key_hi)
        tomb = np.asarray(table.tombstone)
        slot = int(scrub_ops.mix64_np(
            np.asarray([lo]), np.asarray([hi])
        )[0]) & (cap - 1)
        probes = 0
        while not (key_lo[slot] == lo and key_hi[slot] == hi):
            if key_lo[slot] == 0 and key_hi[slot] == 0 and not bool(
                tomb[slot]
            ):
                return None  # absent from the canonical pad
            slot = (slot + 1) & (cap - 1)
            probes += 1
            if probes > cap:
                return None
        idx = cap + slot
        siblings = np.empty(max(0, cap.bit_length() - 1), np.uint64)
        for level in range(len(siblings)):
            siblings[level] = nodes[idx ^ 1]
            idx >>= 1
        return slot, siblings, int(nodes[1])

    def quarantine(self) -> None:
        """Quarantine the in-flight device pipeline: drain the FIFO dispatch
        lane (joining any running closure).  Nothing staged is cached (a
        request's operands are fresh and die with its dispatch), so there
        is no device buffer of the commit path to invalidate."""
        lane, self._lane = self._lane, None
        if lane is not None:
            lane.shutdown(wait=True)

    def _rematerialize_from_mirror(self) -> None:
        """Rebuild the device ledger (fresh buffers) from the authoritative
        mirror and resynchronize the host-side derived state.  Content-
        exact; table layout is rebuilt (invisible to semantics and to the
        order-independent digests)."""
        model = self._scrub_mirror
        if model is None or self._scrub_suspect:
            raise DeviceStateUnrecoverable("mirror unavailable or suspect")
        if self._tiering or self.cold.count:
            # The mirror holds every transfer but cannot reproduce the
            # hot/cold split the bloom filter and spill manifest encode.
            raise DeviceStateUnrecoverable(
                "cold tier active: mirror re-materialization unsupported"
            )
        # Property assignment: under TB_SHARDS the setter re-places the
        # single-layout materialization onto the mesh.
        self.ledger = scrub_ops.materialize_ledger(model, self.config)
        self._merkle_mark_dirty()  # fresh layout: forest rebuilds from it
        self._resync_host_state_from_mirror(model)

    def _resync_host_state_from_mirror(self, model) -> None:
        self._accounts_bound = len(model.accounts)
        self._transfers_bound = len(model.transfers)
        self._posted_bound = len(model.posted)
        self._history_bound = len(model.history)
        self._history_accounts_possible = any(
            a.flags & types.AccountFlags.HISTORY
            for a in model.accounts.values()
        )
        self._limit_accounts_possible = any(
            a.flags & _LIMIT_FLAGS for a in model.accounts.values()
        )
        bound = 0
        for a in model.accounts.values():
            bound = max(a.debits_pending, a.debits_posted,
                        a.credits_pending, a.credits_posted, bound)
        self._balance_bound = min(bound, _BOUND_CLAMP)
        self.commit_timestamp = max(
            self.commit_timestamp, model.commit_timestamp
        )
        self.index.reset()
        self.scans_transfers.reset()
        self.scans_accounts.reset()

    def reset_device_state(self) -> None:
        """Genesis reset (the replica's checkpoint-free recovery path):
        fresh empty ledger, derived state cleared.  The prepare clock is
        PRESERVED — already-issued prepare timestamps must stay monotone."""
        cfg = self.config
        if self._shard_mesh is not None:
            from .parallel import sharded as shard_mod

            self._ledger = shard_mod.make_sharded_ledger(
                self._shard_mesh, cfg.accounts_capacity,
                cfg.transfers_capacity, cfg.posted_capacity,
                history_capacity=cfg.history_capacity,
            )
            self._ledger_is_sharded = True
            self._shard_insert_bounds = {
                "accounts": np.zeros(self.shards, np.int64),
                "transfers": np.zeros(self.shards, np.int64),
            }
        else:
            self._ledger = sm.make_ledger(
                cfg.accounts_capacity, cfg.transfers_capacity,
                cfg.posted_capacity, cfg.history_capacity,
            )
        self._canon = None
        self._merkle_mark_dirty()
        self.commit_timestamp = 0
        self._accounts_bound = self._transfers_bound = 0
        self._posted_bound = self._history_bound = 0
        self._history_accounts_possible = False
        self._limit_accounts_possible = False
        self._balance_bound = 0
        self.index.reset()
        self.scans_transfers.reset()
        self.scans_accounts.reset()

    def _retry_backoff(self) -> None:
        """Jittered exponential backoff between re-dispatch attempts
        (vsr/timeout.py Timeout — the same discipline replica retries use).
        Sleeps retry_tick_s per tick; 0 (the sim) skips the sleep, keeping
        virtual-time replay deterministic (the jitter prng feeds only the
        sleep duration, never state)."""
        if self._retry_timeout is None:
            from .vsr.timeout import Timeout

            self._retry_timeout = Timeout(
                self._retry_prng, base_ticks=1, max_ticks=64
            )
        ticks = self._retry_timeout.next_backoff()
        if _obs.enabled:
            _obs.counter("device_recovery.retries").inc()
        if self.retry_tick_s > 0:
            _time.sleep(ticks * self.retry_tick_s)  # backoff sleep, not state

    def _degrade_to_host_engine(self, err) -> None:
        """After device_fault_limit consecutive dispatch failures: stop
        trusting the device entirely and serve from the native host engine
        over a ledger rebuilt from the mirror — a RuntimeWarning, not a
        wedge (the DEGRADED_DEVICE_COUNT discipline in jaxenv.py)."""
        from .host_engine import HostEngine, HostLedger, engine_available

        model = self._scrub_mirror
        if model is None or self._scrub_suspect:
            raise DeviceStateUnrecoverable(
                "device failing and mirror unavailable"
            ) from err
        if self._tiering or self.cold.count or (
            self.hot_transfers_capacity_max is not None
        ):
            raise DeviceStateUnrecoverable(
                "device failing under tiering: host engine cannot take over"
            ) from err
        if not engine_available():
            raise DeviceStateUnrecoverable(
                "device failing and the native host engine is unavailable"
            ) from err
        self.quarantine()
        self._host_led = scrub_ops.build_host_ledger(model, self.config)
        self._engine = HostEngine(self._host_led, self.config.max_probe)
        self._resync_host_state_from_mirror(model)
        self._index_stale = True
        self._device_stale = True
        self._ledger = None  # lazily re-materialized for queries/checkpoints
        self.scrub_disarm()  # the host ledger IS the authority now
        self.degraded_to_host_engine = True
        if _obs.enabled:
            _obs.counter("device_recovery.degraded").inc()
        warnings.warn(
            f"device dispatch failed {self.device_fault_limit} consecutive "
            f"times ({err!r}); degraded to the native host engine "
            "(device path disabled for this process)",
            RuntimeWarning,
            stacklevel=3,
        )

    # -- host-engine mode (host_engine.py) -----------------------------------

    @property
    def ledger(self):
        """The device (jnp) ledger.  In host-engine mode the numpy mirror is
        the authority; the device view is materialized on first access after
        engine commits (queries, checkpoints, digests, sharding)."""
        if self._engine is not None and self._device_stale:
            self._ledger = self._host_led.to_device()
            self._device_stale = False
        return self._ledger

    @ledger.setter
    def ledger(self, value) -> None:
        if (
            getattr(self, "_shard_mesh", None) is not None
            and getattr(self, "_ledger_is_sharded", False)
            and value is not None
            and np.ndim(value.accounts.count) == 0
        ):
            # External install of a single-layout ledger (checkpoint
            # restore, state sync) while sharded mode is live: re-place it
            # into the owner-partitioned layout.  Internal sharded commits
            # assign sharded values (vector counts) and pass through; the
            # sequential-fallback window flips _ledger_is_sharded off so
            # its single-layout intermediate states also pass through.
            from .parallel import sharded as shard_mod

            value = shard_mod.shard_ledger(value, self._shard_mesh)
            self._refresh_shard_bounds(value)
        self._ledger = value
        self._canon = None
        if getattr(self, "_engine", None) is not None:
            # External ledger swap (checkpoint restore, state sync): refresh
            # the host mirror — it must mirror the new authority exactly.
            from .host_engine import HostLedger

            self._host_led = HostLedger.from_device(value)
            self._engine.ledger = self._host_led
            self._device_stale = False

    def _query_ledger(self):
        """The single-layout ledger view queries/lookups/checkpoints probe:
        identity when sharding is off; under TB_SHARDS a cached canonical
        un-sharding of the live ledger (content-exact, single-device probe
        layout), rebuilt lazily after a commit invalidates it.  Every query
        kernel (index, scans, history, point lookups) and the checkpoint
        serializer thus keep their existing single-device programs."""
        if self._shard_mesh is None or not self._ledger_is_sharded:
            return self.ledger
        if self._canon is None:
            from .parallel import sharded as shard_mod

            # The whole rebuild: every shard's arrays to the host, the
            # host-side re-placement, the upload to device 0.
            with txtrace.stage("unshard"):
                self._canon = shard_mod.unshard_ledger(
                    self._ledger, self._shard_mesh
                )
            if _obs.enabled:
                _obs.counter("sharding.unshards").inc()
        return self._canon

    def checkpoint_ledger(self):
        """The ledger snapshot checkpoints serialize: canonical single-
        device layout, so a checkpoint restores into ANY shard config (and
        every replica of a homogeneous cluster writes byte-identical
        arrays — the converters are deterministic)."""
        return self._query_ledger()

    def _engine_grow(
        self, accounts: int = 0, transfers: int = 0, posted: int = 0,
        history: int = 0,
    ) -> None:
        """Load-factor management for the host tables (mirror of
        _grow_if_needed, same <= 0.5 policy, same host-side bounds)."""
        led = self._host_led
        for which, need in (
            ("accounts", self._accounts_bound + accounts),
            ("transfers", self._transfers_bound + transfers),
            ("posted", self._posted_bound + posted),
        ):
            cap = self._target_capacity(getattr(led, which).capacity, need)
            if cap != getattr(led, which).capacity:
                self._engine.grow(which, cap)
        if history and self._history_bound + history > led.history_capacity:
            led.grow_history(self._history_bound + history)

    def _engine_commit(
        self, operation: str, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        count = len(batch)
        if count == 0:
            return []
        # Invalidate derived views BEFORE dispatching: a partial application
        # (EngineError after some events applied) must not leave queries
        # serving the pre-commit device ledger.
        self._device_stale = True
        self._index_stale = True
        if operation == "create_accounts":
            if bool((batch["flags"] & types.AccountFlags.HISTORY).any()):
                self._history_accounts_possible = True
            if bool((batch["flags"] & _LIMIT_FLAGS).any()):
                self._limit_accounts_possible = True
            self._engine_grow(accounts=count)
            codes = self._engine.create_accounts(batch, timestamp)
            self._accounts_bound += count
        else:
            pv_count, hist_count = self._transfer_growth_counts(batch)
            self._engine_grow(
                transfers=count, posted=pv_count, history=hist_count
            )
            codes = self._engine.create_transfers(batch, timestamp)
            self._transfers_bound += count
            self._posted_bound += pv_count
            self._history_bound += hist_count
        results = self._compress(codes, count)
        self._update_commit_timestamp(codes, count, timestamp)
        return results

    def _index_fresh(self) -> None:
        """Engine commits bypass the per-batch index append; rebuild the
        derived index from the (refreshed) ledger before serving a query."""
        if self._engine is not None and self._index_stale:
            self.index.reset()
            self.scans_transfers.reset()
            self.scans_accounts.reset()
            self._index_stale = False

    def _sanitize_arm_tripwire(self) -> None:
        """TB_SANITIZE: baseline the compile count at a known-legitimate
        compile point (end of warmup, after a growth rehash).  Serving
        dispatches past this point must not compile; _d2h_codes checks."""
        if not self._sanitize:
            return
        from . import jaxenv

        if not jaxenv.instrument_compiles():
            # No listener -> compile_count() is frozen and every delta
            # would be a vacuous 0.  Stay DISARMED (base None) and say so,
            # rather than reporting the serving path compile-free.
            _san._warn_unarmed("serving commit path")
            self._sanitize_compile_base = None
            return
        self._sanitize_compile_base = jaxenv.compile_count()
        self._sanitize_grace = False
        self._sanitize_soft = False

    def _sanitize_absorb_compiles(self) -> None:
        """Fold compiles made by a NON-commit entry point (first lookup/
        query/digest after warmup jit-compiles its kernel) into the
        tripwire baseline: they are first-use compiles of read paths, not
        serving-commit recompiles, and must not be attributed to (or
        strict-raise out of) the next commit's readback."""
        if self._sanitize and self._sanitize_compile_base is not None:
            from . import jaxenv

            self._sanitize_compile_base = jaxenv.compile_count()

    def _sanitize_recompile_check(self, where: str) -> None:
        from . import jaxenv

        cur = jaxenv.compile_count()
        if self._sanitize_grace:
            # First readback after a growth rehash: new capacity = new
            # shape class, its compiles are legitimate.  Re-baseline.
            self._sanitize_grace = False
            self._sanitize_compile_base = cur
            return
        delta = cur - self._sanitize_compile_base
        if delta > 0:
            # Re-baseline FIRST so a strict raise (or a burst of late
            # compiles) reports once, not once per readback.  Strict
            # raising is downgraded to the warning (_sanitize_soft, set at
            # a growth or the history-flag flip) and whenever the device
            # fault domain is armed: scrub/merkle check kernels compile
            # lazily at their first cadence point, post-warmup by design.
            self._sanitize_compile_base = cur
            strict_ok = not (
                self._sanitize_soft
                or self._scrub_mirror is not None
                or self._merkle_forest is not None
            )
            _san.recompile_trip(where, delta, strict_ok=strict_ok)

    def warmup(self) -> None:
        """Force-compile the hot commit kernels with zero-count batches so
        the first client request doesn't pay tens of seconds of jit latency
        (the CLI calls this before announcing ``listening``).  The kernels
        are functional — results are discarded, state is untouched.

        Under TB_SANITIZE the end of warmup arms the serving recompile
        tripwire: from here on, a commit dispatch that compiles is a
        size-class bug (warn; raise under TB_SANITIZE_STRICT).

        In host-engine mode there is nothing to compile; instead pre-fault
        the numpy tables (lazily-mapped pages would otherwise fault inside
        the serving hot loop)."""
        try:
            self._warmup_impl()
        finally:
            self._sanitize_arm_tripwire()

    def _warmup_impl(self) -> None:
        if self._engine is not None:
            self._host_led.prefault()
            return
        if self._ledger_is_sharded:
            # Warm the sharded commit kernels (accounts, fast, the full
            # variant for the current waves setting): one zero-count
            # dispatch each, state value-identical.  Staged exactly as the
            # served routes stage (jit keys an executable on each operand's
            # sharding: a warm-up that handed other operands would leave
            # the served program to compile inside a client's request).
            staged_a = self._stage_sharded(
                np.zeros(0, dtype=types.ACCOUNT_DTYPE), 1
            )
            self.ledger, codes_a = self._shard_steps["accounts"](
                self.ledger, *staged_a
            )
            staged_t = self._stage_sharded(
                np.zeros(0, dtype=types.TRANSFER_DTYPE), 1
            )
            self.ledger, codes_f = self._shard_steps["fast"](
                self.ledger, *staged_t
            )
            # The async sharded engine dispatches the PROBED sharded
            # step — deferred at depth >= 2 AND blocking grouped runs
            # (commit_group_fast routes through it at any depth); a
            # client must never pay its compile mid-request.  The batch
            # is not donated, so the staged operands serve every step.
            r = self._shard_steps["fast_probed"](self.ledger, *staged_t)
            self.ledger = r[0]
            np.asarray(r[1]), np.asarray(r[2])
            step = self._shard_steps[
                "full_waves" if self.waves_enabled else "full"
            ]
            r = step(self.ledger, *staged_t)
            self.ledger = r[0]
            np.asarray(codes_a), np.asarray(codes_f), np.asarray(r[1])
            return
        from .ops import transfer_full as tf

        # The kernels donate the ledger buffers: adopt the returned ledger
        # (a zero-count batch applies nothing, so it is value-identical).
        # Staged exactly as the served routes stage.
        staged_a = self._stage(np.zeros(0, dtype=types.ACCOUNT_DTYPE), 1)
        self.ledger, codes_a = sm.create_accounts(self.ledger, *staged_a)
        empty = np.zeros(0, dtype=types.TRANSFER_DTYPE)
        staged_t = self._stage(empty, 1)
        cold_checked = (
            jnp.zeros((self.batch_lanes,), jnp.bool_) if self._tiering else None
        )
        # Warm BOTH reachable serving variants for the CURRENT history
        # flag: dispatch selects (has_postvoid=pv_count>0,
        # has_history=self._history_accounts_possible), so a plain batch
        # and a post/void batch must both find their kernel compiled — a
        # client must never pay a kernel compile inside the serving path.
        # (If a HISTORY account is created later the flag flips and the
        # True-history variants compile on first use; warming them here
        # would charge every history-free server two extra compiles.)
        for has_postvoid in (False, True):
            r = tf.create_transfers_full(
                self.ledger, *staged_t, self._bloom_dev, cold_checked,
                max_passes=self.config.jacobi_max_passes,
                has_postvoid=has_postvoid,
                has_history=self._history_accounts_possible,
                use_waves=self.waves_enabled,
            )
            self.ledger, codes_t, kflags = r[0], r[1], r[2]
        if self._tiering:
            self._warmup_cold_tier()
        if self._fast_path_ok(empty):
            # Only pay the extra compile when the fast path is reachable
            # (tiering / restored limit flags / blown balance bound disable
            # it for the process lifetime).
            self.ledger, codes_f = sm.create_transfers_fast(
                self.ledger, *staged_t
            )
            np.asarray(codes_f)
            if self.pipeline_depth > 1:
                # The pipelined serving engine dispatches the PROBED
                # variant (overflow rides the codes readback in a fresh
                # buffer); a client must never pay its compile mid-request.
                self.ledger, codes_p, *_ = sm.create_transfers_fast_probed(
                    self.ledger, *staged_t
                )
                np.asarray(codes_p)
            # The grouped dispatch is a distinct program for each of
            # the stack's two leading dimensions (_group_rows), ONE for
            # every group length that fits it (a zero count runs zero
            # steps); a client must never pay a compile mid-group.
            for rows in sorted({self._group_rows(2),
                                self._group_rows(self.GROUP_K)}):
                self.ledger, codes_g, *_ = _group_fast_dispatch(
                    self.ledger, *staging.stage_group(
                        [empty], self.batch_lanes, [1], rows
                    )
                )
                np.asarray(codes_g)
        np.asarray(codes_a), np.asarray(codes_t), int(kflags)

    # -- prepare (state_machine.zig:503-512) --------------------------------

    def prepare(self, operation: str, count: int, wall_clock_ns: int = 0) -> int:
        if wall_clock_ns > self.prepare_timestamp:
            self.prepare_timestamp = wall_clock_ns
        if operation in ("create_accounts", "create_transfers"):
            self.prepare_timestamp += count
        return self.prepare_timestamp

    # -- batch plumbing ------------------------------------------------------

    def _stage(self, batch: np.ndarray, timestamp: int) -> tuple:
        """The ONE staging of a request (``ops/staging.stage_batch``): the
        operands every one-chip commit program takes after the ledger,
        padded to ``batch_lanes``, in one ``device_put`` to the default
        device.  Fresh host arrays a call: nothing is pooled, so nothing
        can be refilled under a transfer (or an XLA-CPU alias) that still
        reads it, and no program donates them."""
        return staging.stage_batch(batch, self.batch_lanes, timestamp)

    def _stage_sharded(self, batch: np.ndarray, timestamp: int) -> tuple:
        """``_stage``'s twin on the mesh: the same operands, already
        replicated on it, for every ``self._shard_steps`` commit program.
        Runs on the thread that enqueues the program right after: the
        serving thread on the blocking routes and for a lone deferred
        request, the LANE thread for every batch of a grouped run
        (``_commit_group_fast_sharded``).  Safe there: ``stage_batch``
        allocates fresh host arrays a call, and what it reads of the
        machine (``_shard_mesh``, ``batch_lanes``) changes only at a
        reshard's cutover, which runs between commits."""
        from jax.sharding import NamedSharding, PartitionSpec

        if _obs.enabled:
            _obs.counter("sharding.staged").inc()
        return staging.stage_batch(
            batch, self.batch_lanes, timestamp,
            NamedSharding(self._shard_mesh, PartitionSpec()),
        )

    @staticmethod
    def _compress(codes: np.ndarray, count: int) -> List[Tuple[int, int]]:
        codes = codes[:count]
        idx = np.flatnonzero(codes)
        # tolist() converts both columns to Python ints in one vector pass.
        return list(zip(idx.tolist(), codes[idx].tolist()))

    @staticmethod
    def _has_intra_batch_dup_ids(batch: np.ndarray) -> bool:
        # id 0 lanes can never insert (id_must_not_be_zero), so repeats of 0
        # are not order-dependent duplicates.
        nonzero = (batch["id_lo"] != 0) | (batch["id_hi"] != 0)
        ids = np.stack([batch["id_hi"][nonzero], batch["id_lo"][nonzero]], axis=1)
        return len(np.unique(ids, axis=0)) < len(ids)

    def commit_batch(
        self, operation: str, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        """Commit a batch whose prepare timestamp was already assigned (by
        this replica's prepare(), by the primary, or during WAL replay) —
        the replica's StateMachine.commit() seam (state_machine.zig:894-928).
        """
        if operation not in ("create_accounts", "create_transfers"):
            raise ValueError(f"unknown commit operation {operation}")
        # Replay/backup path: keep the local prepare clock >= the primary's.
        if timestamp > self.prepare_timestamp:
            self.prepare_timestamp = timestamp
        # Attribution stage over the WHOLE blocking commit — dispatch +
        # compute + the readback barrier ("kernel dispatch -> completion",
        # obs/txtrace.STAGES) — so the ledger is backend-honest: XLA-CPU
        # executes inside the jitted call, an async backend inside the
        # _d2h_codes wait; both land here.  Free when attribution is off.
        with txtrace.stage("device_execute"):
            if operation == "create_accounts":
                return self._commit_create_accounts(batch, timestamp)
            return self._commit_create_transfers(batch, timestamp)

    # -- create_accounts -----------------------------------------------------

    def create_accounts(
        self, batch: np.ndarray, wall_clock_ns: int = 0
    ) -> List[Tuple[int, int]]:
        timestamp = self.prepare("create_accounts", len(batch), wall_clock_ns)
        return self._commit_create_accounts(batch, timestamp)

    def _commit_create_accounts(
        self, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        return self._guarded_commit(
            "create_accounts", batch, timestamp,
            self._commit_create_accounts_impl,
        )

    def _commit_create_accounts_impl(
        self, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        count = len(batch)
        if count == 0:
            return []
        if _obs.enabled:
            _obs.histogram("ops.batch_fill_pct", "%").observe(
                100 * count // self.batch_lanes
            )
        if self._engine is not None:
            return self._engine_commit("create_accounts", batch, timestamp)

        any_linked = bool((batch["flags"] & types.AccountFlags.LINKED).any())
        if self.force_sequential or (
            any_linked and self._has_intra_batch_dup_ids(batch)
        ):
            return self._sequential("create_accounts", batch, timestamp)

        self._note_shard_inserts("accounts", batch, count)
        self._grow_if_needed(accounts=count)
        if bool((batch["flags"] & types.AccountFlags.HISTORY).any()):
            if not self._history_accounts_possible and self._sanitize:
                # The has_history=True kernel variants first-compile at
                # the next transfer dispatch (warmup deliberately skips
                # them) — a legitimate compile, not a size-class bug.
                self._sanitize_soft = True
            self._history_accounts_possible = True
        if bool((batch["flags"] & _LIMIT_FLAGS).any()):
            self._limit_accounts_possible = True
        if self._ledger_is_sharded:
            # Same codes, owner-local inserts (parallel/sharded.py); the
            # probe_overflow check below reads the per-shard lane vector.
            with txtrace.stage("stage_h2d"):
                staged = self._stage_sharded(batch, timestamp)
            self.ledger, codes = self._shard_steps["accounts"](
                self.ledger, *staged
            )
        else:
            with txtrace.stage("stage_h2d"):
                staged = self._stage(batch, timestamp)
            self.ledger, codes = sm.create_accounts(self.ledger, *staged)
        codes, overflow = self._d2h_codes(
            codes, self.ledger.accounts.probe_overflow
        )
        self._accounts_bound += count
        if bool(np.any(overflow)):
            # Load-factor management keeps this unreachable; losing inserts
            # silently is the one unacceptable outcome, so fail loud.
            raise RuntimeError("accounts probe overflow during insert")
        self._scan_append_accounts(staged, codes, count)
        results = self._compress(codes, count)
        self._update_commit_timestamp(codes, count, timestamp)
        return results

    # -- create_transfers ----------------------------------------------------

    def create_transfers(
        self, batch: np.ndarray, wall_clock_ns: int = 0
    ) -> List[Tuple[int, int]]:
        timestamp = self.prepare("create_transfers", len(batch), wall_clock_ns)
        return self._commit_create_transfers(batch, timestamp)

    def _commit_create_transfers(
        self, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        return self._guarded_commit(
            "create_transfers", batch, timestamp,
            self._commit_create_transfers_impl,
        )

    def _commit_create_transfers_impl(
        self, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        count = len(batch)
        if count == 0:
            return []
        if _obs.enabled:
            _obs.histogram("ops.batch_fill_pct", "%").observe(
                100 * count // self.batch_lanes
            )
        if self._engine is not None:
            return self._engine_commit("create_transfers", batch, timestamp)

        with txtrace.stage("route"):
            self._note_balance_bound(batch)
            fast = not (
                self.force_sequential or self._ledger_is_sharded
            ) and self._fast_path_ok(batch)
        if self.force_sequential:
            return self._sequential("create_transfers", batch, timestamp)

        if self._ledger_is_sharded:
            return self._sharded_commit_transfers(batch, timestamp, count)

        if fast:
            return self._commit_fast(batch, timestamp, count)

        with txtrace.stage("general_commit", n=count):
            return self._commit_general(batch, timestamp, count)

    def _commit_general(
        self, batch: np.ndarray, timestamp: int, count: int
    ) -> List[Tuple[int, int]]:
        """The general (Jacobi) kernel's route, blocking on the calling
        thread: one dispatch and one device sync per attempt."""
        from .ops import transfer_full as tf

        pv_count, hist_count = self._transfer_growth_counts(batch)
        with txtrace.stage("grow"):
            self._grow_if_needed(
                transfers=count, posted=pv_count, history=hist_count
            )
        with txtrace.stage("stage_h2d"):
            staged = self._stage(batch, timestamp)
            cold_checked = (
                jnp.zeros((self.batch_lanes,), jnp.bool_)
                if self._tiering else None
            )
        # STATIC phase hints: a batch with no post/void lanes skips the
        # four pending-side probe loops and the posted write; a ledger that
        # provably holds no HISTORY-flagged account skips the 21-column
        # history append.  Each (hint, hint) pair is its own jit variant.
        has_postvoid = pv_count > 0
        has_history = self._history_accounts_possible
        use_waves = self.waves_enabled
        for _attempt in range(8):
            with txtrace.stage("dispatch"):
                r = tf.create_transfers_full(
                    self.ledger, *staged, self._bloom_dev, cold_checked,
                    max_passes=self.config.jacobi_max_passes,
                    has_postvoid=has_postvoid, has_history=has_history,
                    use_waves=use_waves,
                )
            self.ledger, codes, kflags = r[0], r[1], r[2]
            # The rest in the program's order (tf.create_transfers_full):
            # the waves' vector, the tier's flagged lanes, the index's feed.
            rest = iter(r[3:])
            wave_vec = next(rest) if use_waves else None
            cold_lanes = next(rest) if self._bloom_dev is not None else None
            index_feed = tuple(rest)
            # The kflags scalar read IS this path's blocking device sync
            # (the codes transfer below rides an already-complete dispatch);
            # under the cold tier the lanes FLAG_COLD is about ride it too.
            kflags, wave_host, cold_lanes = self._full_kflags_sync(
                kflags, wave_vec, cold_lanes)
            if kflags == 0:
                results = self._full_commit_success(
                    codes, count, pv_count, hist_count, timestamp,
                    wave_host, index_feed=index_feed,
                )
                # Deferred tier rebalance: eviction is only safe BETWEEN
                # batches (mid-loop it would invalidate the certification
                # and the batch's hot gathers).
                self._maybe_evict_between_batches()
                return results
            if _obs.enabled:
                _obs.counter("ops.general.retries").inc()
            ev0 = self._evictions
            if kflags & tf.FLAG_COLD:
                # Possible cold-tier ids: resolve the flagged lanes exactly
                # on the host, rehydrate any real cold rows into the hot
                # table, and certify the batch so Bloom false positives
                # terminate (an unflagged lane's ids are hot or miss the
                # filter, which has no false negatives).
                with txtrace.stage("cold_resolve", n=count):
                    self._resolve_cold(batch, cold_lanes)
                # Any eviction voids the certification: freshly-cold rows
                # must be re-detected by the Bloom on the next attempt.
                cold_checked = (
                    jnp.ones((self.batch_lanes,), jnp.bool_)
                    if self._evictions == ev0
                    else jnp.zeros((self.batch_lanes,), jnp.bool_)
                )
                continue
            if kflags & tf.FLAG_SEQ:
                # Order-dependent batch (balancing / limit accounts / deep
                # intra-batch chains): exact sequential execution.
                if _obs.enabled:
                    _obs.counter("ops.general.seq_handovers").inc()
                return self._sequential("create_transfers", batch, timestamp)
            # Probe overflow despite load management (hash clustering):
            # grow the flagged tables and retry — the kernel applied nothing.
            self._grow_flagged(kflags)
            if self._tiering and self._evictions != ev0 and cold_checked is not None:
                cold_checked = jnp.zeros((self.batch_lanes,), jnp.bool_)
        raise RuntimeError("transfer kernel could not place batch after growth")

    def _full_kflags_sync(self, kflags, wave_vec, cold_lanes=None):
        """The general kernel's blocking commit barrier, shared by the
        single-device and sharded dispatch loops: the kflags scalar read
        (plus the 11-scalar wave profile riding the SAME sync when armed,
        and under the cold tier the lanes FLAG_COLD is about), timed so the
        e2e decomposition sees the device wait.  Returns ``(kflags, wave
        profile or None, cold lanes or None)`` on the host."""
        self._injected_fault_check()
        t0 = _time.perf_counter()
        with txtrace.stage("full_sync"):
            if not _obs.enabled:
                wave_vec = None
            if wave_vec is not None or cold_lanes is not None:
                got = jax.device_get(  # tblint: ignore[host-sync] commit barrier
                    (kflags, wave_vec, cold_lanes)
                )
                kflags, wave_host, cold_lanes = got
                kflags = int(kflags)
            else:
                kflags = int(kflags)
                wave_host = None
        wait = _time.perf_counter() - t0
        if _obs.enabled:
            _obs.counter("ops.dispatch").inc()
            _obs.histogram("ops.dispatch_wait_us", "us").observe(wait * 1e6)
        return kflags, wave_host, cold_lanes

    def _full_commit_success(self, codes, count, pv_count, hist_count,
                             timestamp, wave_host, index_feed=None):
        """Post-commit bookkeeping of a COMMITTED general-kernel batch
        (kflags == 0), shared by both dispatch loops.  Only committed
        batches feed the wave occupancy series — a routed or retried
        attempt applied nothing and would overstate them — and only a
        committed attempt's ``index_feed`` (the id columns, the index key
        columns its kernel wrote and the lanes it wrote them for; None
        from the sharded loop, whose index is lazy) reaches the index."""
        if wave_host is not None:
            self._record_wave_metrics(wave_host)
        if _obs.enabled:
            _obs.counter("ops.route.general").inc()
            _obs.counter("ops.general.lanes").inc(count)
            _obs.counter("ops.general.postvoid_lanes").inc(pv_count)
        codes = np.asarray(codes)
        self._transfers_bound += count
        self._posted_bound += pv_count
        self._history_bound += hist_count
        with txtrace.stage("index_append"):
            if index_feed is None:
                self._index_lazy_reset()
            else:
                self._index_append_device(*index_feed)
        results = self._compress(codes, count)
        if _obs.enabled:
            _obs.counter("ops.general.rejected_lanes").inc(len(results))
        self._update_commit_timestamp(codes, count, timestamp)
        return results

    def _record_wave_metrics(self, wave_host) -> None:
        """Wave occupancy series (docs/observability.md): wave_host is the
        kernel's int32[11] = (passes, bound, hist[9]) profile vector."""
        passes, bound = int(wave_host[0]), int(wave_host[1])
        hist = [int(v) for v in wave_host[2:]]
        # Every lane but wave 0's is a hazard lane: one whose result a limit,
        # a balancing clamp or a near-overflow balance makes depend on
        # earlier lanes of its batch (`_kernel_core`'s `hazard`).
        _obs.counter("ops.general.limit_lanes").inc(sum(hist[1:]))
        if bound > 0:
            _obs.counter("waves.batches_scheduled").inc()
            _obs.histogram("waves.bound_passes", "passes").observe(bound)
        else:
            _obs.counter("waves.batches_unscheduled").inc()
        _obs.histogram("waves.jacobi_passes", "passes").observe(passes)
        total = sum(hist)
        if total:
            _obs.histogram("waves.wave0_pct", "%").observe(
                100 * hist[0] // total
            )

    def _sharded_commit_transfers(
        self, batch: np.ndarray, timestamp: int, count: int
    ) -> List[Tuple[int, int]]:
        """The sharded live commit path (docs/sharding.md): cross-shard
        transfers settle through a two-phase split inside the jitted step —
        each shard probes/validates its local partition (the debit and
        credit legs of a cross-shard lane resolve on different shards), ONE
        psum-combined context exchange carries every leg's outcome to every
        shard, the pure validation core runs replicated, and balances/
        inserts apply owner-locally.  Result codes and balances are
        byte-identical to the single-device kernels; linked chains, in-batch
        pending refs, and history accounts fall back to the sequential path
        exactly like the wave scheduler's unschedulable exit."""
        if self._tiering or self.cold.count:
            # The mesh kernels carry no bloom, so a cold (evicted) id
            # would silently read as not-found there.  Tiered transfer
            # commits route through the sequential fallback's canonical
            # window, where the existing host-exact cold resolution
            # (_resolve_cold) applies unchanged — correctness over
            # throughput while the tier is active.
            return self._sequential("create_transfers", batch, timestamp)

        with txtrace.stage("route"):
            self._note_cross_shard(batch, count)
            self._note_shard_inserts("transfers", batch, count)
            fast = self._fast_path_ok(batch)
        if fast:
            if _obs.enabled:
                _obs.counter("ops.route.fast").inc()
            with txtrace.stage("grow"):
                self._grow_if_needed(transfers=count)
            with txtrace.stage("stage_h2d"):
                staged = self._stage_sharded(batch, timestamp)
            with txtrace.stage("dispatch"):
                self.ledger, codes = self._shard_steps["fast"](
                    self.ledger, *staged
                )
            codes, overflow = self._d2h_codes(
                codes, self.ledger.transfers.probe_overflow
            )
            self._transfers_bound += count
            if bool(np.any(overflow)):
                raise RuntimeError(
                    "transfers probe overflow during fast insert"
                )
            if _obs.enabled:
                _obs.counter("sharding.batches").inc()
            self._index_lazy_reset()
            results = self._compress(codes, count)
            self._update_commit_timestamp(codes, count, timestamp)
            return results

        with txtrace.stage("general_commit", n=count):
            return self._sharded_commit_general(batch, timestamp, count)

    def _sharded_commit_general(
        self, batch: np.ndarray, timestamp: int, count: int
    ) -> List[Tuple[int, int]]:
        """The sharded general (Jacobi) program's route, blocking on the
        calling thread like ``_commit_general``, under the same spans: one
        shard_map dispatch and one device sync per attempt."""
        from .ops import transfer_full as tf

        pv_count, hist_count = self._transfer_growth_counts(batch)
        with txtrace.stage("grow"):
            self._grow_if_needed(
                transfers=count, posted=pv_count, history=hist_count
            )
        with txtrace.stage("stage_h2d"):
            staged = self._stage_sharded(batch, timestamp)
        use_waves = self.waves_enabled
        step = self._shard_steps["full_waves" if use_waves else "full"]
        for _attempt in range(8):
            with txtrace.stage("dispatch"):
                r = step(self.ledger, *staged)
            self.ledger, codes, kflags = r[0], r[1], r[2]
            wave_vec = r[3] if use_waves else None
            kflags, wave_host, _ = self._full_kflags_sync(kflags, wave_vec)
            if kflags == 0:
                if _obs.enabled:
                    _obs.counter("sharding.batches").inc()
                # No index feed: the index append is a reset under shards.
                return self._full_commit_success(
                    codes, count, pv_count, hist_count, timestamp, wave_host,
                )
            if kflags & tf.FLAG_SEQ:
                # Order-dependent (linked / balancing-chain / limit
                # cascade), in-batch pending refs, or history accounts:
                # the unschedulable exit.
                if _obs.enabled:
                    _obs.counter("ops.general.seq_handovers").inc()
                return self._sequential("create_transfers", batch, timestamp)
            # No FLAG_COLD on the mesh path (tiering is single-device);
            # remaining bits are probe-overflow growth requests.
            self._grow_flagged(kflags)
        raise RuntimeError(
            "sharded transfer kernel could not place batch after growth"
        )

    def _note_shard_inserts(self, which: str, batch: np.ndarray,
                            count: int):
        """Advance the per-shard attempted-insert bound for ``which`` by
        this batch's id owners (over-approximation, like the global
        bounds: rejected lanes still count).  Called BEFORE the growth
        decision, mirroring the global bound+count discipline.  Returns
        the per-shard owner counts (None off the mesh) — the deferred
        dispatch path records them as pipeline.shard.* lane occupancy."""
        if self._shard_mesh is None or count == 0:
            return None
        from .ops.scrub import mix64_np

        owners = (
            mix64_np(
                batch["id_lo"][:count].astype(np.uint64),
                batch["id_hi"][:count].astype(np.uint64),
            ) & np.uint64(self.shards - 1)
        ).astype(np.int64)
        counts = np.bincount(owners, minlength=self.shards)
        self._shard_insert_bounds[which] += counts
        return counts

    def _refresh_shard_bounds(self, ledger) -> None:
        """Re-floor the per-shard bounds at the actual live per-shard
        counts (external install, sequential-fallback reshard, recovery)
        — the same floor discipline restore_host_state applies to the
        global bounds."""
        if self._shard_mesh is None:
            return
        self._shard_insert_bounds = {
            "accounts": np.asarray(ledger.accounts.count).astype(np.int64),
            "transfers": np.asarray(ledger.transfers.count).astype(np.int64),
        }

    def _note_cross_shard(self, batch: np.ndarray, count: int) -> None:
        """Cross-shard accounting, host-side (one mix64 pass per side): a
        lane whose debit and credit accounts hash to different owners
        settles through the psum leg exchange (docs/sharding.md).  Post/
        void lanes carry zero account ids on both sides and count as
        same-shard — the pending legs they resolve were classified when
        the pending transfer committed."""
        from .ops.scrub import mix64_np

        mask = np.uint64(self.shards - 1)
        dr = mix64_np(
            batch["debit_account_id_lo"].astype(np.uint64),
            batch["debit_account_id_hi"].astype(np.uint64),
        ) & mask
        cr = mix64_np(
            batch["credit_account_id_lo"].astype(np.uint64),
            batch["credit_account_id_hi"].astype(np.uint64),
        ) & mask
        cross = int((dr != cr).sum())
        self.shard_lanes_total += count
        self.shard_lanes_cross += cross
        if _obs.enabled:
            _obs.counter("sharding.lanes").inc(count)
            _obs.counter("sharding.cross_shard_lanes").inc(cross)
            _obs.histogram("sharding.cross_shard_pct", "%").observe(
                100 * cross // max(count, 1)
            )

    # -- online shard split (docs/reconfiguration.md) ------------------------
    #
    # An N -> 2N split executed WHILE SERVING: the old layout keeps
    # committing; between batches the engine ships the owner-changed row
    # subset through the vsr/statesync codec (per-chunk Merkle
    # verification against the source tree), catches up changed slots in
    # delta rounds, and cuts over only after the staged full state passes
    # the whole-state checksum gate AND the new layout's per-shard scrub
    # lanes fold to the canonical digest.  Any verification failure
    # abandons the split and keeps serving the old layout — graceful
    # degradation, never a wedge.  Migration state is volatile by design:
    # a crash mid-migration restarts on the old layout (clean rollback)
    # and the split is simply re-armed.

    @property
    def reshard_active(self) -> bool:
        return self._reshard is not None

    def reshard_begin(
        self, target_shards: int, *, verify: bool = True,
        chunk_rows: int = 512, corrupt_chunks=(), corrupt_persistent=False,
    ) -> bool:
        """Arm an online N -> 2N shard split.  Returns True when the
        migration is armed (idempotent while one is in flight); False —
        counted, logged, never a wedge — when this machine cannot split.
        ``corrupt_chunks``/``corrupt_persistent`` are fault-injection
        hooks (VOPR reconfig kind): flip a byte in the numbered migration
        chunks, transiently or on every retry."""
        if self._reshard is not None:
            return True
        reason = None
        if self.shards < 2 or self._shard_mesh is None:
            reason = "machine is not in sharded mode"
        elif target_shards != self.shards * 2:
            reason = f"{self.shards} -> {target_shards} is not a doubling"
        elif self._engine is not None:
            reason = "host engine is the commit authority"
        elif self._tiering or self.cold.count:
            reason = "cold tier active (evicted rows have no leaves)"
        elif len(jax.devices()) < target_shards:
            reason = (
                f"{target_shards} shards need {target_shards} devices, "
                f"have {len(jax.devices())}"
            )
        else:
            for cap in (self.config.accounts_capacity,
                        self.config.transfers_capacity,
                        self.config.posted_capacity):
                if cap % target_shards:
                    reason = "capacity not divisible by the target shards"
        if reason is not None:
            self.reshard_stats["abandons"] += 1
            if _obs.enabled:
                _obs.counter("reconfig.reshard_abandoned").inc()
            warnings.warn(
                f"shard split refused: {reason} (serving continues on the "
                f"current layout)", RuntimeWarning, stacklevel=2,
            )
            return False
        self.reshard_stats["splits_started"] += 1
        self._reshard = {
            "target": int(target_shards), "verify": bool(verify),
            "chunk_rows": int(chunk_rows), "round": 0, "queue": [],
            "src": None, "trees": None, "wire": None,
            "shipped_leaves": None, "shipped_mask": None, "chunks_sent": 0,
            "corrupt_chunks": set(int(c) for c in corrupt_chunks),
            "corrupt_persistent": bool(corrupt_persistent),
        }
        if _obs.enabled:
            _obs.counter("reconfig.reshard_started").inc()
            _obs.gauge("reconfig.reshard_active").set(1)
        return True

    def reshard_abort(self) -> None:
        """Operator abort: drop the migration, keep serving the old
        layout untouched."""
        if self._reshard is not None:
            self._reshard_abandon("operator abort")

    def reshard_step(self, max_chunks: int = 8) -> str:
        """Advance an active split by up to ``max_chunks`` verified
        migration chunks; call between commit batches (the replica tick /
        VOPR driver seam).  Returns 'idle' (no split), 'migrating',
        'done' (cutover installed this step) or 'abandoned'."""
        rs = self._reshard
        if rs is None:
            return "idle"
        from .vsr import statesync as _ss  # lazy: machine sits below vsr

        for _ in range(max_chunks):
            rs = self._reshard
            if rs is None:
                return "abandoned"
            if not rs["queue"]:
                status = self._reshard_advance()
                if status != "migrating":
                    return status
                continue
            pad, slots = rs["queue"].pop(0)
            tree = rs["trees"][pad]
            cap = _ss.pad_capacity(rs["src"], pad)
            chunk_id = rs["chunks_sent"]
            rows = None
            for attempt in (0, 1):
                corrupt = chunk_id in rs["corrupt_chunks"] and (
                    attempt == 0 or rs["corrupt_persistent"]
                )
                body = _ss.ship_chunk(
                    rs["src"], tree, pad, slots, corrupt=corrupt
                )
                if not rs["verify"]:
                    # Scrub-off negative control: install unaudited.
                    rows = _ss.unpack_rows(rs["src"], pad, slots, body)
                    break
                rows = _ss.verify_chunk(rs["src"], tree, pad, slots, body)
                if rows is not None:
                    break
                self.reshard_stats["chunk_retries"] += 1
                if _obs.enabled:
                    _obs.counter("reconfig.chunk_retries").inc()
            if rows is None:
                return self._reshard_abandon(
                    f"chunk {chunk_id} ({pad}) failed verification twice"
                )
            for k in _ss.per_slot_keys(rs["src"], pad):
                rs["wire"][pad][k][slots] = rows[k]
            # Record the SOURCE leaf as shipped even unaudited: with
            # verification off a corrupted chunk must stay divergent all
            # the way to cutover (the auditor's job to catch), not be
            # silently re-shipped clean next round.
            rs["shipped_leaves"][pad][slots] = tree[cap + slots]
            rs["shipped_mask"][pad][slots] = True
            rs["chunks_sent"] += 1
            self.reshard_stats["chunks"] += 1
            self.reshard_stats["bytes_migrated"] += len(body)
            if _obs.enabled:
                _obs.counter("reconfig.bytes_migrated").inc(len(body))
        return "migrating"

    def _reshard_snapshot(self):
        """Fresh canonical flat-array snapshot + trees (the statesync
        responder's view of THIS machine's live state)."""
        from .vsr import checkpoint as _ckpt
        from .vsr import statesync as _ss

        arrays = {
            k: np.asarray(v)
            for k, v in _ckpt.ledger_to_arrays(self.checkpoint_ledger()).items()
        }
        return arrays, _ss.build_trees(arrays)

    def _reshard_advance(self) -> str:
        """Queue drained: take a fresh snapshot, enqueue the moved slots
        whose leaves changed since their last ship (delta round), or cut
        over when a round comes back empty."""
        from .parallel import sharded as shard_mod
        from .vsr import statesync as _ss

        rs = self._reshard
        arrays, trees = self._reshard_snapshot()
        if rs["src"] is not None and any(
            _ss.pad_capacity(arrays, pad) != _ss.pad_capacity(rs["src"], pad)
            for pad in _ss.PADS
        ):
            # A table grew mid-migration: leaf indexes are incomparable
            # across capacities — restart the split from scratch (counted;
            # the old layout served throughout).
            self.reshard_stats["restarts"] += 1
            if _obs.enabled:
                _obs.counter("reconfig.reshard_restarts").inc()
            rs["wire"] = None
        if rs["wire"] is None:
            rs["wire"] = {
                pad: {
                    k: np.zeros_like(arrays[k])
                    for k in _ss.per_slot_keys(arrays, pad)
                }
                for pad in _ss.PADS
            }
            rs["shipped_leaves"] = {
                pad: np.zeros(_ss.pad_capacity(arrays, pad), np.uint64)
                for pad in _ss.PADS
            }
            rs["shipped_mask"] = {
                pad: np.zeros(_ss.pad_capacity(arrays, pad), bool)
                for pad in _ss.PADS
            }
            rs["round"] = 0
            # Full-transfer baseline the differential protocol is judged
            # against: every live row of every pad.
            self.reshard_stats["bytes_full"] = sum(
                int((
                    (arrays[f"{pad}/key_lo"] | arrays[f"{pad}/key_hi"]) != 0
                ).sum()) * _ss.row_bytes(arrays, pad)
                for pad in _ss.PADS
            )
        queue = []
        for pad in _ss.PADS:
            cap = _ss.pad_capacity(arrays, pad)
            moved = shard_mod.split_moved_mask(
                arrays[f"{pad}/key_lo"], arrays[f"{pad}/key_hi"], self.shards
            )
            leaves = trees[pad][cap:]
            need = moved & (
                ~rs["shipped_mask"][pad]
                | (leaves != rs["shipped_leaves"][pad])
            )
            for piece in _ss.chunk_slots(
                np.nonzero(need)[0], rs["chunk_rows"]
            ):
                queue.append((pad, piece))
        rs["src"], rs["trees"] = arrays, trees
        if not queue:
            return self._reshard_cutover(arrays, trees)
        rs["queue"] = queue
        if rs["round"] > 0:
            self.reshard_stats["catchup_rounds"] += 1
        rs["round"] += 1
        return "migrating"

    def _reshard_cutover(self, arrays, trees) -> str:
        """The cutover rule (docs/reconfiguration.md): staged state =
        stayed rows (never left their device) + wire rows (each chunk
        Merkle-verified); it must pass the whole-state checksum gate, and
        the NEW layout's per-shard scrub lanes must fold to the canonical
        digest, before the swap.  Any gate failure abandons — the old
        layout was never touched."""
        from jax.sharding import Mesh

        from .parallel import sharded as shard_mod
        from .vsr import checkpoint as _ckpt
        from .vsr import statesync as _ss

        rs = self._reshard
        staged = {k: v.copy() for k, v in arrays.items()}
        for pad in _ss.PADS:
            moved = shard_mod.split_moved_mask(
                arrays[f"{pad}/key_lo"], arrays[f"{pad}/key_hi"], self.shards
            )
            slots = np.nonzero(moved)[0]
            for k in _ss.per_slot_keys(arrays, pad):
                staged[k][slots] = rs["wire"][pad][k][slots]
        if rs["verify"] and (
            _ss.arrays_checksum(staged) != _ss.arrays_checksum(arrays)
        ):
            return self._reshard_abandon("whole-state checksum gate failed")
        digest_want = _ss.np_digest(arrays)
        devs = jax.devices()
        new_mesh = Mesh(np.array(devs[: rs["target"]]), (shard_mod.AXIS,))
        new_steps = shard_mod.machine_steps(
            new_mesh, self.config.jacobi_max_passes
        )
        sharded_led = shard_mod.shard_ledger(
            _ckpt.arrays_to_ledger(staged), new_mesh
        )
        # Per-shard commitment gate: the 2N scrub lanes (wrap-add partial
        # folds, one per shard) must sum to the canonical accounts digest.
        lanes = np.asarray(new_steps["scrub"](sharded_led)).astype(np.uint64)
        with np.errstate(over="ignore"):
            got = int(lanes[:, 0].sum(dtype=np.uint64))
        if rs["verify"] and got != digest_want:
            return self._reshard_abandon(
                "per-shard commitment roots do not fold to the canonical "
                "digest"
            )
        old_shards = self.shards
        self.shards = rs["target"]
        self._shard_mesh = new_mesh
        self._shard_steps = new_steps
        self._ledger = sharded_led  # already placed on the new mesh
        self._ledger_is_sharded = True
        self._canon = None
        self._refresh_shard_bounds(sharded_led)
        self._merkle_mark_dirty()
        # First dispatches on the 2N mesh legitimately jit-compile: the
        # TB_SANITIZE recompile tripwire gets the same grace as growth.
        self._sanitize_grace = True
        self._sanitize_soft = True
        self.reshard_stats["splits_completed"] += 1
        audited = rs["verify"]
        self._reshard = None
        if _obs.enabled:
            _obs.counter("reconfig.reshard_completed").inc()
            _obs.gauge("reconfig.reshard_active").set(0)
            _obs.gauge("sharding.shards").set(self.shards)
        if audited:
            # Converter sanity on the audited path only: with verification
            # disabled (the scrub-off negative control) an installed
            # divergence is the AUDITOR's to catch downstream.
            assert int(self.digest()) == digest_want, (
                f"post-cutover digest diverged after {old_shards} -> "
                f"{self.shards} split"
            )
        return "done"

    def _reshard_abandon(self, reason: str) -> str:
        self.reshard_stats["abandons"] += 1
        self._reshard = None
        if _obs.enabled:
            _obs.counter("reconfig.reshard_abandoned").inc()
            _obs.gauge("reconfig.reshard_active").set(0)
        warnings.warn(
            f"shard split abandoned: {reason} (serving continues on the "
            f"{self.shards}-shard layout)", RuntimeWarning, stacklevel=3,
        )
        return "abandoned"

    def _note_balance_bound(self, batch: np.ndarray) -> None:
        """Over-approximate the largest possible single balance field after
        this batch (fast-path precondition P3: the overflow ladder cannot
        fire below 2^126). Non-balancing amounts add at most count * max.
        A balancing lane's clamp is NOT bounded by the pre-batch balance
        (chained balancing lanes in one batch compound against the running
        balance), but it IS capped at u64-max per lane: a zero-amount
        balancing transfer's ceiling is maxInt(u64) (transfer_full.py
        amount0; state_machine.zig:1288), and a nonzero amount is already
        counted under count * max. Ledgers that blow the bound just lose
        the fast path — correctness never depends on it."""
        if self._balance_bound >= _BOUND_CLAMP or len(batch) == 0:
            return
        mx = (int(batch["amount_hi"].max()) << 64) | int(batch["amount_lo"].max())
        n_bal = int((
            (batch["flags"]
             & (types.TransferFlags.BALANCING_DEBIT
                | types.TransferFlags.BALANCING_CREDIT)) != 0
        ).sum())
        self._balance_bound += len(batch) * mx + n_bal * ((1 << 64) - 1)
        if self._balance_bound > _BOUND_CLAMP:
            self._balance_bound = _BOUND_CLAMP

    def _fast_path_ok(self, batch: np.ndarray) -> bool:
        """Plain-transfer batches run the fast kernel.  Measured on one v5e
        at the control's table sizes (PERF_LEDGER.jsonl, PR 46; 8190-event
        batches): an execution of the general program is 61.0 ms
        (`general_kernel_ms`), a grouped fast step 23.8 ms
        (`kernel_ms_per_batch`), so a batch that can take the fast kernel
        does.  The preconditions are ops/state_machine.py's P1-P4, checked
        host-side in a few vector ops over the batch."""
        if (
            self._tiering
            or self._history_accounts_possible
            or self._limit_accounts_possible
            or self._balance_bound >= (1 << 126)
        ):
            return False
        if bool((batch["flags"] & _SLOW_TRANSFER_FLAGS).any()):
            return False
        if bool(batch["amount_hi"].any()):
            return False
        return True

    # -- commit-path switches -----------------------------------------------

    @property
    def waves_enabled(self) -> bool:
        """Conflict-index wave scheduler for the general commit kernel
        (TB_WAVES env; DEFAULT ON since the PR 10 soak — the pinned
        regression seed set replayed green under TB_WAVES=1 x TB_SHARDS
        {0, 2}, WAVES_SOAK.json; docs/waves.md records the decision).
        TB_WAVES=0 is bit-for-bit the pre-waves path — the kernel
        compiles the exact pre-waves program.  On, the general kernel
        computes a per-batch conflict index over the touched
        (debit, credit) account slots and commits certified batches after
        a PROVED number of Jacobi passes instead of waiting for the
        stability pass — same codes, same balances (docs/waves.md)."""
        if self._waves_enabled is None:
            import os

            self._waves_enabled = os.environ.get("TB_WAVES", "1") != "0"
        return self._waves_enabled

    @waves_enabled.setter
    def waves_enabled(self, value: bool) -> None:
        self._waves_enabled = bool(value)

    @property
    def merkle_async(self) -> bool:
        """Deferred commitment lane (TB_MERKLE_ASYNC env only, default
        OFF).  On, committed batches enqueue
        touched-row records instead of paying the O(batch * log cap)
        leaf->root refresh inside the dispatch closure; merkle_settle()
        drains the lane at every point a maintained root is observed
        (scrub check, get_proof, reply-root stamping, merkle_roots), so
        roots remain exactly as certified today — they just no longer
        serialize the commit stream.  Off is bit-identical pre-lane
        behavior.  No-op unless TB_MERKLE is armed."""
        if self._merkle_async is None:
            import os

            self._merkle_async = os.environ.get("TB_MERKLE_ASYNC", "") == "1"
        return self._merkle_async

    @merkle_async.setter
    def merkle_async(self, value: bool) -> None:
        value = bool(value)
        if not value and self._merkle_async and self._merkle_pending:
            # Turning the lane off must not strand queued records (callers
            # toggle at quiescent points: setup, tests).
            self.merkle_settle()
        self._merkle_async = value

    @property
    def pipeline_depth(self) -> int:
        """Deferred-readback depth (TB_PIPELINE env, default 2; the CLI's
        --pipeline-depth overrides).  Depth 1 disables deferral — every
        commit blocks on its own codes readback, exactly the pre-pipeline
        serving path; depth >= 2 pipelines one commit group (deeper
        values reserved, currently equivalent to 2)."""
        if self._pipeline_depth is None:
            self._pipeline_depth = pipeline_depth_default()
        return self._pipeline_depth

    @pipeline_depth.setter
    def pipeline_depth(self, value: int) -> None:
        self._pipeline_depth = max(1, int(value))

    def _dispatch_lane(self):
        """The single-thread FIFO executor deferred dispatches run on.

        On backends whose execute BLOCKS the dispatching thread (XLA-CPU:
        jax runs the computation synchronously inside the call), a deferred
        handle alone overlaps nothing — the lane restores the async-
        dispatch property: device execute happens GIL-free on this thread
        while the serving thread journals, stages the next upload, and
        builds replies.  On a TPU the jitted commit call returns once it is
        enqueued (2-3 ms), but the closure then sits in its index appends
        until the device has run the commit (_lane_dispatch): the lane is
        not "one cheap hop", it is where the device wait shows.  ONE worker
        == dispatch order == op order; growth rides each closure so the
        ledger chain never interleaves."""
        if self._lane is None:
            import concurrent.futures

            self._lane = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tb-dispatch"
            )
        return self._lane

    def _lane_dispatch(self, dispatch, deferred, seq=0):
        """Run (deferred=False) or submit (deferred=True) a commit closure,
        timed as the ``device_execute`` stage on the thread that runs it,
        with ``grow`` / ``dispatch`` / ``index_append`` as its children.
        On XLA-CPU the jitted calls may compute synchronously inside the
        closure.  On a TPU only the ``dispatch`` child is an enqueue
        (2-4 ms); ``index_append`` then holds the thread until the
        device has run the commit and the appends queued behind it (all
        but 4 ms of a grouped closure's 0.54-0.62 s, and so the same in
        the serving thread's join, ``dispatch_wait``; ``readback`` is left
        with under 1 ms after a grouped closure — one TPU v5 lite, PR 27,
        PERF.md section 5).  The lane thread's observations land in the
        same process-global ledger.  ``seq``: the submitting group's (the
        lane runs later)."""
        if not txtrace.active:
            return (
                self._dispatch_lane().submit(dispatch) if deferred
                else dispatch()
            )

        def staged():
            with txtrace.stage("device_execute", seq=seq):
                return dispatch()

        return (
            self._dispatch_lane().submit(staged) if deferred else staged()
        )

    # The cap on a grouped dispatch's run.  A group pads its stack with
    # zero-count rows and the program's loop stops at the first of them, so
    # a group of k costs k steps (a padded step would cost most of what a
    # full one does: the kernel's table-sized work does not depend on the
    # count).  Amortizing a run over one dispatch + one readback keeps the
    # device serving path off the per-dispatch host round trip.
    GROUP_K = 32
    # The stack's leading dimension for a SHORT run: the upload goes by the
    # stack's bytes, not the run's (1.08 MB a row), and with 8 sessions no
    # run is longer than 8.  One jit variant for each of the two leading
    # dimensions, both warmed at startup.
    GROUP_ROWS_SHORT = 8

    def _group_rows(self, k: int) -> int:
        """The leading dimension of a grouped run's staged stack, from the
        run's length: the short stack where it fits, GROUP_K beyond."""
        short = self.GROUP_ROWS_SHORT
        return short if k <= short < self.GROUP_K else self.GROUP_K

    def commit_group_fast(
        self, batches: List[np.ndarray], timestamps: List[int],
        deferred: bool = False,
    ):
        """Commit a RUN of fast-path-eligible create_transfers batches in
        ONE device dispatch (a loop over the stacked batches that runs
        len(batches) steps, not GROUP_K) with ONE device->host codes
        transfer.

        Returns per-batch results index-aligned with ``batches``, or None
        when the run is not groupable — caller falls back to per-batch
        commits.  Loop order == batch order, and each batch carries its
        own already-assigned prepare timestamp, so results are
        bit-identical to committing the run batch by batch.

        ``deferred=True`` returns a DeviceCommitHandle instead of blocking
        on the codes readback: the dispatch is in flight, and the caller
        resolves the handle (in dispatch order) when it needs the results
        — dispatch N+1 then overlaps readback N."""
        if (
            self._engine is not None
            or self.force_sequential
            or not (2 <= len(batches) <= self.GROUP_K)
        ):
            return None
        counts = [len(b) for b in batches]
        if any(c == 0 or c > self.batch_lanes for c in counts):
            return None
        # Eligibility is ORDER-dependent (the balance bound grows per
        # batch): note bounds exactly as the per-batch path would.  On a
        # mid-run refusal, restore the entry bound — the per-batch fallback
        # re-notes every batch itself, and double-counting the prefix would
        # ratchet the monotonic bound toward the 2^126 threshold and
        # permanently cost the fast path (ADVICE r4).
        bound0 = self._balance_bound
        with txtrace.stage("route", n=len(batches)):
            for b in batches:
                self._note_balance_bound(b)
                if not self._fast_path_ok(b):
                    self._balance_bound = bound0
                    return None
        if timestamps[-1] > self.prepare_timestamp:
            # Replay/backup parity with commit_batch's clock catch-up.
            self.prepare_timestamp = timestamps[-1]
        self._scrub_maybe_check()  # no-op unless armed, due, and lane idle
        if _obs.enabled:
            _obs.counter("ops.route.grouped").inc(len(batches))
        if self._ledger_is_sharded:
            # Grouped stacking over the mesh (docs/sharding.md
            # composition): K per-batch shard_map dispatches inside ONE
            # lane closure, ONE deferred readback for the whole run.
            return self._commit_group_fast_sharded(
                batches, timestamps, counts, deferred
            )
        k = len(batches)
        if _obs.enabled:
            # Batches held: the loop runs one step a batch.
            _obs.counter("ops.group.batches").inc(k)
        seq = txtrace.group_seq  # for the closure's spans on the lane
        with txtrace.stage("stage_h2d", n=k):
            staged = staging.stage_group(
                batches, self.batch_lanes, timestamps, self._group_rows(k)
            )
        # Host row bounds advance at SUBMIT (not readback): the next
        # group's growth decision must see this group's inserts coming,
        # and the closure's growth target is snapshotted HERE so it never
        # depends on how far the serving thread raced ahead.
        need = self._transfers_bound + sum(counts)
        for c in counts:
            self._transfers_bound += c
        # TB_MERKLE_ASYNC: the knob is read ONCE here on the serving
        # thread — the closure must not re-read it at execute time (a
        # toggle racing an in-flight lane would split one run's updates
        # across modes).
        merkle_closure = self._merkle_forest is not None and not self.merkle_async

        def dispatch():
            # Growth + dispatch + index maintenance stay ONE unit on the
            # FIFO lane: it preserves the ledger chain and the order of the
            # index's runs.  The appends read the keys the program returns,
            # not the ledger (a FieldIndex of ops/scan_builder.py, where a
            # query has made one, still probes THIS ledger).
            with txtrace.stage("grow", seq=seq):
                self._grow_if_needed(transfers_need=need)
            # The ONE-worker FIFO lane orders every ledger write, and the
            # serving thread reads self.ledger only after resolve()'s join
            # (or lane.shutdown(wait=True) in reset paths).
            with txtrace.stage("dispatch", seq=seq, n=k):
                (self.ledger, codes, overflow,  # tblint: ignore[lane-race] FIFO+join
                 *index_feed) = _group_fast_dispatch(self.ledger, *staged)
            with txtrace.stage("index_append", seq=seq, n=k):
                for j in range(k):
                    self._index_append_device(*index_feed, row=j)
            if merkle_closure:
                # Commitment updates ride the ledger chain on the lane,
                # PER BATCH: one key-size class per workload shape, so
                # variable run lengths never hit fresh jit variants
                # mid-serving (concatenating the run would key the update
                # program on k — a compile per distinct run length).
                for j in range(k):
                    self._merkle_update_transfers_batches([batches[j]])
            return codes, overflow

        armed_mirror = self._scrub_mirror is not None
        armed = armed_mirror or self._merkle_forest is not None
        result = self._lane_dispatch(dispatch, deferred, seq)
        handle = DeviceCommitHandle(
            self, result, counts, timestamps, stacked=True,
            # Batch retention feeds mirror recovery re-dispatch; the
            # forest needs no retention (a mismatch escalates to the
            # durable-state rebuild instead).
            batches=list(batches) if armed_mirror else None,
            deferred=deferred,
        )
        if self._merkle_forest is not None and not merkle_closure:
            # Deferred commitment lane: queue the run's touch records on
            # the serving thread; settle barriers replay them in order.
            for b in batches:
                self._merkle_lane_enqueue("create_transfers", b)
        if deferred:
            self._deferred_submitted(sum(counts))
        if armed:
            self._inflight_handles.append(handle)
        if deferred:
            return handle
        return handle.resolve()  # ONE D2H for the whole group

    def _commit_group_fast_sharded(self, batches, timestamps, counts,
                                   deferred):
        """Grouped/deferred commit stacking over the mesh (the async
        sharded engine, docs/sharding.md composition section): ONE
        dispatch-lane closure stages each batch of the run and enqueues the
        cached ``sharded.machine_steps`` fast_probed program on it, one
        batch after the other: stage 0, dispatch 0, stage 1, dispatch 1,
        ...  Under ``--shards`` a run is K separate executions, not one
        loop over a stack, and execution j needs batch j alone: staged
        right before its own enqueue (``_stage_sharded``: 2.5-4 ms a batch
        and 1.3-1.8 ms an enqueue on a four-chip v5e host, against 22 ms of
        device time an execution), the device starts on batch 0 while the
        lane stages the others; staged up front at submit it would sit
        through all K stagings before the first execution (27-30 ms of a 45
        ms gap a run; PERF.md PR 40).  The one-chip grouped route is ONE
        loop over the stacked batches, needs all K before it can start and
        keeps its staging at submit (``commit_group_fast``).

        What stays at SUBMIT, on the serving thread: the ``route`` pass, the
        growth snapshot and ``_transfers_bound``.  What rides the lane, in
        FIFO order: growth, then per batch the staging, the per-batch
        shard_map dispatch (the loop-grouped single-device program would
        re-trace per mesh layout; the per-shard lanes are the parallelism
        lever here) with the ledger chain threaded through, the lazy
        index's reset and the Merkle update; ONE deferred D2H readback
        (codes + per-shard overflow lanes) serves the whole run.
        ``deferred=False`` runs the same closure inline.  The closure reads
        ``batches[j]`` on the lane (the staging, the Merkle update): the
        caller must leave the batch bodies alone until the handle resolves.
        A staging that fails on the lane surfaces as a failed enqueue does:
        out of the handle's ``resolve``; the ledger chain stands where the
        last good execution left it.  Results are bit-identical to
        committing the run batch by batch through the blocking sharded fast
        path."""
        k = len(batches)
        total = 0
        owner_sum = np.zeros(max(self.shards, 1), np.int64)
        with txtrace.stage("route", n=k):
            for b, c in zip(batches, counts):
                self._note_cross_shard(b, c)
                owners = self._note_shard_inserts("transfers", b, c)
                if owners is not None:
                    owner_sum += owners
                total += c
        if _obs.enabled:
            # K per-batch dispatches, no padded steps on this route.
            _obs.counter("ops.group.batches").inc(k)
        seq = txtrace.group_seq  # for the closure's spans on the lane
        # Submit-time growth snapshot (see commit_group_fast / the
        # shard_bounds note in _grow_if_needed).
        need = self._transfers_bound + total
        self._transfers_bound += total
        snap = {name: v.copy()
                for name, v in self._shard_insert_bounds.items()}
        step = self._shard_steps["fast_probed"]
        # Knob read once at submit (see commit_group_fast).
        merkle_closure = self._merkle_forest is not None and not self.merkle_async

        def dispatch():
            with txtrace.stage("grow", seq=seq):
                self._grow_if_needed(transfers_need=need, shard_bounds=snap)
            codes_out, ovf_out = [], []
            for j in range(k):
                # Staged HERE, right before its own enqueue: the device
                # runs batch j-1 meanwhile.
                with txtrace.stage("stage_h2d", seq=seq):
                    staged = self._stage_sharded(batches[j], timestamps[j])
                # Same handoff as the single-device closure above: ONE
                # FIFO lane worker, serving-thread reads behind the join.
                with txtrace.stage("dispatch", seq=seq):
                    self.ledger, codes, overflow = step(  # tblint: ignore[lane-race] FIFO+join
                        self.ledger, *staged
                    )
                with txtrace.stage("index_append", seq=seq):
                    self._index_lazy_reset()
                if merkle_closure:
                    self._merkle_update_transfers_batches([batches[j]])
                codes_out.append(codes)
                ovf_out.append(overflow)
            if _obs.enabled:
                _obs.counter("sharding.batches").inc(k)
                _obs.counter("sharding.staged.lane").inc(k)
            return tuple(codes_out), tuple(ovf_out)

        armed_mirror = self._scrub_mirror is not None
        armed = armed_mirror or self._merkle_forest is not None
        result = self._lane_dispatch(dispatch, deferred, seq)
        handle = DeviceCommitHandle(
            self, result, list(counts), list(timestamps), stacked=True,
            batches=list(batches) if armed_mirror else None,
            deferred=deferred,
        )
        if self._merkle_forest is not None and not merkle_closure:
            for b in batches:
                self._merkle_lane_enqueue("create_transfers", b)
        if deferred:
            self._deferred_submitted(total, owner_sum)
        if armed:
            self._inflight_handles.append(handle)
        if deferred:
            return handle
        return handle.resolve()  # ONE D2H for the whole run

    def _commit_fast(
        self, batch: np.ndarray, timestamp: int, count: int
    ) -> List[Tuple[int, int]]:
        if _obs.enabled:
            _obs.counter("ops.route.fast").inc()
        self._grow_if_needed(transfers=count)
        with txtrace.stage("stage_h2d"):
            staged = self._stage(batch, timestamp)
        self.ledger, codes = sm.create_transfers_fast(self.ledger, *staged)
        # Overflow flag rides the codes readback: one sync, not two.
        codes, overflow = self._d2h_codes(
            codes, self.ledger.transfers.probe_overflow
        )
        self._transfers_bound += count
        if int(overflow):
            # Load-factor management keeps this unreachable; losing inserts
            # silently is the one unacceptable outcome, so fail loud.
            raise RuntimeError("transfers probe overflow during fast insert")
        self._index_append(staged, codes, count)
        results = self._compress(codes, count)
        self._update_commit_timestamp(codes, count, timestamp)
        return results

    def commit_fast_deferred(
        self, batch: np.ndarray, timestamp: int
    ) -> Optional[DeviceCommitHandle]:
        """Dispatch ONE fast-path create_transfers batch and return a
        deferred readback handle, or None when the batch is not fast-path
        eligible (caller falls back to the blocking commit_batch path).

        Semantically identical to the _commit_fast route — same kernel
        body, same codes, same bookkeeping — only the readback timing
        moves: the probed kernel variant carries the overflow flag in a
        fresh output buffer so resolve() works even after a later dispatch
        donated this ledger (see sm.create_transfers_fast_probed; under
        TB_SHARDS the sharded fast_probed step plays the same role with
        per-shard overflow lanes)."""
        count = len(batch)
        if (
            self._engine is not None
            or self.force_sequential
            or count == 0
            or count > self.batch_lanes
        ):
            return None
        bound0 = self._balance_bound
        with txtrace.stage("route"):
            self._note_balance_bound(batch)
            if not self._fast_path_ok(batch):
                # The blocking fallback re-notes the batch itself; leaving
                # this note in place would double-count it against the
                # monotonic bound (same discipline as commit_group_fast's
                # mid-run refusal).
                self._balance_bound = bound0
                return None
        if timestamp > self.prepare_timestamp:
            # Replay/backup parity with commit_batch's clock catch-up.
            self.prepare_timestamp = timestamp
        self._scrub_maybe_check()  # no-op unless armed, due, and lane idle
        if _obs.enabled:
            _obs.counter("ops.route.fast").inc()
            _obs.histogram("ops.batch_fill_pct", "%").observe(
                100 * count // self.batch_lanes
            )
        owners = None
        if self._ledger_is_sharded:
            with txtrace.stage("route"):
                self._note_cross_shard(batch, count)
                owners = self._note_shard_inserts("transfers", batch, count)
        seq = txtrace.group_seq  # for the closure's spans on the lane
        with txtrace.stage("stage_h2d"):
            # Staged on the serving thread, for the route's own programs.
            staged = (
                self._stage_sharded(batch, timestamp)
                if self._ledger_is_sharded else self._stage(batch, timestamp)
            )
        # Snapshot the growth target pre-submit (see _grow_if_needed).
        need = self._transfers_bound + count
        self._transfers_bound += count
        # Knob read once at submit (see commit_group_fast).
        merkle_closure = self._merkle_forest is not None and not self.merkle_async
        if self._ledger_is_sharded:
            snap = {name: v.copy()
                    for name, v in self._shard_insert_bounds.items()}
            step = self._shard_steps["fast_probed"]

            def dispatch():
                # The sharded probed step donates only the ledger, never
                # the staged batch; the overflow lanes ride a fresh output.
                with txtrace.stage("grow", seq=seq):
                    self._grow_if_needed(
                        transfers_need=need, shard_bounds=snap
                    )
                with txtrace.stage("dispatch", seq=seq):
                    self.ledger, codes, overflow = step(
                        self.ledger, *staged
                    )
                with txtrace.stage("index_append", seq=seq):
                    self._index_lazy_reset()
                if merkle_closure:
                    self._merkle_update_transfers_batches([batch])
                if _obs.enabled:
                    _obs.counter("sharding.batches").inc()
                return codes, overflow
        else:
            def dispatch():
                with txtrace.stage("grow", seq=seq):
                    self._grow_if_needed(transfers_need=need)
                # Index maintenance uses the id and key columns the
                # program passes through: no staged operand is sliced here.
                with txtrace.stage("dispatch", seq=seq):
                    (self.ledger, codes, overflow,  # tblint: ignore[lane-race] FIFO+join
                     *index_feed) = sm.create_transfers_fast_probed(
                        self.ledger, *staged
                    )
                with txtrace.stage("index_append", seq=seq):
                    self._index_append_device(*index_feed)
                if merkle_closure:
                    # Commitment update rides the ledger chain; keys come
                    # from the retained HOST batch.
                    self._merkle_update_transfers_batches([batch])
                return codes, overflow

        armed_mirror = self._scrub_mirror is not None
        armed = armed_mirror or self._merkle_forest is not None
        fut = self._lane_dispatch(dispatch, True, seq)
        handle = DeviceCommitHandle(
            self, fut, [count], [timestamp], stacked=False,
            batches=[batch] if armed_mirror else None, deferred=True,
        )
        if self._merkle_forest is not None and not merkle_closure:
            self._merkle_lane_enqueue("create_transfers", batch)
        self._deferred_submitted(count, owners)
        if armed:
            self._inflight_handles.append(handle)
        return handle

    def _maybe_evict_between_batches(self) -> None:
        hot_max = self.hot_transfers_capacity_max
        if hot_max is not None and self._transfers_bound * 2 > hot_max and (
            self.ledger.transfers.capacity >= hot_max
        ):
            self.evict_cold()

    # -- cold tier (ops/cold.py) --------------------------------------------

    def _resolve_cold(self, batch: np.ndarray, cold_lanes=None) -> int:
        """Host-exact resolution of a FLAG_COLD batch: every cold row that a
        FLAGGED lane references by id (``cold_lanes`` bit 0) or pending_id
        (bit 1) is rehydrated into the hot table.  ``cold_lanes`` None (the
        sequential route, which has no filter): every lane, both ids.
        Returns the rows found cold.  Vectorised over the flagged ids
        (``ColdStore.lookup_arrays``); one upload and one program for the
        rows (``_rehydrate``)."""
        if cold_lanes is None:
            by_id = by_pend = np.ones(len(batch), dtype=bool)
        else:
            cold_lanes = np.asarray(cold_lanes)[: len(batch)]
            by_id, by_pend = (cold_lanes & 1) != 0, (cold_lanes & 2) != 0
        keys = np.unique(np.stack([
            np.concatenate([batch["id_lo"][by_id],
                            batch["pending_id_lo"][by_pend]]),
            np.concatenate([batch["id_hi"][by_id],
                            batch["pending_id_hi"][by_pend]]),
        ], axis=1), axis=0)
        keys = keys[(keys != 0).any(axis=1)]
        found, rows = self.cold.lookup_arrays(keys[:, 0], keys[:, 1])
        n_found = int(found.sum())
        if _obs.enabled and cold_lanes is not None:
            # A re-dispatch for FLAG_COLD, and whether any of it was true.
            _obs.counter("cold.redispatches").inc()
            _obs.counter("cold.flagged_lanes").inc(len(keys))
            _obs.counter("cold.false_positive_lanes").inc(
                len(keys) - n_found)
            if not n_found:
                _obs.counter("cold.false_redispatches").inc()
        if n_found:
            with txtrace.stage("cold_rehydrate", n=n_found):
                self._rehydrate(rows[found])
        return n_found

    def _rehydrate(self, rows: np.ndarray) -> None:
        """Insert cold rows back into the hot table (immutable duplicates of
        their cold copies; a later eviction may spill them again), but for
        those already hot (an earlier rehydration).  A batch's lanes at a
        time through ONE program of that shape (``ops/cold.rehydrate``,
        warmed at start), so no count of rows compiles anything."""
        from .ops import cold as cold_mod

        # No eviction here (evictions mid-commit invalidate the batch's
        # certification); a slightly-elevated load factor until the next
        # between-batches rebalance is fine.
        self._grow_if_needed(transfers=len(rows), evict_ok=False)
        transfers, inserted = self.ledger.transfers, 0
        for at in range(0, len(rows), self.batch_lanes):
            transfers, n = cold_mod.rehydrate(
                transfers, *staging.stage_batch(
                    rows[at:at + self.batch_lanes], self.batch_lanes, 0),
                max_probe=self.config.max_probe,
            )
            inserted += int(n)
        if bool(np.asarray(transfers.probe_overflow)):
            raise RuntimeError("cold rehydration overflowed the hot table")
        self.ledger = self.ledger.replace(transfers=transfers)
        self._transfers_bound += inserted
        self._merkle_mark_dirty()  # rows appeared outside a commit batch
        if _obs.enabled:
            _obs.counter("cold.rehydrated_rows").inc(inserted)

    def evict_cold(self, frac: Optional[float] = None) -> int:
        """Spill the oldest ~frac of live hot transfers to the cold store.
        Deterministic given the ledger state; called at checkpoint
        boundaries by the replica, or directly under memory pressure.
        Returns the number of rows evicted."""
        assert self._engine is None, "tiering runs on the device path"
        if self._shard_mesh is not None and self._ledger_is_sharded:
            # Tiering under TB_SHARDS (the long-excluded VOPR scenario,
            # folded back in PR 20): eviction is a canonical-layout
            # concern — pull the ledger single-layout (the _sequential
            # window discipline), run the EXISTING exact eviction
            # unchanged, re-place onto the mesh.  Determinism: both
            # converters and the threshold selection are deterministic,
            # so replicas evicting at the same op boundary stay
            # byte-identical.
            from .parallel import sharded as shard_mod

            self._ledger = shard_mod.unshard_ledger(
                self._ledger, self._shard_mesh
            )
            self._ledger_is_sharded = False
            try:
                return self._evict_cold_impl(frac)
            finally:
                self._ledger = shard_mod.shard_ledger(
                    self._ledger, self._shard_mesh
                )
                self._ledger_is_sharded = True
                self._canon = None
                self._refresh_shard_bounds(self._ledger)
        return self._evict_cold_impl(frac)

    def _evict_cold_impl(self, frac: Optional[float] = None) -> int:
        from .ops import cold as cold_mod

        if not self._tiering:
            self._tiering = True
            self._bloom_np = np.zeros(((1 << self._bloom_log2) // 32,), np.uint32)
        num = self._eviction_permille(frac)
        table = self.ledger.transfers
        # Each device step ends in a blocking read, so a span's time is its
        # program's (docs/tracing.md: `cold_evict` and its six children).
        with txtrace.stage("cold_evict"):
            with txtrace.stage("cold_threshold"):
                threshold, n, live = jax.device_get(  # tblint: ignore[host-sync] eviction
                    cold_mod.eviction_threshold(table, num, 1000))
                n, live = int(n), int(live)
            if n == 0:
                return 0
            with txtrace.stage("cold_extract", n=n):
                # The evicted count's size class, not the capacity: the
                # gather moves the rows that leave and no others.
                packed = cold_mod.extract_evicted(
                    table, threshold,
                    cold_mod.size_class(n, self.batch_lanes),
                )
                jax.block_until_ready(packed)  # tblint: ignore[host-sync] eviction
            with txtrace.stage("cold_fetch", n=n):
                rows = cold_mod.rows_to_numpy(*packed)
                del packed
            with txtrace.stage("cold_spill", n=n):
                # Durable before the eviction returns: sorted by id,
                # written, fsynced, renamed.
                self.cold.append_run(rows)
            with txtrace.stage("cold_rehash", n=live - n):
                table = cold_mod.drop_evicted(
                    table, threshold,
                    cold_mod.size_class(live - n, self.batch_lanes),
                )
                jax.block_until_ready(table.count)  # tblint: ignore[host-sync] eviction
                self.ledger = self.ledger.replace(transfers=table)
            with txtrace.stage("cold_filter", n=n):
                cold_mod.bloom_add_host(
                    self._bloom_np, rows["id_lo"], rows["id_hi"])
                self._maybe_grow_bloom()
                self._bloom_dev = jnp.asarray(self._bloom_np)
        self._transfers_bound = max(0, self._transfers_bound - len(rows))
        self._evictions += 1
        self._merkle_mark_dirty()  # rows left the hot table wholesale
        if _obs.enabled:
            # The tier rebalance is this runtime's compaction stage
            # (replica pipeline naming: prefetch/commit/compact/checkpoint).
            _obs.counter("ops.compactions").inc()
            _obs.counter("ops.rows_evicted").inc(len(rows))
            self._report_cold_gauges()
        # The query index stores ids (not slots), so it stays valid; row
        # resolution for cold ids happens in get_account_transfers.
        return len(rows)

    def _eviction_permille(self, frac: Optional[float] = None) -> int:
        """The share of the live rows an eviction moves, in thousandths."""
        if frac is None:
            frac = self.config.eviction_fraction
        return max(1, min(999, int(frac * 1000)))

    def _report_cold_gauges(self) -> None:
        _obs.gauge("cold.rows").set(self.cold.count)
        _obs.gauge("cold.runs").set(len(self.cold.runs))
        _obs.gauge("cold.bloom_bits_log2").set(self._bloom_log2)

    def _maybe_grow_bloom(self) -> None:
        """The filter's design load is 12 bits a cold id (false-positive
        rate ~1e-3 at 4 hashes); ``start --cold-bloom-log2`` sizes it so
        that a deployment stays under it.  Past it, rebuild from the runs
        at four times the bits: a NEW SHAPE of the general commit program's
        argument, so the next commit compiles (tens of seconds on a v5e) inside a
        request.  Counted, and said once."""
        while self.cold.count * 12 > (1 << self._bloom_log2):
            if not self._bloom_grows:
                warnings.warn(
                    f"cold tier: {self.cold.count} cold ids pass the design "
                    f"load of a 2^{self._bloom_log2}-bit filter; it grows, "
                    "and the commit program recompiles at each growth "
                    "(size it at start: --cold-bloom-log2)",
                    RuntimeWarning, stacklevel=2,
                )
            self._bloom_grows += 1
            self._bloom_log2 += 2
            self._bloom_np = self.cold.rebuild_bloom(self._bloom_log2)
            if _obs.enabled:
                _obs.counter("cold.bloom.grows").inc()

    def _transfer_growth_counts(self, batch: np.ndarray) -> Tuple[int, int]:
        """(posted rows, history rows) this batch could append at most —
        host-computable from flags, keeping the posted/history stores from
        growing with plain-transfer volume."""
        pv = int(
            (
                (batch["flags"]
                 & (types.TransferFlags.POST_PENDING_TRANSFER
                    | types.TransferFlags.VOID_PENDING_TRANSFER)) != 0
            ).sum()
        )
        hist = (len(batch) - pv) if self._history_accounts_possible else 0
        return pv, hist

    @staticmethod
    def _target_capacity(capacity: int, needed_rows: int) -> int:
        """Smallest power-of-two capacity keeping load factor <= 0.5."""
        while needed_rows * 2 > capacity:
            capacity *= 2
        return capacity

    def _shard_peak_floor(self, which: str, cap: int, bounds=None) -> int:
        """Under sharding, capacity must also keep the PEAK shard's
        attempted-insert bound under half its cap/n local region — the
        per-shard twin of the global load<=0.5 policy (hash skew can
        overfill one shard while the global count looks fine, and a
        fast-path probe overflow is fatal).

        ``bounds`` overrides the live per-shard bounds: deferred dispatch
        closures pass a submit-time snapshot so the growth moment never
        depends on how far the serving thread raced ahead (the sharded
        twin of the transfers_need snapshot)."""
        if bounds is None:
            bounds = self._shard_insert_bounds
        if self._ledger_is_sharded and which in bounds:
            peak = int(bounds[which].max())
            while peak * 2 > cap // self.shards:
                cap *= 2
        return cap

    def _table_grow(self, table, name: str, capacity: int):
        """ht.grow, layout-aware: a sharded table rehashes per shard
        (owners are the low hash bits, so rows never migrate between
        shards; only local homes change)."""
        from .ops import hash_table as ht

        # Growth rehashes every slot: the commitment forest (whose arrays
        # are capacity-shaped) rebuilds from the grown layout at the next
        # update/check (docs/commitments.md "growth rehash").
        self._merkle_mark_dirty()
        if self._sanitize and self._sanitize_compile_base is not None:
            # The grown capacity is a NEW shape class: the grow kernel and
            # the next commit dispatch legitimately compile.  Open the
            # one-readback grace window, and downgrade strict raising for
            # the rest of this arm period (variants not yet run at the new
            # capacity first-compile arbitrarily later).
            self._sanitize_grace = True
            self._sanitize_soft = True
        if self._ledger_is_sharded:
            from .parallel import sharded as shard_mod

            if _obs.enabled:
                _obs.counter("sharding.grows").inc()
            return shard_mod.grow_sharded_table(
                table, name, capacity, self._shard_mesh
            )
        return ht.grow(table, capacity)

    def _grow_if_needed(
        self, accounts: int = 0, transfers: int = 0, posted: int = 0,
        history: int = 0, evict_ok: bool = True,
        transfers_need: Optional[int] = None, shard_bounds=None,
    ) -> None:
        """Keep every table's load factor under 0.5 using host-side row
        bounds (no device sync; bounds only overestimate).

        ``transfers_need``: an explicit row target snapshotted by the
        caller — the deferred dispatch closures run on the lane thread
        while the serving thread keeps advancing _transfers_bound, so a
        live read here would make the growth moment timing-dependent.
        ``shard_bounds`` is the per-shard twin (a submit-time snapshot of
        _shard_insert_bounds) for the same reason."""
        led = before = self.ledger
        accounts_need = self._accounts_bound + accounts
        if transfers_need is None:
            transfers_need = self._transfers_bound + transfers
        cap = self._shard_peak_floor("accounts", self._target_capacity(
            led.accounts.capacity, accounts_need
        ), bounds=shard_bounds)
        if cap != led.accounts.capacity:
            led = led.replace(
                accounts=self._table_grow(led.accounts, "accounts", cap)
            )
        cap = self._shard_peak_floor("transfers", self._target_capacity(
            led.transfers.capacity, transfers_need,
        ), bounds=shard_bounds)
        if cap != led.transfers.capacity:
            hot_max = self.hot_transfers_capacity_max
            if hot_max is not None and cap > hot_max and (
                led.transfers.capacity >= hot_max
            ):
                if evict_ok:
                    # At the hot ceiling: spill the old half to the cold
                    # store instead of growing (BASELINE config 4 tiering).
                    self.ledger = led
                    self.evict_cold()
                    led = self.ledger
                # else: accept elevated load until the between-batches
                # rebalance (MAX_PROBE absorbs it).
            else:
                if hot_max is not None:
                    cap = min(cap, max(hot_max, led.transfers.capacity))
                if cap != led.transfers.capacity:
                    led = led.replace(
                        transfers=self._table_grow(
                            led.transfers, "transfers", cap
                        )
                    )
        posted_need = self._posted_bound + posted
        if self._ledger_is_sharded:
            # Posted keys (pending timestamps) are not host-computable per
            # shard; a conservative 2x target (global load <= 0.25) keeps
            # the peak shard's load under 0.5 except at negligible-tail
            # skew, and the full path's claim overflow still grows+retries.
            posted_need *= 2
        cap = self._target_capacity(led.posted.capacity, posted_need)
        if cap != led.posted.capacity:
            led = led.replace(posted=self._table_grow(led.posted, "posted", cap))
        if history and self._history_bound + history > led.history.capacity:
            led = led.replace(
                history=sm.grow_history(led.history, self._history_bound + history)
            )
            if self._sanitize and self._sanitize_compile_base is not None:
                self._sanitize_grace = True  # new history capacity class
                self._sanitize_soft = True
        self.ledger = led
        if _obs.enabled:
            # Rows bound over slots, as this growth check saw them (a bound
            # only overestimates: failed events count as rows).
            _obs.gauge("ledger.accounts.load").set(
                accounts_need / led.accounts.capacity)
            _obs.gauge("ledger.transfers.load").set(
                transfers_need / led.transfers.capacity)
            if led is not before:
                self._report_table_bytes(led)

    @staticmethod
    def _report_table_bytes(ledger) -> None:
        """Gauge ``ledger.table_bytes``: the bytes of the three tables'
        arrays (keys, tombstones, value columns), set where tables are made
        or grown."""
        if not _obs.enabled or ledger is None:
            return
        _obs.gauge("ledger.table_bytes").set(sum(
            col.nbytes
            for t in (ledger.accounts, ledger.transfers, ledger.posted)
            for col in (t.key_lo, t.key_hi, t.tombstone, *t.cols.values())
        ))

    def _grow_flagged(self, kflags: int) -> None:
        from .ops import transfer_full as tf

        led = self.ledger
        if kflags & tf.FLAG_GROW_ACCOUNTS:
            led = led.replace(
                accounts=self._table_grow(
                    led.accounts, "accounts", led.accounts.capacity * 2
                )
            )
        if kflags & tf.FLAG_GROW_TRANSFERS:
            hot_max = self.hot_transfers_capacity_max
            if hot_max is not None and led.transfers.capacity >= hot_max:
                # Never allocate past the HBM budget the ceiling encodes:
                # make room by spilling instead (certification is reset by
                # the caller via the eviction counter).
                self.ledger = led
                self.evict_cold()
                led = self.ledger
            else:
                led = led.replace(
                    transfers=self._table_grow(
                        led.transfers, "transfers", led.transfers.capacity * 2
                    )
                )
        if kflags & tf.FLAG_GROW_POSTED:
            led = led.replace(
                posted=self._table_grow(
                    led.posted, "posted", led.posted.capacity * 2
                )
            )
        self.ledger = led
        self._report_table_bytes(led)

    def _sequential(
        self, operation: str, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        if self._shard_mesh is not None and self._ledger_is_sharded:
            # The unschedulable exit of the sharded commit path (linked
            # chains, in-batch pending refs, history accounts, deep
            # cascades — exactly the wave scheduler's fallback set): pull
            # the ledger into the canonical single-device layout, run the
            # EXISTING exact sequential path unchanged (growth, bounds,
            # index bookkeeping included — _ledger_is_sharded is off for
            # the window, so every internal self.ledger assignment stays
            # single-layout), then re-place the result onto the mesh.
            # O(rows) host conversions; routed batches are rare by design.
            from .parallel import sharded as shard_mod

            self.shard_seq_fallbacks += 1
            if _obs.enabled:
                _obs.counter("sharding.seq_fallbacks").inc()
            self._ledger = shard_mod.unshard_ledger(
                self._ledger, self._shard_mesh
            )
            self._ledger_is_sharded = False
            try:
                return self._sequential_impl(operation, batch, timestamp)
            finally:
                self._ledger = shard_mod.shard_ledger(
                    self._ledger, self._shard_mesh
                )
                self._ledger_is_sharded = True
                self._canon = None
                self._refresh_shard_bounds(self._ledger)
        return self._sequential_impl(operation, batch, timestamp)

    def _sequential_impl(
        self, operation: str, batch: np.ndarray, timestamp: int
    ) -> List[Tuple[int, int]]:
        from .ops import scan_path

        count = len(batch)
        if _obs.enabled:
            # Order-dependent batches are latency-bound (lax.scan): track
            # how often serving falls off the vectorized kernels.
            _obs.counter("ops.sequential_batches").inc()
        if operation == "create_accounts":
            self._grow_if_needed(accounts=count)
            if bool((batch["flags"] & types.AccountFlags.HISTORY).any()):
                self._history_accounts_possible = True
            if bool((batch["flags"] & _LIMIT_FLAGS).any()):
                self._limit_accounts_possible = True
            pv_count = hist_count = 0
        else:
            if self.cold.count:
                # The scan path only sees the hot table: rehydrate any cold
                # rows this batch references so its semantics stay exact.
                self._resolve_cold(batch)
            pv_count, hist_count = self._transfer_growth_counts(batch)
            self._grow_if_needed(
                transfers=count, posted=pv_count, history=hist_count
            )

        with txtrace.stage("stage_h2d"):
            staged = self._stage(batch, timestamp)
        kernel = (
            scan_path.create_accounts_seq
            if operation == "create_accounts"
            else scan_path.create_transfers_seq
        )
        # The scan path may tombstone slots (linked-chain rollback) — a
        # mutation the touched-key over-approximation cannot see; the
        # commitment forest rebuilds at the next update/check.
        self._merkle_mark_dirty()
        self.ledger, codes = kernel(self.ledger, *staged)
        codes = self._d2h_codes(codes)
        if operation == "create_accounts":
            self._accounts_bound += count
            self._scan_append_accounts(staged, codes, count)
        else:
            self._transfers_bound += count
            self._posted_bound += pv_count
            self._history_bound += hist_count
            self._index_append(staged, codes, count)
        results = self._compress(codes, count)
        self._update_commit_timestamp(codes, count, timestamp)
        return results

    def _index_lazy_reset(self) -> bool:
        """Bulk-ingest mode (and sharded mode, whose ledger no single-device
        FieldIndex probe could read): invalidate the index instead of
        maintaining it; the next query rebuilds from the canonical table
        (+cold runs) in one shot.  True if this machine runs that way."""
        if not (self.config.lazy_index or self._shard_mesh is not None):
            return False
        if not self.index.stale:
            self.index.reset()
        self.scans_transfers.reset()
        return True

    def _index_append_device(self, id_lo, id_hi, keys, ok, row=None) -> None:
        """The index append of a keyed route (a dispatch-lane closure, or
        the blocking general commit), right after its kernel, from what
        that kernel returned, in its order: the id columns, the index key
        columns and the lanes it wrote (a grouped dispatch: all of them
        stacked, and ``row`` the batch).  No mask and no slice is taken
        here: on a TPU each would be a dispatch the device waits for."""
        if self._index_lazy_reset():
            return
        watching = self._sanitize and self._sanitize_compile_base is not None

        def _index_events():
            return self.index.shape_class_events + sum(
                ix.shape_class_events
                for ix in self.scans_transfers.indexes.values()
            )

        ev0 = _index_events() if watching else 0
        if _obs.enabled:
            _obs.counter("index.runs.keyed").inc()
        self.index.append_batch(keys, id_lo, id_hi, ok, row)
        if self.scans_transfers.indexes:
            # self.ledger is live here: a deferred handle's resolve may run
            # after a later dispatch has donated this ledger's buffers.
            if row is not None:
                id_lo, id_hi, ok = id_lo[row], id_hi[row], ok[row]
            self.scans_transfers.append_batch(self.ledger, id_lo, id_hi, ok)
        if watching and _index_events() != ev0:
            # A Bentley–Saxe carry reached a NEW power-of-two level: its
            # first merge/fill legitimately jit-compiles (bounded:
            # log(rows) levels ever).  Same grace as a table growth.
            self._sanitize_grace = True

    def _written_mask(self, codes: np.ndarray, count: int) -> jax.Array:
        ok = np.zeros(self.batch_lanes, dtype=bool)
        ok[:count] = codes[:count] == 0
        return jnp.asarray(ok)

    def _index_append(self, staged: tuple, codes: np.ndarray, count: int) -> None:
        """The index append of a blocking route whose kernel returns no
        keys (the sequential path, the unprobed fast kernel), from host
        codes: the keys are read back from the transfers table by id.  The
        id columns are sliced out of the staged operands here, on the host
        (``staging.id_columns``): two programs of their own, on routes no
        deferred commit takes."""
        if self._index_lazy_reset():
            return
        ok_dev = self._written_mask(codes, count)
        id_lo, id_hi = staging.id_columns(staged, types.TRANSFER_DTYPE)
        if _obs.enabled:
            _obs.counter("index.runs.probed").inc()
        keys, written = index_ops.probe_keys(self.ledger, id_lo, id_hi, ok_dev)
        self.index.append_batch(keys, id_lo, id_hi, written)
        if self.scans_transfers.indexes:
            self.scans_transfers.append_batch(
                self.ledger, id_lo, id_hi, ok_dev
            )

    def _scan_append_accounts(
        self, staged: tuple, codes: np.ndarray, count: int
    ) -> None:
        if not self.scans_accounts.indexes:
            return
        if self.config.lazy_index or self._shard_mesh is not None:
            self.scans_accounts.reset()
            return
        self.scans_accounts.append_batch(
            self.ledger, *staging.id_columns(staged, types.ACCOUNT_DTYPE),
            self._written_mask(codes, count),
        )

    def _update_commit_timestamp(
        self, codes: np.ndarray, count: int, timestamp: int
    ) -> None:
        ok_lanes = np.nonzero(codes[:count] == 0)[0]
        if len(ok_lanes):
            self.commit_timestamp = timestamp - count + int(ok_lanes[-1]) + 1

    # -- lookups -------------------------------------------------------------

    def lookup_accounts(self, ids: List[int]) -> np.ndarray:
        """Return found accounts as an ACCOUNT_DTYPE array (misses omitted,
        state_machine.zig:1091-1107)."""
        if not ids:
            return np.zeros(0, dtype=types.ACCOUNT_DTYPE)
        if self._engine is not None:
            return self._engine.lookup_accounts(ids)
        lo = jnp.asarray([i & U64_MAX for i in ids], jnp.uint64)
        hi = jnp.asarray([i >> 64 for i in ids], jnp.uint64)
        found, cols = sm.lookup_accounts(self._query_ledger(), lo, hi)
        found = np.asarray(found)
        self._sanitize_absorb_compiles()  # read-path first-use jit
        host = {k: np.asarray(v) for k, v in cols.items()}
        host["reserved"] = np.zeros(len(ids), np.uint32)
        rows = types.from_soa(host, types.ACCOUNT_DTYPE)
        return rows[found]

    def lookup_transfers(self, ids: List[int]) -> np.ndarray:
        if not ids:
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        if self._engine is not None:
            found, rows = self._engine.lookup_transfers(ids)
            return rows[found]  # no cold tier in host mode
        lo_np = np.array([i & U64_MAX for i in ids], np.uint64)
        hi_np = np.array([i >> 64 for i in ids], np.uint64)
        found, cols = sm.lookup_transfers(
            self._query_ledger(), jnp.asarray(lo_np), jnp.asarray(hi_np)
        )
        found = np.array(found)
        self._sanitize_absorb_compiles()  # read-path first-use jit
        host = {k: np.asarray(v) for k, v in cols.items()}
        rows = types.from_soa(host, types.TRANSFER_DTYPE)
        if self.cold.count and not found.all():
            # Misses may be cold (evicted): merge rows from the spill,
            # preserving request order.
            miss = np.flatnonzero(~found)
            was_cold, cold_rows = self.cold.lookup_arrays(
                lo_np[miss], hi_np[miss])
            rows[miss[was_cold]] = cold_rows[was_cold]
            found[miss[was_cold]] = True
        return rows[found]

    # -- queries (state_machine.zig:693-892, 1128-1195) ----------------------

    @staticmethod
    def _filter_window(filt: np.void) -> Optional[Tuple[int, int, int, int, bool, int]]:
        """Validate an AccountFilter and resolve its effective window.

        Mirrors get_scan_from_filter (state_machine.zig:823-837): invalid
        filters yield None -> empty results, not errors.  Returns
        (acct_lo, acct_hi, ts_min, ts_max, descending, limit)."""
        acct_lo = int(filt["account_id_lo"])
        acct_hi = int(filt["account_id_hi"])
        ts_min = int(filt["timestamp_min"])
        ts_max = int(filt["timestamp_max"])
        limit = int(filt["limit"])
        flags = int(filt["flags"])
        valid = (
            (acct_lo, acct_hi) != (0, 0)
            and (acct_lo, acct_hi) != (U64_MAX, U64_MAX)
            and ts_min != U64_MAX
            and ts_max != U64_MAX
            and (ts_max == 0 or ts_min <= ts_max)
            and limit != 0
            and flags & (types.AccountFilterFlags.DEBITS | types.AccountFilterFlags.CREDITS)
            and flags & ~0x7 == 0
            and not bytes(filt["reserved"]).strip(b"\0")
        )
        if not valid:
            return None
        # TimestampRange defaults (lsm/timestamp_range.zig:4-5).
        eff_min = ts_min if ts_min != 0 else 1
        eff_max = ts_max if ts_max != 0 else U64_MAX - 1
        descending = bool(flags & types.AccountFilterFlags.REVERSED)
        return acct_lo, acct_hi, eff_min, eff_max, descending, limit

    def get_account_transfers(self, filt: np.void) -> np.ndarray:
        """Transfers on either side of the filtered account, timestamp-ordered
        (prefetch_get_account_transfers, state_machine.zig:693-723).

        Served from the sorted-runs secondary index (ops/index.py): a few
        binary searches + a bounded gather per level — flat in table capacity
        — instead of round 1's full-table argsort."""
        window = self._filter_window(filt)
        if window is None:
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        self._index_fresh()
        acct_lo, acct_hi, ts_min, ts_max, descending, limit = window
        flags = int(filt["flags"])
        # Static candidate cap: the next power of two covering the largest
        # reply (one compiled query program per level layout).
        k = 1 << (QUERY_ROWS_MAX - 1).bit_length()
        valid, tid_lo, tid_hi = self.index.query(
            self._query_ledger(),
            jnp.uint64(acct_lo), jnp.uint64(acct_hi),
            jnp.uint64(ts_min), jnp.uint64(ts_max),
            jnp.bool_(bool(flags & types.AccountFilterFlags.DEBITS)),
            jnp.bool_(bool(flags & types.AccountFilterFlags.CREDITS)),
            k,
            bool(descending),
        )
        return self._resolve_transfer_rows(tid_lo, tid_hi, valid, limit)

    def _resolve_transfer_rows(
        self, tid_lo, tid_hi, valid, limit: int
    ) -> np.ndarray:
        """Resolve timestamp-ordered index hits (transfer ids) to wire rows:
        hot-table batch lookup, adjacent-duplicate dedup, cold-spill merge
        (the ScanLookup role, lsm/scan_lookup.zig)."""
        found, cols = sm.lookup_transfers(
            self._query_ledger(), jnp.asarray(tid_lo), jnp.asarray(tid_hi)
        )
        idx_valid = np.asarray(valid)
        found = np.asarray(found)
        # Dedupe repeated index entries for one transfer id (a rebuild can
        # index a rehydrated transfer from both the hot table and its cold
        # run).  Results are timestamp-ordered, so duplicates are adjacent.
        tl_np, th_np = np.asarray(tid_lo), np.asarray(tid_hi)
        if len(tl_np) > 1:
            dup = np.zeros(len(tl_np), dtype=bool)
            dup[1:] = (
                idx_valid[1:] & idx_valid[:-1]
                & (tl_np[1:] == tl_np[:-1]) & (th_np[1:] == th_np[:-1])
            )
            idx_valid = idx_valid & ~dup
        host = {name: np.asarray(col) for name, col in cols.items()}
        out = types.from_soa(host, types.TRANSFER_DTYPE)
        if self.cold.count and bool((idx_valid & ~found).any()):
            # Index hits whose rows were evicted: resolve from the spill,
            # preserving timestamp order.
            miss = np.flatnonzero(idx_valid & ~found)
            was_cold, cold_rows = self.cold.lookup_arrays(
                tl_np[miss], th_np[miss])
            found = np.array(found)
            out[miss[was_cold]] = cold_rows[was_cold]
            found[miss[was_cold]] = True
        return out[idx_valid & found][: min(limit, QUERY_ROWS_MAX)]

    # -- general composed scans (ops/scan_builder.py) ------------------------

    @staticmethod
    def _scan_window(timestamp_min: int, timestamp_max: int) -> Tuple[int, int]:
        # TimestampRange defaults (lsm/timestamp_range.zig:4-5).
        return (
            timestamp_min if timestamp_min else 1,
            timestamp_max if timestamp_max else U64_MAX - 1,
        )

    def scan_transfers(
        self, expr, timestamp_min: int = 0, timestamp_max: int = 0,
        limit: int = QUERY_ROWS_MAX, reversed: bool = False,
    ) -> np.ndarray:
        """Composed index scan over transfers: any ops/scan_builder.py
        expression (prefix conditions on any indexed field, union /
        intersection / difference to any depth), results timestamp-ordered.
        Strictly more general than the reference's implemented surface
        (scan_builder.zig stubs merge_intersection/merge_difference)."""
        self._index_fresh()
        ts_min, ts_max = self._scan_window(timestamp_min, timestamp_max)
        limit = min(limit, QUERY_ROWS_MAX)
        tid_lo, tid_hi = self.scans_transfers.evaluate(
            expr, self._query_ledger(), ts_min, ts_max, limit, bool(reversed)
        )
        if len(tid_lo) == 0:
            return np.zeros(0, dtype=types.TRANSFER_DTYPE)
        # Pad ids to a power of two so the lookup kernel compiles per size
        # class, not per result count.
        n = len(tid_lo)
        cap = 1 << (n - 1).bit_length()
        pad_lo = np.zeros(cap, np.uint64)
        pad_hi = np.zeros(cap, np.uint64)
        pad_lo[:n], pad_hi[:n] = tid_lo, tid_hi
        valid = np.zeros(cap, bool)
        valid[:n] = True
        return self._resolve_transfer_rows(pad_lo, pad_hi, valid, limit)

    def scan_accounts(
        self, expr, timestamp_min: int = 0, timestamp_max: int = 0,
        limit: int = QUERY_ROWS_MAX, reversed: bool = False,
    ) -> np.ndarray:
        """Composed index scan over accounts (accounts are never evicted, so
        resolution is one batched hot-table lookup)."""
        self._index_fresh()
        ts_min, ts_max = self._scan_window(timestamp_min, timestamp_max)
        limit = min(limit, QUERY_ROWS_MAX)
        tid_lo, tid_hi = self.scans_accounts.evaluate(
            expr, self._query_ledger(), ts_min, ts_max, limit, bool(reversed)
        )
        ids = [int(lo) | (int(hi) << 64) for lo, hi in zip(tid_lo, tid_hi)]
        if not ids:
            return np.zeros(0, dtype=types.ACCOUNT_DTYPE)
        # Pad to a power of two so the lookup kernel compiles per size
        # class, not per result count; id 0 can never exist, so the pad
        # lanes drop out as misses.
        cap = 1 << (len(ids) - 1).bit_length()
        return self.lookup_accounts(ids + [0] * (cap - len(ids)))

    def query_transfers_where(
        self, timestamp_min: int = 0, timestamp_max: int = 0,
        limit: int = QUERY_ROWS_MAX, reversed: bool = False, **conditions,
    ) -> np.ndarray:
        """QueryFilter-style multi-field query: the intersection of
        equality conditions on indexed fields (e.g. ``ledger=1, code=5``) —
        the semantics newer upstream exposes as ``query_transfers`` and
        this reference declares but stubs (scan_builder.zig:184-205)."""
        from .ops import scan_builder as sb

        if not conditions:
            raise ValueError("query_transfers_where needs >=1 condition")
        expr = sb.merge_intersection(
            *(sb.scan_prefix(f, v) for f, v in sorted(conditions.items()))
        )
        return self.scan_transfers(
            expr, timestamp_min, timestamp_max, limit, reversed
        )

    def query_accounts_where(
        self, timestamp_min: int = 0, timestamp_max: int = 0,
        limit: int = QUERY_ROWS_MAX, reversed: bool = False, **conditions,
    ) -> np.ndarray:
        from .ops import scan_builder as sb

        if not conditions:
            raise ValueError("query_accounts_where needs >=1 condition")
        expr = sb.merge_intersection(
            *(sb.scan_prefix(f, v) for f, v in sorted(conditions.items()))
        )
        return self.scan_accounts(
            expr, timestamp_min, timestamp_max, limit, reversed
        )

    def get_account_history(self, filt: np.void) -> np.ndarray:
        """Balance history of a HISTORY-flagged account
        (prefetch_get_account_history, state_machine.zig:736-797): empty
        unless the account exists and carries the flag."""
        from .ops import query

        window = self._filter_window(filt)
        if window is None:
            return np.zeros(0, dtype=types.ACCOUNT_BALANCE_DTYPE)
        acct_lo, acct_hi, ts_min, ts_max, descending, limit = window
        account = self.lookup_accounts([acct_lo | (acct_hi << 64)])
        if len(account) == 0 or not (
            int(account[0]["flags"]) & types.AccountFlags.HISTORY
        ):
            return np.zeros(0, dtype=types.ACCOUNT_BALANCE_DTYPE)
        flags = int(filt["flags"])
        qled = self._query_ledger()
        k = min(qled.history.capacity, QUERY_ROWS_MAX)
        valid, rows = query.scan_history(
            qled,
            jnp.uint64(acct_lo), jnp.uint64(acct_hi),
            jnp.uint64(ts_min), jnp.uint64(ts_max),
            jnp.bool_(bool(flags & types.AccountFilterFlags.DEBITS)),
            jnp.bool_(bool(flags & types.AccountFilterFlags.CREDITS)),
            jnp.bool_(descending),
            k,
        )
        valid = np.asarray(valid)
        host = {name: np.asarray(col) for name, col in rows.items()}
        host["reserved"] = np.zeros(len(valid), dtype="V56")
        out = types.from_soa(host, types.ACCOUNT_BALANCE_DTYPE)
        return out[valid][: min(limit, k)]

    # -- checkpoint surface --------------------------------------------------

    def host_state(self) -> dict:
        """Host-tracked state that must survive restarts (checkpointed
        alongside the device ledger)."""
        return {
            "prepare_timestamp": self.prepare_timestamp,
            "commit_timestamp": self.commit_timestamp,
            "accounts_bound": self._accounts_bound,
            "transfers_bound": self._transfers_bound,
            "posted_bound": self._posted_bound,
            "history_bound": self._history_bound,
            "history_accounts_possible": self._history_accounts_possible,
            "limit_accounts_possible": self._limit_accounts_possible,
            "balance_bound": min(self._balance_bound, _BOUND_CLAMP),
            "cold_manifest": self.cold.manifest(),
            "bloom_log2": self._bloom_log2,
        }

    def restore_host_state(self, state: dict) -> None:
        self.prepare_timestamp = int(state["prepare_timestamp"])
        self.commit_timestamp = int(state["commit_timestamp"])
        # Floor the bounds at the live device counts so checkpoints that
        # predate bound tracking still trigger growth correctly (one sync at
        # restart is fine).
        led = self.ledger
        self._report_table_bytes(led)

        def _count(table) -> int:
            # Layout-agnostic: sharded tables carry per-shard count vectors.
            return int(np.asarray(table.count).sum())

        self._accounts_bound = max(
            int(state.get("accounts_bound", 0)), _count(led.accounts)
        )
        self._transfers_bound = max(
            int(state.get("transfers_bound", 0)), _count(led.transfers)
        )
        self._posted_bound = max(
            int(state.get("posted_bound", 0)), _count(led.posted)
        )
        self._history_bound = max(
            int(state.get("history_bound", 0)), int(np.asarray(led.history.count))
        )
        self._history_accounts_possible = bool(
            state.get("history_accounts_possible", True)
        )
        # Absent fields (older checkpoints) default to "fast path off" —
        # always safe.
        self._limit_accounts_possible = bool(
            state.get("limit_accounts_possible", True)
        )
        self._balance_bound = int(state.get("balance_bound", _BOUND_CLAMP))
        manifest = state.get("cold_manifest", [])
        if manifest:
            # Cold tier under TB_SHARDS is served by the sequential
            # fallback (mesh kernels carry no bloom): commits route
            # through the canonical single-layout window while any row is
            # cold, so a tiered checkpoint restores sharded just fine.
            self._tiering = True
            self.cold.load_manifest(manifest)
            # The filter is derived state, rebuilt from the runs: the size
            # this start asked for stands unless the checkpoint's is larger.
            self._bloom_log2 = max(
                self._bloom_log2, int(state.get("bloom_log2", 0)))
            self._bloom_np = self.cold.rebuild_bloom(self._bloom_log2)
            self._bloom_dev = jnp.asarray(self._bloom_np)
        elif self.cold.runs:
            # Restored to a pre-eviction checkpoint: drop stale in-memory
            # cold state (files stay; older checkpoints may reference them).
            self.cold.clear()
            self._bloom_np = np.zeros(((1 << self._bloom_log2) // 32,), np.uint32)
            self._bloom_dev = jnp.asarray(self._bloom_np)
        # The ledger was just swapped underneath us (restart or state sync):
        # the derived index no longer matches and rebuilds on next use.
        self.index.reset()
        self.scans_transfers.reset()
        self.scans_accounts.reset()
        self._index_stale = False
        if self.scrub_armed:
            # The new ledger is digest-verified by the caller (checkpoint
            # restore / state-sync install): reseed the mirror and/or
            # rebuild the commitment forest from it.
            self.scrub_arm()

    # -- parity surface ------------------------------------------------------

    def balances_snapshot(self) -> List[Tuple[int, int, int, int, int, int]]:
        """(id, dp, dpo, cp, cpo, ts) sorted by id — comparable with
        ReferenceStateMachine.balances_snapshot()."""
        a = self.ledger.accounts
        key_lo = np.asarray(a.key_lo)
        key_hi = np.asarray(a.key_hi)
        live = (key_lo != 0) | (key_hi != 0)
        cols = {k: np.asarray(v)[live] for k, v in a.cols.items()}
        ids = (key_hi[live].astype(object) << 64) | key_lo[live].astype(object)

        def u128_col(name):
            return (cols[name + "_hi"].astype(object) << 64) | cols[
                name + "_lo"
            ].astype(object)

        out = list(
            zip(
                ids,
                u128_col("debits_pending"),
                u128_col("debits_posted"),
                u128_col("credits_pending"),
                u128_col("credits_posted"),
                (int(t) for t in cols["timestamp"]),
            )
        )
        return sorted(
            (int(a_), int(b), int(c), int(d), int(e), int(f))
            for a_, b, c, d, e, f in out
        )

    def digest(self) -> int:
        out = int(sm.ledger_digest(self.ledger))
        self._sanitize_absorb_compiles()  # read-path first-use jit
        return out
