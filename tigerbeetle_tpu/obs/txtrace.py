"""End-to-end causal tracing, commit-stage attribution, black-box recorder.

The third observability layer (docs/tracing.md), three coupled pieces:

**TRACE** — a u64 trace id carved from the reserved header bytes
(vsr/wire.py's shared frame prefix, offset 64; zero = untraced = the
legacy wire, bit-identical).  Clients stamp it on a sampled fraction of
requests (``TB_TRACE_SAMPLE=1/N``); the replica copies it request ->
prepare -> reply, and every hop on the way — bus ingress, consensus
prepare/ack/commit, the FIFO dispatch lane, the kernel dispatch, the
merkle path refresh, the fsync barrier, the reply release — emits a
cross-process *flow event* into the host tracer's Chrome buffer.  One
request, one causal chain, across all replicas of a SimCluster or a
real cluster_bus deployment, readable in Perfetto as connected arrows.

**ATTRIBUTE** — two kinds of record (docs/tracing.md).  *Thread spans*:
``txtrace.stage(name)`` is the one way a commit-path site times a block;
the duration lands in a ``txtrace.stage.<name>`` registry histogram (when
the registry is on), in the in-process total table that
``stage_totals()`` returns, and — while a ``jax.profiler``
session is on — as a ``tb.<name>`` event on that thread's line of the
profile's host plane, on the device trace's clock.  Spans nest and
overlap; they do not sum, but their SELF times do, a thread: a span's
duration minus what the spans opened inside it on the same thread took
(one stack a thread; ``txtrace.self_us.<role>.<name>``, the role being
the thread's: ``serving``, ``lane``, ``io``, ``checkpoint``).  The
serving thread's selector is a span too (``loop_wait``, net/bus.py), so
every instant of that thread is socket wait, device wait (the self time
of ``dispatch_wait``, ``readback`` and ``full_sync`` there) or work.
*Request intervals*: the bus stamps every
commit group (``GroupTimeline``) and at reply release observes, once per
request, six consecutive ``txtrace.request.*`` intervals that sum to the
request's time in the server exactly.

**BLACKBOX** — a bounded per-replica ring of protocol events (command,
view, op, checksums, queue depths, tick) at one-append cost when
enabled, dumped to a postmortem artifact on oracle failure,
``DeviceStateUnrecoverable``, crash-path exits, and on demand.  VOPR
failing seeds write per-replica dumps next to ``vopr_viz_<seed>.txt``.

Cost discipline (obs/metrics.py's): everything starts OFF.  An untraced
request pays one attribute load + branch per hop site; stage sites pay
the same guard before any clock read; a disabled blackbox is ``None``.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..utils.tracer import tracer
from .metrics import registry as _obs

# Synthetic pid base for per-replica rows in the merged Chrome trace: a
# SimCluster runs every replica in one process, but each replica still
# gets its own Perfetto process row (and the flow arrows visibly cross
# them).  Below obs/profile.DEVICE_PID_BASE (1<<20), above real pids'
# typical range is irrelevant — rows are keyed by exact pid value.
REPLICA_PID_BASE = 1 << 18

# The thread-span vocabulary, in pipeline order (docs/tracing.md has the
# site, the thread and the bounds of each).  ``tb.<name>`` in a profile.
STAGES = (
    "socket_read",      # bus: the loop's callback for a readable socket
    "ingress_verify",   # bus: body checksum of one ingress frame
    "commit_group",     # bus: the synchronous replica call for one group
    "prepare",          # replica: header assign + hash chain per request
    "route",            # machine: a run's eligibility checks, at submit
    "stage_h2d",        # machine: staging fill + device_put of a run
    "device_execute",   # machine: the commit closure (lane thread if deferred)
    "general_commit",   # ... the general kernel's blocking route, whole
    "grow",             # ... its table growth check
    "dispatch",         # ... its jitted commit call(s)
    "full_sync",        # ... the general route's blocking device wait
    "cold_resolve",     # ... a FLAG_COLD batch's flagged ids, resolved exactly
    "cold_rehydrate",   # ...... its true cold rows back into the hot table
    "index_append",     # ... its secondary-index maintenance
    "merkle_refresh",   # ... touched-path leaf->root update kernels
    "unshard",          # machine: --shards' canonical copy, rebuilt for a read
    "wal_write",        # replica: journal appends of the group
    "wal_fsync",        # replica: the fsync barrier (io pool thread)
    "pipeline_flush",   # bus: flush because the request queue idled
    "dispatch_wait",    # machine: join of the lane closure
    "readback",         # machine: deferred D2H resolve (codes readback)
    "phase_b",          # replica: bookkeeping + reply build per op
    "reply_release",    # bus: reply writes of one group
    "checkpoint_capture",  # replica: a checkpoint's inline half, whole
    "checkpoint_d2h",   # ... every table column copied to the host
    "checkpoint_digest",  # ... the ledger's digest (a device program + wait)
    "checkpoint_write",  # replica: forest files, fsync, superblock (bg thread)
    "cold_evict",       # machine: a tier eviction, whole (serving thread)
    "cold_threshold",   # ... the timestamp that halves the hot window
    "cold_extract",     # ... the leaving rows compacted and packed (device)
    "cold_fetch",       # ... those rows to the host, one fetch
    "cold_spill",       # ... sorted by id, written, fsynced: the run file
    "cold_rehash",      # ... the hot table rebuilt without them (device)
    "cold_filter",      # ... their ids into the filter, and its upload
    "loop_wait",        # bus: the event loop asleep in its selector
)

# The serving thread's top-level synchronous sections (bus sites, never
# nested in one another): their durations add up to ``serve.busy_us``.
# The thread is "busy" in them while it blocks on the device;
# ``loop_wait`` is not one of them.
SERVING_SECTIONS = frozenset(
    ("ingress_verify", "commit_group", "pipeline_flush", "reply_release")
)

# The one name whose duration the registry also keeps by role,
# ``txtrace.stage.device_execute.<role>``: a deferred closure on the lane
# thread (`lane_closure_ms`), a blocking commit on the serving thread
# (`blocking_commit_ms`).  The other names that run on both threads are
# split by ``txtrace.self_us.<role>.<name>``.
BY_ROLE_STAGE = "device_execute"

# A thread's role, by the name its pool or its starter gave it; any other
# thread (the bus's loop thread; the main thread of a test, a tool or a
# simulator) is ``serving``.
_ROLE_PREFIXES = (("tb-dispatch", "lane"), ("tb-wal-fsync", "io"),
                  ("tb-checkpoint", "checkpoint"))


class _ThreadSpans(threading.local):
    """One thread's role and its stack of open spans; made at the thread's
    first ACTIVE span (``threading.local`` runs ``__init__`` once a thread)."""

    def __init__(self) -> None:
        name = threading.current_thread().name
        self.role = next(
            (role for prefix, role in _ROLE_PREFIXES
             if name.startswith(prefix)), "serving")
        self.stack: List["_StageSpan"] = []


_thread = _ThreadSpans()

# Consecutive intervals of one request inside the server; they sum to
# ``total`` exactly (integer microseconds of one clock).
REQUEST_INTERVALS = (
    "ingress",          # header read -> body read, verified, enqueued
    "admission_wait",   # enqueued -> its group is picked up
    "commit_host",      # pickup -> the synchronous replica call returned
    "results_wait",     # returned -> the replies promise resolved
    "barrier_wait",     # results -> max(results, fsync done)
    "reply_release",    # -> the group's replies are written
)
_REQUEST_SERIES = tuple(
    f"txtrace.request.{name}" for name in REQUEST_INTERVALS + ("total",)
)


def now_us() -> int:
    """The request timeline's clock: integer microseconds, monotonic."""
    return time.monotonic_ns() // 1000


class GroupTimeline:
    """Stamps (``now_us``) of one commit group from pickup to release,
    made by the bus only while ``txtrace.active``.  ``t_results`` and
    ``t_durable`` are taken in the futures' done callbacks — on whichever
    thread completes them — not when the awaiting task resumes: the
    resumption waits for the serving thread, which is what is measured."""

    __slots__ = ("seq", "t_pickup", "t_returned", "t_results", "t_durable",
                 "t_released")

    def __init__(self, seq: int) -> None:
        self.seq = seq
        self.t_pickup = now_us()
        self.t_returned = self.t_results = self.t_durable = 0
        self.t_released = 0

    def returned(self, replies, fsync) -> None:
        """The synchronous call returned ``(replies, fsync)``: either may
        be a concurrent future still to complete."""
        self.t_returned = now_us()
        if hasattr(replies, "add_done_callback"):
            replies.add_done_callback(self._results_done)
        else:
            self.t_results = self.t_returned
        if fsync is not None:
            fsync.add_done_callback(self._durable_done)

    def _results_done(self, _future) -> None:
        self.t_results = now_us()

    def _durable_done(self, _future) -> None:
        self.t_durable = now_us()

    def intervals(self, t_header: int, t_enqueued: int) -> Tuple[int, ...]:
        """The six REQUEST_INTERVALS of one request of this group, then
        their total; consecutive stamps, so the six sum to the total."""
        results = max(self.t_results, self.t_returned)
        barrier = max(results, self.t_durable)
        stamps = (t_header, t_enqueued, self.t_pickup, self.t_returned,
                  results, barrier, self.t_released)
        return tuple(
            b - a for a, b in zip(stamps, stamps[1:])
        ) + (self.t_released - t_header,)


class _StageSpan:
    """One open ``txtrace.stage`` block: a TraceMe annotation held open
    (the profiler's clock) around a perf_counter_ns duration, and a frame
    on its thread's stack: the spans that close inside it add their
    durations to ``_children_us``, and what is left is its self time."""

    __slots__ = ("_tx", "_name", "_annotation", "_t0", "_children_us")

    def __init__(self, tx, name: str, annotation) -> None:
        self._tx = tx
        self._name = name
        self._annotation = annotation

    def __enter__(self) -> None:
        self._children_us = 0.0
        _thread.stack.append(self)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> bool:
        us = (time.perf_counter_ns() - self._t0) / 1e3
        self._annotation.__exit__(*exc)
        stack = _thread.stack
        stack.pop()
        if stack:
            stack[-1]._children_us += us
        self._tx.stage_observe(self._name, us, us - self._children_us,
                               _thread.role)
        return False


# What an inactive stage site gets: shared, re-entrant, does nothing.
_STAGE_OFF = contextlib.nullcontext()


def _mix64(x: int) -> int:
    """splitmix64 finalizer: cheap, well-distributed u64 ids."""
    x &= 0xFFFF_FFFF_FFFF_FFFF
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9 & 0xFFFF_FFFF_FFFF_FFFF
    x = (x ^ (x >> 27)) * 0x94D049BB133111EB & 0xFFFF_FFFF_FFFF_FFFF
    return x ^ (x >> 31)


def parse_sample(spec: str) -> int:
    """``TB_TRACE_SAMPLE`` grammar -> sample period N (0 = off).

    Accepts ``1/N`` (one in N), a bare integer ``N`` (same), or
    empty/``0`` (off).  Malformed values read as off — a typo must not
    take down a server at import time."""
    spec = (spec or "").strip()
    if not spec:
        return 0
    try:
        if "/" in spec:
            num, den = spec.split("/", 1)
            if int(num) != 1:
                return 0
            return max(0, int(den))
        return max(0, int(spec))
    except ValueError:
        return 0


class TxTracer:
    """Process-global trace-id sampler + flow emitter + stage ledger."""

    def __init__(self) -> None:
        self.sample_every = parse_sample(os.environ.get("TB_TRACE_SAMPLE", ""))
        # Attribution accumulation is independent of sampling: a caller
        # arms it for every batch (no sampling) while flow tracing stays off.
        self.attribution = False
        # Sequence number of the commit group the serving thread is in
        # (set by the bus at pickup while active): the default ``seq``
        # argument of a stage's profile event.
        self.group_seq = 0
        self._trace_annotation = None  # jax.profiler's, on first use
        self._seq = 0
        self._lock = threading.Lock()
        # name -> [count, total_us, self_us]; plain dict + lock (stage sites
        # are hot-path-adjacent, but only ever taken when attribution is on).
        self._stages: Dict[str, List[float]] = {}
        self._pids_named: set = set()

    # -- sampling / ids ------------------------------------------------------

    @property
    def sampling(self) -> bool:
        return self.sample_every > 0

    @property
    def active(self) -> bool:
        """Any stage site should bother reading the clock."""
        return self.attribution or _obs.enabled

    def maybe_trace(self, key: int = 0) -> int:
        """Return a fresh nonzero u64 trace id for a sampled request, or 0.

        Sampling is a counter (every Nth request), so ``1/1`` traces
        everything and a pinned request sequence yields a deterministic
        id stream; the id itself mixes the sequence with ``key`` (e.g.
        the client id) so concurrent clients cannot collide."""
        n = self.sample_every
        if n <= 0:
            return 0
        with self._lock:
            self._seq += 1
            seq = self._seq
        if seq % n:
            return 0
        return _mix64((seq << 20) ^ key) or 1  # force nonzero

    # -- flow events (the causal chain in the merged Chrome trace) -----------

    def _pid_tid(self, replica: Optional[int]):
        pid = (
            REPLICA_PID_BASE + replica if replica is not None
            else os.getpid()
        )
        tid = threading.get_ident() & 0xFFFF
        if replica is not None and pid not in self._pids_named:
            self._pids_named.add(pid)
            tracer.emit({
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": f"replica r{replica}"},
            })
        return pid, tid

    def hop(self, trace: int, name: str, phase: str = "step",
            replica: Optional[int] = None, **args) -> None:
        """One hop of a traced request's causal chain.

        Emits a 1 us slice named ``name`` plus the Chrome flow event
        (``ph s/t/f`` by ``phase`` start/step/end) that links it to the
        other hops carrying the same trace id.  No-op when the tracer is
        off or the frame is untraced (trace == 0)."""
        if not trace or not tracer.enabled:
            return
        pid, tid = self._pid_tid(replica)
        ts = time.perf_counter_ns() / 1e3
        args["trace"] = f"{trace:#x}"
        tracer.emit({
            "name": name, "ph": "X", "cat": "txtrace",
            "ts": ts, "dur": 1.0, "pid": pid, "tid": tid, "args": args,
        })
        flow = {
            "name": "tx", "cat": "txflow",
            "ph": {"start": "s", "step": "t", "end": "f"}[phase],
            "id": trace, "ts": ts + 0.5, "pid": pid, "tid": tid,
        }
        if phase == "end":
            flow["bp"] = "e"
        tracer.emit(flow)

    @contextlib.contextmanager
    def span(self, trace: int, name: str, replica: Optional[int] = None,
             **args):
        """A timed slice bound into a traced request's flow (a hop with
        real duration).  No-op when untraced or the tracer is off."""
        if not trace or not tracer.enabled:
            yield
            return
        pid, tid = self._pid_tid(replica)
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            end = time.perf_counter_ns()
            args["trace"] = f"{trace:#x}"
            ts = start / 1e3
            tracer.emit({
                "name": name, "ph": "X", "cat": "txtrace",
                "ts": ts, "dur": (end - start) / 1e3,
                "pid": pid, "tid": tid, "args": args,
            })
            tracer.emit({
                "name": "tx", "cat": "txflow", "ph": "t",
                "id": trace, "ts": ts + (end - start) / 2e3,
                "pid": pid, "tid": tid,
            })

    # -- stage ledger (attribution) ------------------------------------------

    def stage_observe(self, name: str, us: float,
                      self_us: Optional[float] = None,
                      role: Optional[str] = None) -> None:
        """Record one thread span: its duration, its self time (default:
        all of it, a span with no child) and its thread's role (default:
        the calling thread's).  ``stage`` calls this; a site that has a
        duration already guards on ``txtrace.active`` BEFORE reading any
        clock."""
        if self_us is None:
            self_us = us
        if role is None:
            role = _thread.role
        if _obs.enabled:
            _obs.histogram(f"txtrace.stage.{name}", "us").observe(us)
            _obs.counter(f"txtrace.self_us.{role}.{name}").inc(
                int(self_us + 0.5))
            if name == BY_ROLE_STAGE:
                _obs.histogram(
                    f"txtrace.stage.{name}.{role}", "us").observe(us)
            if name in SERVING_SECTIONS:
                _obs.counter("serve.busy_us").inc(int(us))
        if self.attribution:
            with self._lock:
                slot = self._stages.get(name)
                if slot is None:
                    slot = self._stages[name] = [0, 0.0, 0.0]
                slot[0] += 1
                slot[1] += us
                slot[2] += self_us

    def stage(self, name: Optional[str], seq: int = 0, n: int = 0):
        """Timed thread span, the only way a commit-path site times a
        block.  Inactive (or ``name`` None) it hands back one shared no-op:
        no clock read, nothing allocated.  Active it observes
        ``txtrace.stage.<name>`` and its self time, and holds a
        ``tb.<name>`` TraceMe annotation open for the block, with the
        group's sequence number (``seq``, default: the group the serving
        thread is in; sites that run later or on another thread pass the
        one they captured at submit), where the site knows it a count
        ``n``, and the thread's ``role``."""
        if name is None or not self.active:
            return _STAGE_OFF
        annotation = self._trace_annotation
        if annotation is None:
            # Here, not at import: the client, the simulator and tbmc
            # import txtrace and must not pull the profiler in (importing
            # it starts no backend either).
            from jax.profiler import TraceAnnotation

            annotation = self._trace_annotation = TraceAnnotation
        return _StageSpan(self, name, annotation(
            "tb." + name, seq=seq or self.group_seq, n=n, role=_thread.role
        ))

    def group_begin(self, seq: int) -> GroupTimeline:
        """The bus picked a group up (callers guard on ``active``)."""
        self.group_seq = seq
        return GroupTimeline(seq)

    def request_observe(self, timeline: GroupTimeline, t_header: int,
                        t_enqueued: int) -> None:
        """One released request: its six consecutive intervals and their
        total, into seven series of equal count (so their means add)."""
        if not _obs.enabled:
            return  # registry series only: the stage table is per batch
        values = timeline.intervals(t_header, t_enqueued)
        for series, us in zip(_REQUEST_SERIES, values):
            _obs.histogram(series, "us").observe(us)

    def stage_totals(self) -> Dict[str, dict]:
        """Accumulated {stage: {count, us, self_us}} since the last reset.
        Over the spans of one thread the ``self_us`` sum to the ``us`` of
        its top-level spans."""
        with self._lock:
            return {
                name: {"count": c, "us": round(us, 1),
                       "self_us": round(self_us, 1)}
                for name, (c, us, self_us) in sorted(self._stages.items())
            }

    def reset_stages(self) -> None:
        with self._lock:
            self._stages.clear()

    @contextlib.contextmanager
    def attribution_scope(self, reset: bool = True):
        """Enable the stage ledger for a block, ALWAYS disable on exit
        (the registry's enabled_scope discipline — txtrace is
        process-global too)."""
        if reset:
            self.reset_stages()
        self.attribution = True
        try:
            yield self
        finally:
            self.attribution = False

    @contextlib.contextmanager
    def sampling_scope(self, every: int = 1):
        """Force a sample period for a block (tests/tools), restoring the
        env-derived value on exit."""
        prev = self.sample_every
        self.sample_every = max(0, int(every))
        try:
            yield self
        finally:
            self.sample_every = prev


class Blackbox:
    """Bounded ring of protocol events: the per-replica flight recorder.

    ``record`` is one slot store + one int add (the sim's hot loop calls
    it per protocol event); the ring overwrites oldest-first past ``cap``
    and ``seq`` preserves the true event count, so a dump states exactly
    how much history was lost."""

    __slots__ = ("name", "cap", "seq", "_ring")

    def __init__(self, name: str, cap: int = 512) -> None:
        assert cap > 0
        self.name = name
        self.cap = cap
        self.seq = 0
        self._ring: List[Optional[tuple]] = [None] * cap

    def record(self, event: str, **kw) -> None:
        self._ring[self.seq % self.cap] = (self.seq, event, kw)
        self.seq += 1

    def snapshot(self) -> List[dict]:
        """Retained events, oldest first."""
        start = max(0, self.seq - self.cap)
        out = []
        for i in range(start, self.seq):
            rec = self._ring[i % self.cap]
            if rec is None:  # pragma: no cover — ring invariant
                continue
            seq, event, kw = rec
            out.append({"seq": seq, "ev": event, **kw})
        return out

    def dump_text(self) -> str:
        """One JSON line per retained event, with a provenance header."""
        import json as _json

        events = self.snapshot()
        lost = self.seq - len(events)
        lines = [
            f"# blackbox {self.name}: {self.seq} events recorded, "
            f"{len(events)} retained (cap {self.cap}), {lost} lost",
        ]
        lines.extend(_json.dumps(e, default=str) for e in events)
        return "\n".join(lines) + "\n"


def dump_blackboxes(boxes, directory: str, prefix: str = "blackbox") -> list:
    """Write one ``<prefix>_<name>.txt`` per recorder; returns the paths.
    Best-effort (postmortem paths must never raise over the original
    failure): an unwritable directory yields an empty list."""
    paths = []
    for box in boxes:
        if box is None:
            continue
        path = os.path.join(directory, f"{prefix}_{box.name}.txt")
        try:
            with open(path, "w") as f:
                f.write(box.dump_text())
        except OSError:
            continue
        paths.append(path)
    return paths


# The process-global tracer (the registry/tracer singleton pattern).
txtrace = TxTracer()
