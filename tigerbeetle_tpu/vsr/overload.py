"""Overload control: priority classes, bounded admission, explicit busy.

The reference treats overload as a design constraint discharged statically
(static allocation, bounded queues, client eviction — message_pool.zig,
client_sessions.zig); this port carries the same *bounds* but, before this
module, not the *behavior*: a full pipeline / WAL / send queue silently
dropped the message and the client burned its whole 30 s timeout before
retrying.  This module is the shared vocabulary for the fourth fault domain
(docs/fault_domains.md): overload.

Three transport-agnostic pieces, used by the TCP buses (net/), the
consensus primary (vsr/consensus.py), and the VOPR overload governor
(sim/cluster.py):

- **Priority classes** (``classify``): every wire command maps to one of
  four drain/shed classes.  A client flood must never starve a view change
  or repair — the election traffic that would *end* the overload is
  exactly what naive FIFO queues drop first.

- **AdmissionQueue**: a bounded multi-class queue that drains
  highest-priority-first with per-client round-robin fairness inside the
  client class (one hot client cannot monopolize the pipeline), and sheds
  lowest-priority-first on overflow.  With ``priority=False`` it degrades
  to a plain bounded FIFO with tail drop — the negative control the VOPR
  liveness oracle must demonstrably fail against.

- **busy signaling** helpers: shed a *new client request*, don't drop it —
  reply with a retryable ``Command.busy`` carrying a retry-after tick hint
  (wire.BUSY_*), so the client backs off deliberately instead of timing
  out blindly.

Everything is gated: ``enabled()`` reads ``TB_OVERLOAD`` (the CLI's
``--overload-control`` sets it), and the off path is bit-identical to the
pre-overload behavior — pinned VOPR seeds and the bench differential
replay unchanged.
"""

from __future__ import annotations

import os
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Tuple

from . import wire

# Drain order: lower class number drains first, higher sheds first.
CLASS_VIEW_CHANGE = 0   # elections + liveness probes: ends the overload
CLASS_REPAIR = 1        # repair/sync: heals the cluster under pressure
CLASS_PREPARE = 2       # prepare/commit/reply: the replication stream
CLASS_CLIENT = 3        # client requests: the load being shed

CLASS_NAMES = {
    CLASS_VIEW_CHANGE: "view_change",
    CLASS_REPAIR: "repair",
    CLASS_PREPARE: "prepare",
    CLASS_CLIENT: "client",
}

_COMMAND_CLASS = {
    # View change + the liveness probes that trigger/settle it.  Pings are
    # deliberately here: the primary-suspicion probe and the Marzullo clock
    # both ride ping/pong, and a flood that starves them first fakes a dead
    # primary and then blocks the resulting election.
    wire.Command.start_view_change: CLASS_VIEW_CHANGE,
    wire.Command.do_view_change: CLASS_VIEW_CHANGE,
    wire.Command.start_view: CLASS_VIEW_CHANGE,
    wire.Command.request_start_view: CLASS_VIEW_CHANGE,
    wire.Command.nack_prepare: CLASS_VIEW_CHANGE,
    wire.Command.ping: CLASS_VIEW_CHANGE,
    wire.Command.pong: CLASS_VIEW_CHANGE,
    # Repair + state sync.
    wire.Command.request_headers: CLASS_REPAIR,
    wire.Command.request_prepare: CLASS_REPAIR,
    wire.Command.headers: CLASS_REPAIR,
    wire.Command.request_reply: CLASS_REPAIR,
    wire.Command.request_blocks: CLASS_REPAIR,
    wire.Command.block: CLASS_REPAIR,
    wire.Command.request_sync_checkpoint: CLASS_REPAIR,
    wire.Command.sync_checkpoint: CLASS_REPAIR,
    # The replication stream and its client-visible tail.
    wire.Command.prepare: CLASS_PREPARE,
    wire.Command.prepare_ok: CLASS_PREPARE,
    wire.Command.commit: CLASS_PREPARE,
    wire.Command.reply: CLASS_PREPARE,
    # Client plane.
    wire.Command.request: CLASS_CLIENT,
    wire.Command.ping_client: CLASS_CLIENT,
    wire.Command.pong_client: CLASS_CLIENT,
    wire.Command.eviction: CLASS_CLIENT,
    wire.Command.busy: CLASS_CLIENT,
}


def classify(command: wire.Command) -> int:
    """Drain/shed class for a wire command (unknown commands shed first)."""
    return _COMMAND_CLASS.get(command, CLASS_CLIENT)


def enabled(env: Optional[dict] = None) -> bool:
    """TB_OVERLOAD gate ('' / '0' / 'off' all mean off)."""
    value = (env if env is not None else os.environ).get("TB_OVERLOAD", "")
    return str(value).strip().lower() not in ("", "0", "off", "false")


def busy_message(
    replica_index: int,
    cluster: int,
    view: int,
    request_h,
    reason: int,
    retry_after_ticks: int,
) -> bytes:
    """Encode the explicit shed signal for one client request header."""
    h = wire.new_header(
        wire.Command.busy,
        cluster=cluster,
        view=view,
        request_checksum=wire.header_checksum(request_h),
        client=wire.u128(request_h, "client"),
        request=int(request_h["request"]),
        retry_after_ticks=int(retry_after_ticks),
        reason=int(reason),
    )
    h["replica"] = replica_index
    return wire.encode(h)


class AdmissionQueue:
    """Bounded, class-prioritized ingress queue with per-client fairness.

    ``offer`` either admits an item or returns the items shed to make room
    (possibly the offered item itself); ``pop`` drains one item —
    highest-priority class first; within CLASS_CLIENT, round-robin over
    client ids so one hot client cannot monopolize the drain budget.
    ``priority=False`` turns both knobs off (bounded FIFO, tail drop): the
    VOPR's negative control.

    Counters are plain attributes (the caller mirrors them into the obs
    registry); the queue itself has no metrics dependency so the sim can
    use it without arming the registry.
    """

    def __init__(self, cap: int, priority: bool = True) -> None:
        assert cap > 0
        self.cap = cap
        self.priority = priority
        self.size = 0
        self.admitted = 0
        self.shed = 0
        self.shed_by_class: Dict[int, int] = {c: 0 for c in CLASS_NAMES}
        self.depth_peak = 0
        # priority mode: one deque per non-client class + per-client deques
        # with a round-robin rotation for the client class.
        self._classes: Dict[int, Deque] = {
            CLASS_VIEW_CHANGE: deque(),
            CLASS_REPAIR: deque(),
            CLASS_PREPARE: deque(),
        }
        self._clients: "OrderedDict[int, Deque]" = OrderedDict()
        # FIFO mode: a single deque of (cls, client, item).
        self._fifo: Deque = deque()

    def __len__(self) -> int:
        return self.size

    # -- intake ---------------------------------------------------------------

    def offer(self, cls: int, client: int, item) -> List[Tuple[int, int, object]]:
        """Enqueue; returns the list of (cls, client, item) SHED to honor
        the cap (empty when admitted without eviction).  In priority mode a
        full queue evicts from the lowest-priority tail — so a view-change
        message displaces a queued client request, never the reverse; an
        offered item that is itself the lowest priority is shed directly.
        FIFO mode is plain tail drop."""
        shed: List[Tuple[int, int, object]] = []
        if not self.priority:
            if self.size >= self.cap:
                self._count_shed(cls)
                return [(cls, client, item)]
            self._fifo.append((cls, client, item))
            self.size += 1
            self._note_depth()
            self.admitted += 1
            return shed
        if self.size >= self.cap:
            victim = self._evict_lowest(cls, client)
            if victim is None:
                self._count_shed(cls)
                return [(cls, client, item)]
            shed.append(victim)
        if cls == CLASS_CLIENT:
            self._clients.setdefault(client, deque()).append(item)
        else:
            self._classes[cls].append(item)
        self.size += 1
        self._note_depth()
        self.admitted += 1
        return shed

    def _note_depth(self) -> None:
        if self.size > self.depth_peak:
            self.depth_peak = self.size

    def _count_shed(self, cls: int) -> None:
        self.shed += 1
        self.shed_by_class[cls] = self.shed_by_class.get(cls, 0) + 1

    def _evict_lowest(self, incoming_cls: int, incoming_client: int = 0):
        """Drop one queued item to admit the incoming one; None if nothing
        qualifies.  A higher-priority arrival evicts from the lowest class
        present.  A CLIENT-class arrival may also displace the FATTEST
        client's tail when that backlog exceeds the arriving client's own
        by more than one — max-min fairness at ADMISSION, not just drain:
        a flood that fills the queue cannot lock other clients out, but
        equal-share clients never churn each other out either."""
        for cls in (CLASS_CLIENT, CLASS_PREPARE, CLASS_REPAIR):
            if cls < incoming_cls or (
                cls == incoming_cls and cls != CLASS_CLIENT
            ):
                return None
            if cls == CLASS_CLIENT:
                # Shed from the FATTEST client's tail: the hot client pays
                # for its own flood before anyone else does.
                if not self._clients:
                    continue
                fat = max(
                    self._clients, key=lambda c: len(self._clients[c])
                )
                q = self._clients[fat]
                if incoming_cls == CLASS_CLIENT:
                    mine = len(self._clients.get(incoming_client, ()))
                    if len(q) <= mine + 1:
                        return None  # equal shares: shed the arrival
                item = q.pop()
                if not q:
                    del self._clients[fat]
                self.size -= 1
                self._count_shed(cls)
                return (cls, fat, item)
            q = self._classes[cls]
            if q:
                item = q.pop()
                self.size -= 1
                self._count_shed(cls)
                return (cls, 0, item)
        return None

    # -- drain ----------------------------------------------------------------

    def pop(self) -> Optional[Tuple[int, int, object]]:
        """Dequeue one item, or None when empty."""
        if self.size == 0:
            return None
        self.size -= 1
        if not self.priority:
            return self._fifo.popleft()
        for cls in (CLASS_VIEW_CHANGE, CLASS_REPAIR, CLASS_PREPARE):
            q = self._classes[cls]
            if q:
                return (cls, 0, q.popleft())
        # Client class: round-robin — serve the head of the least-recently-
        # served client's deque, then rotate it to the back.
        client, q = next(iter(self._clients.items()))
        item = q.popleft()
        self._clients.move_to_end(client)
        if not q:
            del self._clients[client]
        return (CLASS_CLIENT, client, item)

