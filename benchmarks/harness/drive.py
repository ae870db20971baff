"""Closed-loop load: `sessions` client sessions over TCP, each with one
request in flight (a TigerBeetle session's protocol limit), each sending its
own queue of requests built before the window opened.

Inside the window a session only sends, receives and notes two clock
readings per request.  A caller that has to follow a run from another thread
(the traced run's profiler cue) hands `run_queues` a `Progress`, which then
also counts the answered requests; without one nothing is counted.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Sent:
    """One request as the client saw it."""
    session: int
    index: int            # position in the session's queue
    operation: str
    events: int
    t_send: float         # time.monotonic() before the send
    t_reply: float        # ... after the reply was decoded
    codes: Optional[list]  # the reply's (index, code) pairs; None on error
    error: Optional[str] = None


class Progress:
    """How far one `run_queues` call has come, for a watcher on another
    thread: seconds since the run began, requests answered, and whether every
    session has ended."""

    def __init__(self) -> None:
        self._changed = threading.Condition()
        self.began: Optional[float] = None   # time.monotonic()
        self.answered = 0
        self.finished = False

    def begin(self) -> None:
        with self._changed:
            self.began = time.monotonic()
            self._changed.notify_all()

    def reply(self) -> None:
        with self._changed:
            self.answered += 1
            self._changed.notify_all()

    def finish(self) -> None:
        with self._changed:
            self.finished = True
            self._changed.notify_all()

    def wait(self, due: Callable[[float, int], bool], wake_s: float
             ) -> Tuple[float, int]:
        """Block until `due(seconds since the run began, answered)` holds or
        the run has finished; returns those two as they then stand.  `due` is
        asked again at every reply and once `wake_s` seconds have passed."""
        with self._changed:
            while True:
                at_s = (0.0 if self.began is None
                        else time.monotonic() - self.began)
                if (self.began is not None and due(at_s, self.answered)
                        ) or self.finished:
                    return at_s, self.answered
                self._changed.wait(wake_s - at_s if wake_s > at_s else None)


def connect(port: int, sessions: int, seed: int, timeout_s: float) -> list:
    from tigerbeetle_tpu.client import Client

    # Client ids from the seed (the client's own default draws from
    # `secrets`): odd and distinct.
    return [
        Client([("127.0.0.1", port)], cluster=0, timeout_s=timeout_s,
               client_id=((seed & 0xFFFF_FFFF) << 32 | (s + 1) << 1 | 1))
        for s in range(sessions)
    ]


def _send(clients, queues, s: int, k: int) -> Sent:
    operation, rows = queues[s][k]
    t0 = time.monotonic()
    try:
        codes = getattr(clients[s], operation)(rows)
    except Exception as err:  # reported as a failed request
        return Sent(s, k, operation, len(rows), t0, time.monotonic(), None,
                    f"{type(err).__name__}: {err}")
    return Sent(s, k, operation, len(rows), t0, time.monotonic(), codes)


def run_queues(clients: Sequence, queues: Sequence[list],
               seconds: Optional[float] = None,
               progress: Optional[Progress] = None) -> List[Sent]:
    """Every session sends its queue in order, all sessions at once, each its
    next request as soon as its reply has come, until its queue is empty or —
    with `seconds` — the time is up (a request in flight then is waited
    for).  A session stops at its first error.  `progress`, where given, is
    told of the run's begin, of every answered request and of its end."""
    deadline = time.monotonic() + (seconds or 0.0)
    records: List[List[Sent]] = [[] for _ in queues]

    def session(s: int) -> None:
        for k in range(len(queues[s])):
            if seconds is not None and time.monotonic() >= deadline:
                break
            records[s].append(_send(clients, queues, s, k))
            if records[s][-1].error:
                break
            if progress is not None:
                progress.reply()

    threads = [threading.Thread(target=session, args=(s,), daemon=True)
               for s in range(len(queues))]
    if progress is not None:
        progress.begin()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        if progress is not None:
            progress.finish()
    return [r for per_session in records for r in per_session]


def latency_quantile_ms(records: Sequence[Sent], q: float) -> Optional[float]:
    """Request->reply milliseconds of the answered requests: the smallest
    sample with at least `q` of the samples at or below it (nearest rank)."""
    ordered = sorted((r.t_reply - r.t_send) * 1e3
                     for r in records if not r.error)
    if not ordered:
        return None
    return ordered[max(0, math.ceil(q * len(ordered) - 1e-9) - 1)]
