"""Tracer: span tree with typed events, Chrome-trace / Perfetto output.

The reference tracer (src/tracer.zig:1-78) records typed spans (commit,
checkpoint, state_machine_{prefetch,commit,compact}, grid I/O, io_flush)
into slots, with a build-time backend choice (none / Tracy).  Here the
backend choice is runtime (``none`` / ``json``): ``json`` appends Chrome
``trace_event`` records (the format Perfetto/chrome://tracing load natively
— the TPU-world analogue of a Tracy capture, and the same format
``jax.profiler`` emits, so device and host traces line up side by side).

Usage::

    from tigerbeetle_tpu.utils.tracer import tracer
    with tracer.span("commit", op=42):
        ...
    tracer.start("replica.tick"); ...; tracer.stop("replica.tick")
    tracer.dump("trace.json")

Zero overhead when disabled: ``span`` is a no-op context manager.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Tuple


class Tracer:
    # Bounded buffer (tracer.zig's fixed slot count): recording stops at the
    # cap and further events are counted as dropped, never unbounded RAM.
    EVENTS_MAX = 1_000_000

    def __init__(self, backend: str = "none") -> None:
        self.backend = backend
        self._events: List[dict] = []
        # Open start()/stop() spans, keyed (thread id, name) — see start().
        self._open: Dict[Tuple[int, str], int] = {}
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self.dropped = 0

    @property
    def enabled(self) -> bool:
        return self.backend != "none"

    def enable(self, backend: str = "json") -> None:
        self.backend = backend

    @contextlib.contextmanager
    def span(self, name: str, **args):
        if not self.enabled:
            yield
            return
        start = time.perf_counter_ns()
        try:
            yield
        finally:
            end = time.perf_counter_ns()
            self._emit(name, start, end, args)

    def start(self, name: str) -> None:
        """Open a span closed by a later stop(name) on the SAME thread.

        Keyed by (thread, name) under the lock: two threads running
        same-named spans concurrently (e.g. ``checkpoint`` on the serving
        thread while the background writer runs its own) must not collide —
        an unkeyed dict let one thread's stop() consume the other's start
        timestamp, corrupting both durations."""
        if self.enabled:
            with self._lock:
                self._open[(threading.get_ident(), name)] = (
                    time.perf_counter_ns()
                )

    def stop(self, name: str, **args) -> None:
        if not self.enabled:
            return
        with self._lock:
            begin = self._open.pop((threading.get_ident(), name), None)
        if begin is not None:
            self._emit(name, begin, time.perf_counter_ns(), args)

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) >= self.EVENTS_MAX:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "ph": "i", "s": "t",
                "ts": time.perf_counter_ns() / 1e3,
                "pid": self._pid, "tid": threading.get_ident() & 0xFFFF,
                "args": args,
            })

    def emit(self, event: dict) -> None:
        """Append one pre-built Chrome trace event (bounded like _emit).
        The cross-process flow events (``ph`` s/t/f) and per-replica
        process_name metadata of obs/txtrace.py enter the buffer here —
        shapes the span helpers above cannot express."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) >= self.EVENTS_MAX:
                self.dropped += 1
                return
            self._events.append(event)

    def _emit(self, name: str, start_ns: int, end_ns: int, args: dict) -> None:
        with self._lock:
            if len(self._events) >= self.EVENTS_MAX:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "ph": "X",
                "ts": start_ns / 1e3, "dur": (end_ns - start_ns) / 1e3,
                "pid": self._pid, "tid": threading.get_ident() & 0xFFFF,
                "args": args,
            })

    def dump(self, path: str) -> int:
        """Write accumulated events as a Chrome trace; returns event count."""
        with self._lock:
            events = list(self._events)
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return len(events)

    def drain(self) -> List[dict]:
        """Hand off (and clear) the buffered events.  Also resets the
        dropped count: it belongs to the drained epoch, and a stale nonzero
        value would defeat the at-exit empty-buffer skip that protects a
        merged trace from being overwritten (obs/profile)."""
        with self._lock:
            events = self._events
            self._events = []
            self.dropped = 0
        return events


# Process-global tracer (tracer.zig's comptime-selected global); enable via
# TB_TRACE=json (trace written at exit to TB_TRACE_PATH, default
# ./tb_trace.json) or programmatically via tracer.enable() + tracer.dump().
tracer = Tracer(os.environ.get("TB_TRACE", "none"))

if tracer.enabled:
    import atexit

    @atexit.register
    def _dump_at_exit() -> None:
        if not tracer._events and not tracer.dropped:
            # Nothing buffered: the process either traced nothing or a
            # merged dump (obs/profile.merge_with_tracer) already drained
            # the events into a host+device trace — overwriting that file
            # with an empty host-only one would destroy it.
            return
        path = os.environ.get("TB_TRACE_PATH", "tb_trace.json")
        try:
            n = tracer.dump(path)
        except OSError:
            return
        print(f"tracer: wrote {n} events to {path} "
              f"({tracer.dropped} dropped)", file=__import__("sys").stderr)
