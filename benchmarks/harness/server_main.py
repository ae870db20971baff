"""Launcher of the server under test: the program's normal entry point
(`tigerbeetle_tpu.cli.main`, what `python -m tigerbeetle_tpu` calls) run
in-process, beside ONE helper thread that answers the parent's cues.

`start` has no hook for a profiler or a mid-run snapshot, and only the
process that holds the chip can trace it or read its memory statistics.  The
helper thread blocks on stdin and costs nothing until a cue arrives:

    {"cmd": "snapshot"}              -> the metrics registry, as it stands
    {"cmd": "trace_start", "dir": d} -> jax.profiler.start_trace(d)
    {"cmd": "trace_stop"}            -> jax.profiler.stop_trace()
    {"cmd": "memory"}                -> memory_stats() of every device

Each answer is written to the cue's `done` path (temp name, then renamed).
"""

from __future__ import annotations

import faulthandler
import json
import os
import signal
import sys
import threading
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _answer(cue: dict) -> dict:
    cmd = cue["cmd"]
    if cmd == "snapshot":
        from tigerbeetle_tpu.obs.metrics import registry

        return registry.snapshot()
    import jax

    if cmd == "trace_start":
        # The device and the host's runtime spans; not the Python tracer,
        # whose per-call hook would slow the server it is there to watch.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(cue["dir"], profiler_options=options)
        return {}
    if cmd == "trace_stop":
        jax.profiler.stop_trace()
        return {}
    if cmd == "memory":
        return {"devices": [
            {"id": d.id, "stats": d.memory_stats() or {}}
            for d in jax.devices()
        ]}
    raise ValueError(f"unknown cue {cmd!r}")


def _serve_cues(stream) -> None:
    for line in stream:
        cue = json.loads(line)
        try:
            answer = _answer(cue)
        except Exception as err:  # reported to the parent, which fails the run
            traceback.print_exc()
            answer = {"error": f"{type(err).__name__}: {err}"}
        tmp = cue["done"] + ".tmp"
        with open(tmp, "w") as f:
            json.dump(answer, f)
        os.replace(tmp, cue["done"])


def _print_pending_tasks(_signum, _frame) -> None:
    """SIGUSR2: where every unfinished asyncio task stands (stderr)."""
    import asyncio
    import gc

    for task in [o for o in gc.get_objects() if isinstance(o, asyncio.Task)]:
        if not task.done():
            print(f"pending task: {task!r}", file=sys.stderr)
            task.print_stack(file=sys.stderr)
    sys.stderr.flush()


def main(argv) -> int:
    sys.path.insert(0, ROOT)
    from benchmarks.harness import procs

    procs.die_with_parent()  # a server left on the chip serves later runs
    # A server that does not stop is asked what it is waiting for: its
    # asyncio tasks (SIGUSR2), then every thread's stack (SIGUSR1).
    signal.signal(signal.SIGUSR2, _print_pending_tasks)
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    threading.Thread(target=_serve_cues, args=(sys.stdin,),
                     daemon=True).start()
    from tigerbeetle_tpu import cli

    return cli.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
