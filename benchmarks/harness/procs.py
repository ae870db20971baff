"""No process the benchmark starts outlives it, on any path out of a run.

Three independent means, because a server left on the chip would serve, or
block, every later run:

- a child calls `die_with_parent()` first thing: the kernel then sends it
  SIGKILL the moment the parent is gone, however the parent went (a SIGKILL
  at a time limit included);
- `run.py` turns SIGTERM / SIGINT / SIGHUP into an exception, so its
  `finally` blocks run and kill what is still alive;
- `run.py` is a sub-reaper: a grandchild whose parent died is handed to it,
  and `kill_children()` on the way out ends and reaps everything below it.
"""

from __future__ import annotations

import ctypes
import os
import signal
import time
from typing import List

PR_SET_PDEATHSIG = 1
PR_SET_CHILD_SUBREAPER = 36
PARENT_PID_ENV = "BENCH_PARENT_PID"


def _prctl(option: int, value: int) -> None:
    ctypes.CDLL(None, use_errno=True).prctl(option, value, 0, 0, 0)


def child_env(env: dict) -> dict:
    """`env` plus the pid a child's `die_with_parent` checks against."""
    return dict(env, **{PARENT_PID_ENV: str(os.getpid())})


def die_with_parent() -> None:
    """SIGKILL from the kernel when the parent dies; and where it died
    before this call (the process was handed to another parent), leave."""
    _prctl(PR_SET_PDEATHSIG, signal.SIGKILL)
    parent = os.environ.get(PARENT_PID_ENV)
    if parent and os.getppid() != int(parent):
        os._exit(1)


def adopt_orphans() -> None:
    _prctl(PR_SET_CHILD_SUBREAPER, 1)


def children() -> List[int]:
    """Pids whose parent is this process (zombies included)."""
    me, found = os.getpid(), []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                # pid (comm) state ppid ...; comm may hold spaces and ')'
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue
        if int(fields[1]) == me:
            found.append(int(name))
    return found


def kill_children(wait_s: float = 120.0) -> List[int]:
    """SIGKILL every process below this one and reap it, again while new
    ones are handed over; returns the pids that had to be killed."""
    killed: List[int] = []
    deadline = time.monotonic() + wait_s
    while True:
        pids = children()
        if not pids or time.monotonic() > deadline:
            return killed
        for pid in pids:
            try:
                if os.waitpid(pid, os.WNOHANG)[0]:
                    continue  # had ended already
                os.kill(pid, signal.SIGKILL)
                killed.append(pid)
                os.waitpid(pid, 0)
            except (ChildProcessError, ProcessLookupError):
                pass  # reaped elsewhere (a Popen.wait) meanwhile
