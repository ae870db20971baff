"""The server child: the one process that touches the chip.

A copy of `chip_smoke.py`'s `Server` (as of d0bcfcd), changed in two ways:
the child is started through `server_main.py`, which runs the program's
normal entry point in-process beside one helper thread, and the parent can
send that thread cues (registry snapshot, profiler start/stop, memory stats).
The child dies with the parent (`procs.py`), and `kill` ends it at once.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from typing import List, Optional, Tuple

from benchmarks.harness import procs

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SERVER_MAIN = os.path.join(HERE, "server_main.py")
# On the chip the program's asyncio shutdown does not end after one SIGTERM
# (PERF.md, Open questions): the second one, sent after this wait, ends it.
STOP_FIRST_WAIT_S = 8.0


class Server:
    """`format` + `start` as a child; stderr's `device` line and stdout's
    `listening` line are read with a deadline."""

    def __init__(self, workdir: str, start_args: List[str], env: dict,
                 metrics: bool, server_main: Optional[str] = None):
        self.workdir = workdir
        self.device: Optional[dict] = None
        self.port: Optional[int] = None
        self._lines: "queue.Queue[Tuple[str, Optional[str]]]" = queue.Queue()
        self.sigterms = 0
        self._cues = 0
        path = os.path.join(workdir, "bench.tb")
        subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu", "format", "--cluster",
             "0", path],
            check=True, cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
        )
        cmd = [sys.executable, server_main or SERVER_MAIN, "start", path,
               "--addresses", "127.0.0.1:0"] + list(start_args)
        if metrics:
            cmd += ["--metrics-json", os.path.join(workdir, "exit.json")]
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=procs.child_env(env), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for name, pipe in (("out", self.proc.stdout),
                           ("err", self.proc.stderr)):
            threading.Thread(target=self._pump, args=(name, pipe),
                             daemon=True).start()

    def _pump(self, name: str, pipe) -> None:
        for line in pipe:
            if name == "err":  # the child's stderr stays visible
                sys.stderr.write("server: " + line)
            self._lines.put((name, line.rstrip("\n")))
        self._lines.put((name, None))

    def wait_ready(self, deadline_s: float) -> None:
        """Block until both the device line and the listening line arrived
        (the gap between them is the warm-up)."""
        deadline = time.monotonic() + deadline_s
        while self.device is None or self.port is None:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"server not ready within {deadline_s:.0f}s "
                    f"(device={self.device}, port={self.port})")
            try:
                name, line = self._lines.get(timeout=min(left, 1.0))
            except queue.Empty:
                if self.proc.poll() is not None:
                    raise RuntimeError(
                        f"server exited rc={self.proc.returncode} before "
                        "it was ready")
                continue
            if line is None:
                continue
            if name == "err" and line.startswith("device "):
                self.device = json.loads(line[len("device "):])
            elif name == "out" and line.startswith("listening "):
                self.port = int(line.rsplit(":", 1)[1])

    def cue(self, cmd: str, timeout_s: float = 120.0, **args) -> dict:
        """Send one cue to the helper thread and wait for its answer file."""
        self._cues += 1
        done = os.path.join(self.workdir, f"cue{self._cues}.json")
        self.proc.stdin.write(json.dumps(dict(args, cmd=cmd, done=done)) + "\n")
        self.proc.stdin.flush()
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(done):
            if self.proc.poll() is not None:
                raise RuntimeError(f"server exited during cue {cmd!r}")
            if time.monotonic() > deadline:
                raise TimeoutError(f"cue {cmd!r} not answered")
            time.sleep(0.02)
        with open(done) as f:
            answer = json.load(f)
        if "error" in answer:
            raise RuntimeError(f"cue {cmd!r}: {answer['error']}")
        return answer

    def stop(self) -> int:
        """SIGTERM and a bounded wait; a second SIGTERM where the first did
        not end the process; then every thread's stack (SIGUSR1) and a kill.
        Returns the exit code; the server's SIGTERM handler exits 143 after
        its atexit dumps.  `self.sigterms` says how many it took."""
        for wait_s in (STOP_FIRST_WAIT_S, 60.0):
            if self.proc.poll() is not None:
                break
            self.sigterms += 1
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(wait_s)
            except subprocess.TimeoutExpired:
                self.proc.send_signal(signal.SIGUSR2)  # its pending tasks
                time.sleep(0.5)
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGUSR1)
            time.sleep(2.0)
            self.kill()
            raise RuntimeError("server ignored two SIGTERMs; killed")
        return self.proc.returncode

    def kill(self) -> None:
        """SIGKILL and wait until it has ended: the way out of a run that
        gives no result, where nothing is owed to the server's shutdown."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(120)

    def stopped_cleanly(self) -> bool:
        return self.sigterms > 0 and self.proc.returncode in (
            0, 143, -signal.SIGTERM)
