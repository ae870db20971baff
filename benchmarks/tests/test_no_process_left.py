"""No process the benchmark starts outlives it (`harness/procs.py`): a
server left on the chip would serve, or block, every later run."""

import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _descendants(pid: int) -> list:
    """Every live process below `pid`, as (pid, command line)."""
    parent_of, cmdline = {}, {}
    for name in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            with open(f"/proc/{name}/cmdline") as f:
                cmdline[int(name)] = f.read().replace("\0", " ")
        except OSError:
            continue
        if fields[0] != "Z":
            parent_of[int(name)] = int(fields[1])
    found, frontier = [], [pid]
    while frontier:
        below = [p for p, parent in parent_of.items() if parent in frontier]
        found += below
        frontier = below
    return [(p, cmdline[p]) for p in found]


def _alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def _sockets(pid: int) -> int:
    count = 0
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                count += os.readlink(
                    f"/proc/{pid}/fd/{fd}").startswith("socket:")
            except OSError:
                pass
    except OSError:
        pass
    return count


def _wait_gone(pids, seconds: float) -> list:
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline and any(map(_alive, pids)):
        time.sleep(0.1)
    return [p for p in pids if _alive(p)]


def test_sigkill_of_the_run_takes_server_and_reference_along(tiny_copy):
    """The driver's time limit ends a run with SIGKILL: no `finally` runs,
    and the server child and the reference child have to go all the same."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    run = subprocess.Popen(
        [sys.executable,
         os.path.join(tiny_copy, "benchmarks/tests/cpu_cell.py"),
         tiny_copy, "tiny-plain", "5", "60", "0"],
        cwd=tiny_copy, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    below = []
    try:
        # Wait until the server serves the 4 sessions: it has printed its
        # last line by then, and nothing but a signal would end it.
        deadline = time.monotonic() + 120
        below, serving = [], False
        while time.monotonic() < deadline and run.poll() is None:
            below = _descendants(run.pid)
            serving = any("server_main.py" in c and _sockets(p) >= 5
                          for p, c in below)
            if serving:
                break
            time.sleep(0.2)
        assert serving, below
        run.kill()
        run.wait()
        left = _wait_gone([p for p, _c in below], 20)
        assert not left, [c for p, c in below if p in left]
    finally:
        if run.poll() is None:
            run.kill()
            run.wait()
        for pid, _c in below:
            if _alive(pid):
                os.kill(pid, signal.SIGKILL)


SWEEP = """
import subprocess, sys, time
sys.path.insert(0, {root!r})
from benchmarks.harness import procs
procs.adopt_orphans()
# The shell starts a sleeper and exits: the sleeper is handed to this process.
pid = int(subprocess.run(["sh", "-c", "sleep 300 >/dev/null 2>&1 & echo $!"],
                         capture_output=True, text=True).stdout)
time.sleep(0.3)
print(pid, procs.kill_children())
"""


def test_the_sweep_on_the_way_out_ends_an_orphaned_grandchild():
    done = subprocess.run(
        [sys.executable, "-c", SWEEP.format(root=ROOT)],
        capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    pid, killed = done.stdout.split(maxsplit=1)
    assert killed.strip() == f"[{pid}]"
    assert not _alive(int(pid))


MAIN = """
import sys
sys.path.insert(0, {copy!r})
from benchmarks import run
run.rebuild_native = lambda: None  # the repo's libtb.so stays as it is
sys.exit(run.main(["--workload", "tiny-plain", "--seed", "5",
                   "--seconds", "60", "--trace", "0"]))
"""


def test_sigterm_ends_the_run_through_its_finally_blocks(tiny_copy):
    """`run.py` itself, signalled while the server is starting: no result,
    a non-zero exit, and nothing left below it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.Popen(
        [sys.executable, "-c", MAIN.format(copy=tiny_copy)], cwd=tiny_copy,
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    below = []
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and run.poll() is None:
            below = _descendants(run.pid)
            if any("server_main.py" in c for _p, c in below):
                break
            time.sleep(0.05)
        assert any("server_main.py" in c for _p, c in below), below
        run.send_signal(signal.SIGTERM)
        out, err = run.communicate(timeout=60)
        assert run.returncode == 1 and not out.strip(), err[-2000:]
        assert "ended by signal 15" in err, err[-2000:]
        assert not _wait_gone([p for p, _c in below], 5)
    finally:
        if run.poll() is None:
            run.kill()
            run.wait()
        for pid, _c in below:
            if _alive(pid):
                os.kill(pid, signal.SIGKILL)
