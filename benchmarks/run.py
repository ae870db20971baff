#!/usr/bin/env python3
"""One cell of the benchmark, once, in a new process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Rebuilds `libtb.so`, formats a data file in a temporary directory, starts the
server as the ONLY process on the chip, sets up (accounts, preload), measures
a closed-loop window from the client's side, compares every answer with the
plain reference, stops the server, and prints one JSON object as the last
line of stdout.  This process never initialises a JAX backend.  It exits
non-zero, before it measures anything, when the server does not report a TPU
with the chips the cell asks for, or reports that the host engine commits.

Nothing here names a cell, a configuration, a mix or a per-layer metric: each
is found by the name `BENCHMARK.json` gives it (see `benchmarks/README.md`).
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import check, drive, procs, trace_cue, trace_reduce
from benchmarks.harness.peaks import peaks_of
from benchmarks.harness.server import Server

# Taken once the imports are done (about 0.1 s after the process started).
T_PROCESS_START = time.monotonic()

READY_S = 1100.0        # a cell's first run in a checkout compiles everything
REQUEST_TIMEOUT_S = 300.0
LOOKUP_MAX = 8190       # ids per lookup request (the 1 MiB message)


class BenchFailure(Exception):
    """The run cannot give a result."""


def require(ok, message: str) -> None:
    if not ok:
        raise BenchFailure(message)


def log(message: str) -> None:
    print(f"bench: {message}", file=sys.stderr, flush=True)


def load_cell(root: str, workload: str) -> dict:
    """The cell, its configuration and its mix, each from the file that
    `BENCHMARK.json` names."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    require(workload in cells,
            f"no workload {workload!r} in BENCHMARK.json "
            f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    mix_path = os.path.join(root, "benchmarks", "traffic",
                            cell["traffic"] + ".json")
    with open(mix_path) as f:
        mix = json.load(f)
    return {"bench": bench, "cell": cell, "config": config, "mix": mix,
            "mix_path": mix_path}


def metrics_for(bench: dict, group: str, workload: str) -> List[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def _lookup(client, operation: str, ids: List[int]) -> np.ndarray:
    parts = [getattr(client, operation)(ids[at:at + LOOKUP_MAX])
             for at in range(0, len(ids), LOOKUP_MAX)]
    return np.concatenate(parts)


def _filesystem_of(path: str) -> str:
    best = ("", "?")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _dev, mount, kind = line.split()[:3]
                if path.startswith(mount) and len(mount) > len(best[0]):
                    best = (mount, kind)
    except OSError:
        pass
    return best[1]


def failed_requests(records: list, allowed: set) -> list:
    """Requests that errored, timed out, or returned a code the mix says
    cannot occur."""
    return [r for r in records if r.error or any(
        c not in allowed for _i, c in r.codes)]


def client_side(window: list, allowed: set):
    """The client's side of the window: how many requests failed, the
    end-to-end numbers, and observations."""
    done = [r for r in window if not r.error]
    failed = failed_requests(window, allowed)
    first_send = min(r.t_send for r in window)
    last_reply = max(r.t_reply for r in window)
    accepted = sum(r.events - len(r.codes) for r in done)
    end_to_end = {"accepted_tx_s": accepted / (last_reply - first_send)}
    seen = {
        "window_seconds": last_reply - first_send,
        "batch_samples": len(done),
        "batch_p50_ms": drive.latency_quantile_ms(window, 0.50),
        "batch_p95_ms": drive.latency_quantile_ms(window, 0.95),
        "batch_max_ms": drive.latency_quantile_ms(window, 1.0),
    }
    if done:  # which request `batch_max_ms` was, and when it was sent
        slowest = max(done, key=lambda r: r.t_reply - r.t_send)
        seen["batch_max_request"] = {
            "session": slowest.session, "index": slowest.index,
            "operation": slowest.operation,
            "sent_at_s": slowest.t_send - first_send}
    return len(failed), end_to_end, seen


def trace_placement(placed: dict, window: list) -> dict:
    """Where the profiler window lay in the measured window: seconds after
    the window's first send at which its two cues were sent, the requests
    answered by then, and when the window's last reply came."""
    first_send = min(r.t_send for r in window)
    return {
        "trace_opened_at_s": placed["opened"] - first_send,
        "trace_closed_at_s": placed["closed"] - first_send,
        "trace_requests_answered": placed["answered"],
        "trace_start_cue_s": placed["started"] - placed["opened"],
        "window_ended_at_s": max(r.t_reply for r in window) - first_send,
    }


def server_side(loaded: dict, snaps: dict, reduced: dict, window: list,
                peaks: Optional[dict]):
    """The server's side of a traced run: every per-layer metric the cell
    lists, each from its reader, and observations from the snapshots."""
    run = {"snapshots": snaps, "trace": reduced, "window": window,
           "mix": loaded["mix"], "config": loaded["config"], "peaks": peaks}
    per_layer = {}
    for metric in metrics_for(loaded["bench"], "per_layer",
                              loaded["cell"]["name"]):
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{metric['name']}")
        value = reader.read(run)
        if value is not None:
            per_layer[metric["name"]] = value
    before, after = snaps["open"], snaps["close"]
    delta = {k: v - before["counters"].get(k, 0)
             for k, v in after["counters"].items()}
    seen = {
        "window_routes": {
            "fast": delta.get("ops.route.fast", 0),
            "grouped": delta.get("ops.route.grouped", 0),
            "general": delta.get("ops.route.general", 0),
            "sequential": delta.get("ops.sequential_batches", 0)},
        "setup_compiles": before["counters"].get("jit.compiles", 0),
        "setup_compile_s": before["histograms"].get(
            "jit.compile_ms", {}).get("sum", 0) / 1e3,
        "start_warmup_s": before["gauges"].get("start.warmup_s"),
    }
    return per_layer, seen


def run_cell(loaded: dict, seed: int, seconds: float, trace: bool,
             platform: str, env: dict, workdir: str, root: str = ROOT,
             artifacts: Optional[str] = None,
             server_main: Optional[str] = None) -> dict:
    """Server up, set-up, window, lookups, server down, comparison.
    `platform` is what the server must report (`main` passes "tpu"; only the
    tests of `benchmarks/tests/` pass another)."""
    cell, config, mix = loaded["cell"], loaded["config"], loaded["mix"]
    out: dict = {"observations": {}}
    obs = out["observations"]
    reference = check.ReferenceProcess(root, loaded["mix_path"], seed, env)
    try:
        server = Server(workdir, config["server_args"], env, metrics=trace,
                        server_main=server_main)
    except BaseException:
        reference.close()
        raise
    clients: list = []
    try:
        plan = check.load_generator(mix).build(mix, seed)  # beside the warm-up
        server.wait_ready(READY_S)
        device = server.device
        out["device"] = device
        obs["ready_s"] = time.monotonic() - T_PROCESS_START
        require(device["platform"] == platform,
                f"server runs on {device['platform']!r}, need {platform!r}")
        require(device["executor"] == "device",
                f"the host engine commits, not the device: {device}")
        require(device["count"] >= cell["chips"],
                f"cell needs {cell['chips']} chips, server sees {device}")
        peaks = peaks_of(device["device_kind"]) if platform == "tpu" else None

        clients = drive.connect(server.port, mix["sessions"], seed,
                                REQUEST_TIMEOUT_S)
        allowed = set(mix.get("allowed_codes", [0]))
        setup_sent: Dict[str, list] = {}
        for phase in plan["setup"]:
            t0 = time.monotonic()
            sent = drive.run_queues(clients, phase["queues"])
            setup_sent[phase["name"]] = sent
            obs[f"setup_{phase['name']}_s"] = time.monotonic() - t0
            bad = failed_requests(sent, allowed)
            require(not bad and len(sent) == sum(map(len, phase["queues"])),
                    f"set-up phase {phase['name']}: "
                    f"{bad[0].error if bad else 'requests missing'}")
        obs["reference_setup_s"] = reference.wait_setup()

        # -- the window ----------------------------------------------------
        snaps: Dict[str, dict] = {}
        trace_dir = os.path.join(workdir, "trace")
        progress: Optional[drive.Progress] = None   # the untraced run: none
        if trace:
            # One profiler window inside the measured window, cued by the
            # window's own progress (`harness/trace_cue.py`).
            snaps["open"] = server.cue("snapshot")
            progress = drive.Progress()
            cue = trace_cue.TraceCue.for_window(
                sum(len(q) for q in plan["window"]), len(plan["window"]),
                seconds)
            placed: dict = {}

            def traced_span() -> None:
                try:
                    placed.update(trace_cue.place(
                        server, progress, cue, trace_dir))
                except Exception as err:  # re-raised on the main thread
                    placed["error"] = err

            tracer = threading.Thread(target=traced_span, daemon=True)
            tracer.start()
        out["setup_s"] = time.monotonic() - T_PROCESS_START
        window = drive.run_queues(clients, plan["window"], seconds=seconds,
                                  progress=progress)
        if trace:
            tracer.join()
            if "error" in placed:
                raise placed["error"]
            snaps["close"] = server.cue("snapshot")
        require(window, "the window sent no request")

        # -- after the window: read back, stop, compare ----------------------
        counts = [sum(1 for r in window if r.session == s and not r.error)
                  for s in range(mix["sessions"])]
        ids = check.sample_transfer_ids(plan, counts, seed, mix)
        t0 = time.monotonic()
        got_accounts = _lookup(clients[0], "lookup_accounts",
                               plan["account_ids"])
        got_transfers = _lookup(clients[0], "lookup_transfers", ids)
        obs["lookups_s"] = time.monotonic() - t0
        memory = server.cue("memory")
        for c in clients:
            c.close()
        clients = []
        try:
            rc = server.stop()
        except RuntimeError as err:  # counted below: the run is not correct
            log(str(err))
            rc = server.proc.returncode
        expected_path = os.path.join(workdir, "expected.npz")
        obs["reference_window_s"] = reference.finish(counts, expected_path)
        numbers = check.compare(check.load_expected(expected_path),
                                setup_sent, window, got_accounts,
                                got_transfers)
        numbers["server_unclean_stop"] = (
            0 if server.stopped_cleanly() else 1, 0)
        obs["server_exit_code"] = rc
        obs["server_sigterms"] = server.sigterms

        failed, out["end_to_end"], seen = client_side(window, allowed)
        out["end_to_end"]["setup_s"] = out["setup_s"]
        numbers["requests_failed"] = (failed, 0)
        out["attempted"], out["failed"] = len(window), failed
        obs.update(seen)
        obs.update({
            "window_requests_per_session": counts,
            "window_hit_its_cap": any(
                n == len(q) for n, q in zip(counts, plan["window"])),
            "workdir_filesystem": _filesystem_of(workdir),
        })
        peak = [d["stats"].get("peak_bytes_in_use")
                for d in memory["devices"]]
        out["memory_peak_bytes"] = max(
            (p for p in peak if p is not None), default=None)
        out["numbers"] = numbers
        out["correct"] = check.verdict(numbers)
        if trace:
            where = trace_placement(placed, window)
            obs.update(where)
            xplane = trace_reduce.find_xplane(trace_dir)
            try:
                out["trace"] = trace_reduce.reduce(
                    trace_reduce.read_events(xplane))
            except ValueError as err:
                raise BenchFailure(f"{err}; the profiler's place in the "
                                   f"window: {json.dumps(where)}") from err
            out["per_layer"], seen = server_side(
                loaded, snaps, out["trace"], window, peaks)
            obs.update(seen)
            if artifacts:
                os.makedirs(artifacts, exist_ok=True)
                with open(os.path.join(artifacts, "run.json"), "w") as f:
                    json.dump({"snapshots": snaps, "trace": out["trace"]}, f)
                shutil.copy(xplane, artifacts)
        return out
    finally:
        # After a result both have ended already.  On any other way out
        # nothing is owed to them: kill, wait, and let no step skip the next.
        for end in [server.kill, reference.close] + [c.close for c in clients]:
            try:
                end()
            except Exception as err:  # keep the first failure on top
                log(f"{end.__qualname__}: {err}")


def rebuild_native() -> None:
    """Remove any libtb.so the copy brought along (built elsewhere, for
    another CPU) and build it here from the committed sources; a silent drop
    to the pure-Python checksum is a failure on this path.  (A copy of
    `chip_smoke.py`'s.)"""
    native_dir = os.path.join(ROOT, "tigerbeetle_tpu", "native")
    for stale in glob.glob(os.path.join(native_dir, "libtb.so*")):
        os.remove(stale)
    from tigerbeetle_tpu import native

    t0 = time.monotonic()
    lib = native.load()
    require(lib is not None, "libtb.so did not build from committed sources")
    log(f"libtb.so rebuilt in {time.monotonic() - t0:.1f}s")


def result_line(loaded: dict, out: dict, trace: bool) -> dict:
    """The contract's last line."""
    bench, name = loaded["bench"], loaded["cell"]["name"]
    group = "per_layer" if trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in metrics_for(bench, group, name)}
    values = out[group]
    device = out["device"]
    line = {
        "correct": bool(out["correct"]),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {k: {"value": values[k], "unit": units[k]}
                    for k in units if values.get(k) is not None},
        "device": {"platform": device["platform"],
                   "kind": device["device_kind"], "count": device["count"],
                   "memory_peak_bytes": out["memory_peak_bytes"]},
    }
    if trace:
        line["device"]["busy_s"] = out["trace"]["busy_s"]
        line["device"]["window_s"] = out["trace"]["window_s"]
        line["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                             "idle_gaps": out["trace"]["idle_gaps"]}
    # Last in the line: every number `correct` compared, beside its limit.
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in out["numbers"].items()}
    return line


def print_report(out: dict, trace: bool) -> None:
    """The observations on stdout; every number compared beside its limit as
    the last lines of stderr."""
    if trace:
        print("end_to_end_while_traced: " + json.dumps(out["end_to_end"]))
        print("programs: " + json.dumps(
            sorted(out["trace"]["programs"].items(),
                   key=lambda kv: -kv[1][0])[:12]))
    print("observations: " + json.dumps(out["observations"]), flush=True)
    for name, (value, limit) in out["numbers"].items():
        print(f"compared {name}: {value}"
              + ("" if limit is None else f" (limit {limit})"),
              file=sys.stderr, flush=True)


def _on_signal(signum, _frame) -> None:
    """A signal that would end this process ends the run instead, through
    every `finally` on the way (the children are killed there)."""
    for s in ENDING_SIGNALS:
        signal.signal(s, signal.SIG_IGN)
    raise BenchFailure(f"ended by signal {signum}")


ENDING_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--artifacts", default=None,
                   help="directory for a traced run's snapshots, reduced "
                        "trace and .xplane.pb (off by default)")
    args = p.parse_args(argv)

    procs.adopt_orphans()
    for s in ENDING_SIGNALS:
        signal.signal(s, _on_signal)
    # What an operator runs: no TB_* switch reaches the server.
    env = {k: v for k, v in os.environ.items() if not k.startswith("TB_")}
    workdir = tempfile.mkdtemp(prefix="tb_bench_")
    try:
        loaded = load_cell(ROOT, args.workload)
        rebuild_native()
        out = run_cell(loaded, args.seed, args.seconds, bool(args.trace),
                       "tpu", env, workdir, artifacts=args.artifacts)
        require(out["memory_peak_bytes"] is not None,
                "the device reports no peak_bytes_in_use")
        from tigerbeetle_tpu import jaxenv

        require(jaxenv.current_platform() is None,
                "the parent initialised a JAX backend")
        line = result_line(loaded, out, bool(args.trace))
    except Exception:  # the boundary: no result line, a non-zero exit
        traceback.print_exc()
        return 1
    finally:
        left = procs.kill_children()
        if left:
            log(f"killed on the way out: pids {left}")
        shutil.rmtree(workdir, ignore_errors=True)
    print_report(out, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
