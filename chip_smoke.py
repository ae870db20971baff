#!/usr/bin/env python3
"""First-light smoke: the served device commit path on the chip, end to end.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the sharded serving path, four chips

Builds ``libtb.so`` from the committed sources, formats a data file, starts
``python -m tigerbeetle_tpu start --no-engine`` as the ONE process that
touches the chip, drives it over TCP through ``tigerbeetle_tpu.client.Client``
with seeded data (accounts, grouped plain transfers, two-phase, failures, a
linked chain, lookups), replays the same operations through the independent
``testing/model.ReferenceStateMachine`` in this process, and requires equal
result codes for every batch and equal rows for every lookup.  The last
stdout line is ``{"ok": ..., "device": {...}}`` with the device the *server*
reported; exit code 0 only when every phase passed on a TPU.

The parent never initializes a JAX backend (docs: README "Running").
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def require(ok, message: str) -> None:
    """The script's checks must hold under ``python -O`` too."""
    if not ok:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Sizes:
    batch: int               # events per request
    accounts: int            # total accounts created
    limit_accounts: int      # of which debits_must_not_exceed_credits
    transfers: int           # plain transfers in the concurrent phase
    sessions: int            # concurrent client sessions
    special: int             # lanes per two-phase / failure batch
    lookups: int             # ids per lookup request
    accounts_log2: int       # --cache-accounts-log2
    transfers_log2: int      # --cache-transfers-log2
    ready_s: float           # deadline for device + listening lines
    timeout_s: float         # per-request client timeout


# The sizing the repo's own `benchmark` subcommand gives its replica
# (cli._spawn_temp_replica): accounts 2^21, transfers 2^23.
# 8190 events per request is batch_max of the 1 MiB message.
FULL = Sizes(
    batch=8190, accounts=1_000_000, limit_accounts=2 * 8190,
    transfers=123 * 8190, sessions=8, special=4096, lookups=8190,
    accounts_log2=21, transfers_log2=23, ready_s=900.0, timeout_s=300.0,
)


@dataclasses.dataclass
class Phase:
    name: str
    operation: str               # create_accounts | create_transfers
    batches: List[np.ndarray]
    concurrent: bool = False     # spread over sessions (order-free phases)


@dataclasses.dataclass
class Plan:
    phases: List[Phase]
    account_lookups: List[List[int]]
    transfer_lookups: List[List[int]]


def _chunks(rows: np.ndarray, batch: int) -> List[np.ndarray]:
    return [rows[i:i + batch] for i in range(0, len(rows), batch)]


def _accounts(ids: np.ndarray, rng, flags: int = 0) -> np.ndarray:
    from tigerbeetle_tpu import types

    rows = np.zeros(len(ids), dtype=types.ACCOUNT_DTYPE)
    rows["id_lo"] = ids
    rows["user_data_64"] = rng.integers(0, 1 << 62, len(ids), dtype=np.uint64)
    rows["user_data_32"] = rng.integers(0, 1 << 31, len(ids), dtype=np.uint32)
    rows["ledger"] = 1
    rows["code"] = 10
    rows["flags"] = flags
    return rows


def _transfers(ids, debit, credit, amount, flags=0, pending_id=0):
    from tigerbeetle_tpu import types

    rows = np.zeros(len(ids), dtype=types.TRANSFER_DTYPE)
    rows["id_lo"] = ids
    rows["debit_account_id_lo"] = debit
    rows["credit_account_id_lo"] = credit
    rows["amount_lo"] = amount
    rows["pending_id_lo"] = pending_id
    rows["ledger"] = 1
    rows["code"] = 7
    rows["flags"] = flags
    return rows


def _pairs(rng, ids: np.ndarray, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """n (debit, credit) pairs drawn from ``ids``, never equal."""
    d = rng.integers(0, len(ids), n)
    c = (d + rng.integers(1, len(ids), n)) % len(ids)
    return ids[d], ids[c]


def build_plan(sizes: Sizes, seed: int) -> Plan:
    """Every operation of the run, made from ``seed``.

    Order matters in one place: ``_fast_path_ok`` (machine.py) turns the fast
    kernel off for the process once ANY account carries a limit flag, so the
    limit-flagged share of the accounts is created AFTER the plain transfers
    — before it the grouped fast route is taken, after it the general one."""
    from tigerbeetle_tpu.types import AccountFlags, TransferFlags

    rng = np.random.default_rng(seed)
    n_plain = sizes.accounts - sizes.limit_accounts
    plain = np.arange(1, n_plain + 1, dtype=np.uint64)
    limit = np.arange(n_plain + 1, sizes.accounts + 1, dtype=np.uint64)
    missing = np.uint64(sizes.accounts + 1_000_003)
    n_sp = sizes.special
    next_id = [1 << 32]

    def ids(n: int) -> np.ndarray:
        out = np.arange(next_id[0], next_id[0] + n, dtype=np.uint64)
        next_id[0] += n
        return out

    phases = [Phase("accounts", "create_accounts",
                    _chunks(_accounts(plain, rng), sizes.batch),
                    concurrent=True)]

    # Plain transfers, all valid, unique ids, no limit account anywhere yet:
    # every batch is fast-path eligible and sessions commute.
    d, c = _pairs(rng, plain, sizes.transfers)
    plain_tids = ids(sizes.transfers)
    plain_amounts = rng.integers(1, 1000, sizes.transfers, dtype=np.uint64)
    phases.append(Phase(
        "transfers", "create_transfers",
        _chunks(_transfers(plain_tids, d, c, plain_amounts), sizes.batch),
        concurrent=True,
    ))

    phases.append(Phase(
        "limit_accounts", "create_accounts",
        _chunks(_accounts(
            limit, rng, int(AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS)
        ), sizes.batch),
    ))

    # From here on one session, strict order: the general kernel.
    funded = limit[:n_sp]
    fund = _transfers(
        ids(n_sp), plain[rng.integers(0, n_plain, n_sp)], funded,
        np.full(n_sp, 1000, np.uint64),
    )
    gd, gc = _pairs(rng, plain, n_sp)
    general = _transfers(ids(n_sp), gd, gc,
                         rng.integers(1, 1000, n_sp, dtype=np.uint64))
    # Pendings: even lanes plain -> plain, odd lanes debit a funded limit
    # account (each once, 400 of its 1000).
    pd, pc = _pairs(rng, plain, n_sp)
    pd[1::2] = funded[1::2]
    pend_ids = ids(n_sp)
    pend_amounts = rng.integers(100, 400, n_sp, dtype=np.uint64)
    pendings = _transfers(pend_ids, pd, pc, pend_amounts,
                          flags=int(TransferFlags.PENDING))
    # A LATER batch resolves them from the table: lane%3 == 0 posts the
    # full amount, == 1 voids, == 2 stays pending; every 6th post is
    # partial (amount - 50).
    lane = np.arange(n_sp)
    post, void = lane % 3 == 0, lane % 3 == 1
    pv = post | void
    pv_amount = np.where(post, pend_amounts, 0).astype(np.uint64)
    pv_amount[(lane % 6 == 0)] -= 50
    pv_flags = np.where(post, int(TransferFlags.POST_PENDING_TRANSFER),
                        int(TransferFlags.VOID_PENDING_TRANSFER))
    postvoid = _transfers(
        ids(int(pv.sum())), 0, 0, pv_amount[pv], flags=pv_flags[pv],
        pending_id=pend_ids[pv],
    )
    postvoid["ledger"] = 0
    postvoid["code"] = 0

    # Deliberate failures, interleaved with successes.
    fd, fc = _pairs(rng, plain, n_sp)
    famount = rng.integers(1, 1000, n_sp, dtype=np.uint64)
    fids = ids(n_sp)
    k = lane % 8
    fd[k == 1] = missing                          # debit_account_not_found
    fc[k == 2] = missing + np.uint64(1)           # credit_account_not_found
    over = k == 3                                  # exceeds_credits
    fd[over] = limit[rng.integers(0, len(limit), int(over.sum()))]
    famount[over] = 10**9
    dup = k == 4                                   # an id of the plain phase
    fids[dup] = plain_tids[: int(dup.sum())]
    same = k == 5                                  # accounts_must_be_different
    fc[same] = fd[same]
    famount[k == 6] = 0                            # zero amount
    failures = _transfers(fids, fd, fc, famount)
    # ... and the exact resend of lane 0, and its id again with another
    # amount, inside the same batch (order-dependent: exists / exists_with_*).
    failures[-2] = failures[0]
    failures[-1] = failures[0]
    failures["amount_lo"][-1] += 1

    # One small linked batch: a chain that commits, a chain whose middle
    # fails (all three roll back), a single, and a chain of a pending with
    # its own post — a linked chain that refers into its own batch is what
    # the general kernel hands to the sequential route (ops/scan_path.py).
    ld, lc = _pairs(rng, plain, 9)
    ld[4] = missing
    linked = _transfers(ids(9), ld, lc, np.full(9, 5, np.uint64))
    linked["flags"][[0, 1, 3, 4]] = int(TransferFlags.LINKED)
    linked["flags"][7] = int(TransferFlags.LINKED | TransferFlags.PENDING)
    linked["flags"][8] = int(TransferFlags.POST_PENDING_TRANSFER)
    linked["pending_id_lo"][8] = linked["id_lo"][7]
    linked["debit_account_id_lo"][8] = linked["credit_account_id_lo"][8] = 0

    # The plain -> plain pendings and the limit-account ones go as two
    # batches: limit accounts make a batch order-dependent, and the first
    # should stay on the general kernel.
    for name, batches in (
        ("fund", [fund]), ("general", [general]),
        ("pendings", [pendings[0::2], pendings[1::2]]),
        ("postvoid", [postvoid]), ("failures", [failures]),
        ("linked", [linked]),
    ):
        phases.append(Phase(name, "create_transfers", batches))

    # Lookups: touched, limit, never-created ids; committed, pending,
    # resolved, failed and unknown transfer ids.
    n_lk = sizes.lookups
    account_lookups = [
        np.concatenate([
            plain[rng.integers(0, n_plain, n_lk - 2 * (n_lk // 4))],
            limit[rng.integers(0, len(limit), n_lk // 4)],
            missing + rng.integers(0, 1000, n_lk // 4).astype(np.uint64),
        ]).tolist(),
        np.concatenate([funded, pd, pc])[:n_lk].tolist(),
    ]
    transfer_lookups = [
        np.concatenate([
            plain_tids[rng.integers(0, sizes.transfers, n_lk // 2)],
            fids[: n_lk // 4],
            (np.uint64(1 << 40) + np.arange(n_lk // 4, dtype=np.uint64)),
        ]).tolist(),
        np.concatenate([pend_ids, postvoid["id_lo"], linked["id_lo"]])[
            :n_lk].tolist(),
    ]
    return Plan(phases, account_lookups, transfer_lookups)


# --------------------------------------------------------------------------
# The server child: the one process that touches the chip.
# --------------------------------------------------------------------------


class Server:
    """``format`` + ``start --no-engine`` as a child; stderr's ``device``
    line and stdout's ``listening`` line are read with a deadline."""

    def __init__(self, workdir: str, sizes: Sizes, shards: int, env: dict):
        self.metrics_path = os.path.join(workdir, "metrics.json")
        self.device: Optional[dict] = None
        self.port: Optional[int] = None
        self._lines: "queue.Queue[Tuple[str, Optional[str]]]" = queue.Queue()
        self._term_sent = False
        path = os.path.join(workdir, "smoke.tb")
        py = [sys.executable, "-m", "tigerbeetle_tpu"]
        subprocess.run(py + ["format", "--cluster", "0", path], check=True,
                       cwd=ROOT, env=env, stdout=subprocess.DEVNULL)
        cmd = py + [
            "start", path, "--addresses", "127.0.0.1:0", "--no-engine",
            "--cache-accounts-log2", str(sizes.accounts_log2),
            "--cache-transfers-log2", str(sizes.transfers_log2),
            "--metrics-json", self.metrics_path,
        ]
        if shards:
            cmd += ["--shards", str(shards)]
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
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
        (the gap between them is the warm-up: every kernel's cold compile)."""
        deadline = time.monotonic() + deadline_s
        while self.device is None or self.port is None:
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"server not ready within {deadline_s:.0f}s "
                    f"(device={self.device}, port={self.port})"
                )
            try:
                name, line = self._lines.get(timeout=min(left, 1.0))
            except queue.Empty:
                if self.proc.poll() is not None:
                    raise RuntimeError(
                        f"server exited rc={self.proc.returncode} before "
                        "it was ready"
                    )
                continue
            if line is None:
                continue
            if name == "err" and line.startswith("device "):
                self.device = json.loads(line[len("device "):])
            elif name == "out" and line.startswith("listening "):
                self.port = int(line.rsplit(":", 1)[1])

    def stop(self) -> int:
        """SIGTERM, bounded wait, then kill.  Returns the exit code; the
        server's SIGTERM handler exits 143 after its atexit dumps."""
        if self.proc.poll() is None:
            self._term_sent = True
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(30)
                raise RuntimeError("server ignored SIGTERM for 60s; killed")
        return self.proc.returncode

    def stopped_cleanly(self) -> bool:
        return self._term_sent and self.proc.returncode in (
            0, 143, -signal.SIGTERM
        )


# --------------------------------------------------------------------------
# Drive, replay, compare.
# --------------------------------------------------------------------------


def drive(port: int, plan: Plan, sizes: Sizes) -> dict:
    """Send every operation of the plan through the Python client; returns
    the result codes of every batch, the looked-up rows, and the wall
    seconds of each phase (observations, not metrics)."""
    from tigerbeetle_tpu.client import Client

    def client() -> Client:
        return Client([("127.0.0.1", port)], cluster=0,
                      timeout_s=sizes.timeout_s)

    sessions = [client() for _ in range(sizes.sessions)]
    results: Dict[str, list] = {}
    seconds: Dict[str, float] = {}
    try:
        for phase in plan.phases:
            t0 = time.monotonic()
            out: list = [None] * len(phase.batches)
            lanes = sessions if phase.concurrent else sessions[:1]
            errors: list = []

            def run(lane: int) -> None:
                try:
                    call = getattr(lanes[lane], phase.operation)
                    for j in range(lane, len(phase.batches), len(lanes)):
                        out[j] = call(phase.batches[j])
                except Exception as err:  # re-raised on the main thread
                    errors.append(err)

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(len(lanes))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
            results[phase.name] = out
            seconds[phase.name] = time.monotonic() - t0
            print(f"chip_smoke: phase {phase.name}: {len(out)} batches in "
                  f"{seconds[phase.name]:.1f}s", file=sys.stderr, flush=True)
        t0 = time.monotonic()
        accounts = [sessions[0].lookup_accounts(ids)
                    for ids in plan.account_lookups]
        transfers = [sessions[0].lookup_transfers(ids)
                     for ids in plan.transfer_lookups]
        seconds["lookups"] = time.monotonic() - t0
    finally:
        for s in sessions:
            s.close()
    return {"results": results, "accounts": accounts,
            "transfers": transfers, "seconds": seconds}


def replay(plan: Plan) -> dict:
    """The same operations through the reference model (no backend)."""
    from tigerbeetle_tpu.testing import model

    ref = model.ReferenceStateMachine()
    results: Dict[str, list] = {}
    for phase in plan.phases:
        convert = (model.accounts_from_batch
                   if phase.operation == "create_accounts"
                   else model.transfers_from_batch)
        out = []
        for batch in phase.batches:
            ts = ref.prepare(phase.operation, len(batch))
            out.append(ref.execute(phase.operation, ts, convert(batch)))
        results[phase.name] = out
    return {
        "results": results,
        "accounts": [ref.lookup_accounts(ids) for ids in plan.account_lookups],
        "transfers": [ref.lookup_transfers(ids)
                      for ids in plan.transfer_lookups],
    }


def compare(got: dict, want: dict) -> dict:
    """Equal result codes for every batch, equal rows for every lookup.
    Every field of a row is compared but ``timestamp``, which the server
    draws from its own clock: that one must be non-zero and unique.  Raises
    SmokeFailure on the first difference; returns what was compared."""
    from tigerbeetle_tpu.testing import model

    batches = nonzero = 0
    for name, want_batches in want["results"].items():
        got_batches = got["results"][name]
        require(len(got_batches) == len(want_batches), f"{name}: batches")
        for j, (g, w) in enumerate(zip(got_batches, want_batches)):
            g = [(int(i), int(c)) for i, c in g]
            w = [(int(i), int(c)) for i, c in w]
            if g != w:  # (message built only on failure)
                first = next((p for p in zip(g, w) if p[0] != p[1]),
                             (g[:1], w[:1]))
                raise SmokeFailure(
                    f"{name}[{j}]: result codes differ from the model: "
                    f"{len(g)} vs {len(w)} entries, first {first}"
                )
            batches += 1
            nonzero += len(w)
    rows = 0
    for kind, convert in (("accounts", model.accounts_from_batch),
                          ("transfers", model.transfers_from_batch)):
        for j, (g_rows, w_objs) in enumerate(zip(got[kind], want[kind])):
            g_objs = convert(g_rows)
            require(len(g_objs) == len(w_objs),
                    f"{kind} lookup {j}: {len(g_objs)} rows, model "
                    f"{len(w_objs)}")
            stamps = [o.timestamp for o in g_objs]
            require(all(stamps), f"{kind} lookup {j}: zero timestamp")
            by_id = {o.id: o.timestamp for o in g_objs}
            require(len(set(by_id.values())) == len(by_id),
                    f"{kind} lookup {j}: timestamps not unique")
            for g, w in zip(g_objs, w_objs):
                g = dataclasses.replace(g, timestamp=0)
                w = dataclasses.replace(w, timestamp=0)
                if g != w:
                    raise SmokeFailure(
                        f"{kind} lookup {j}: server {g} != model {w}"
                    )
                rows += 1
    return {"batches": batches, "nonzero_codes": nonzero, "rows": rows}


def run(sizes: Sizes, seed: int, shards: int, env: dict, platform: str,
        workdir: str, report: dict) -> None:
    """One full pass: server up, drive + replay, compare, server down,
    checks on the server's own exit report.  ``platform`` is what the child
    must report.  Fills ``report`` as it goes, so a failure still shows how
    far the run got."""
    plan = build_plan(sizes, seed)
    model_out: dict = {}

    def model_thread() -> None:
        t0 = time.monotonic()
        try:
            model_out["want"] = replay(plan)
        except Exception as err:  # re-raised on the main thread
            model_out["error"] = err
        model_out["seconds"] = time.monotonic() - t0

    server = Server(workdir, sizes, shards, env)
    try:
        t0 = time.monotonic()
        replayer = threading.Thread(target=model_thread)
        replayer.start()  # overlaps the server's warm-up and work
        try:
            server.wait_ready(sizes.ready_s)
            report["device"] = server.device
            report["ready_s"] = time.monotonic() - t0
            require(server.device["platform"] == platform,
                    f"server runs on {server.device['platform']!r}, "
                    f"need {platform!r}")
            require(server.device["executor"] == "device",
                    str(server.device))
            require(server.device["count"] >= max(shards, 1),
                    str(server.device))
            got = drive(server.port, plan, sizes)
        finally:
            replayer.join()
        if "error" in model_out:
            raise model_out["error"]
        report["got"], report["want"] = got, model_out["want"]
        report["seconds"] = got["seconds"]
        report["model_s"] = model_out["seconds"]
        report["compared"] = compare(got, model_out["want"])
        rc = server.stop()
        require(server.stopped_cleanly(),
                f"server exit code {rc}")
    finally:
        if server.proc.poll() is None:
            # A failed run: stop the server the same way, so that its exit
            # snapshot says how far it got, then make sure it is gone.
            try:
                server.stop()
            except RuntimeError as err:  # keep the first failure on top
                print(f"chip_smoke: {err}", file=sys.stderr)
        if os.path.exists(server.metrics_path):
            with open(server.metrics_path) as f:
                report["server"] = summarize_server(json.load(f))
    check_server_report(report["server"], shards)


def summarize_server(snap: dict) -> dict:
    """What the server says it did, from its --metrics-json exit snapshot."""
    c, g, h = snap["counters"], snap["gauges"], snap["histograms"]
    compile_ms = h.get("jit.compile_ms", {})
    return {
        "warmup_s": g.get("start.warmup_s"),
        "compiles": c.get("jit.compiles", 0),
        "compile_s": round(compile_ms.get("sum", 0) / 1e3, 1),
        "compile_max_s": round(compile_ms.get("max", 0) / 1e3, 1),
        "dispatches": c.get("ops.dispatch", 0),
        "groups": c.get("pipeline.groups", 0),
        "routes": {
            "fast": c.get("ops.route.fast", 0),
            "grouped": c.get("ops.route.grouped", 0),
            "general": c.get("ops.route.general", 0),
            "sequential": c.get("ops.sequential_batches", 0),
        },
        "ledger_bytes": {k.split(".")[1]: v for k, v in g.items()
                         if k.endswith(".ledger_bytes")},
        "peak_bytes": {k.split(".")[1]: v for k, v in g.items()
                       if k.endswith(".peak_bytes_in_use")},
    }


def check_server_report(out: dict, shards: int) -> None:
    """The device executed the commits, the grouped, general and sequential
    routes were each taken, and (sharded) every device holds its share of
    the ledger."""
    routes = out["routes"]
    require(out["dispatches"] > 0,
            "no device dispatch counted")
    require(routes["grouped"] > 0,
            f"grouped route never taken: {routes}")
    require(routes["general"] > 0,
            f"general route never taken: {routes}")
    require(routes["sequential"] > 0,
            f"sequential route never taken: {routes}")
    held = [v for _k, v in sorted(out["ledger_bytes"].items())]
    if shards:
        require(len(held) >= shards and min(held[:shards]) > 0,
                f"ledger not spread over {shards} devices: {out['ledger_bytes']}")
        require(min(held[:shards]) * 2 > max(held[:shards]),
                f"ledger shares uneven: {out['ledger_bytes']}")


# --------------------------------------------------------------------------
# main: the chip run (no option accepts a CPU).
# --------------------------------------------------------------------------


def rebuild_native() -> None:
    """Remove any libtb.so the copy brought along (built elsewhere, for
    another CPU) and build it here from the committed sources; a silent
    drop to the pure-Python checksum is a failure on this path."""
    native_dir = os.path.join(ROOT, "tigerbeetle_tpu", "native")
    for stale in glob.glob(os.path.join(native_dir, "libtb.so*")):
        os.remove(stale)
    gxx = subprocess.run(["g++", "--version"], capture_output=True, text=True)
    print("g++:", (gxx.stdout.splitlines() or ["?"])[0], flush=True)
    from tigerbeetle_tpu import native

    t0 = time.monotonic()
    lib = native.load()
    require(lib is not None,
            "libtb.so did not build from committed sources")
    print(f"libtb.so rebuilt in {time.monotonic() - t0:.1f}s, "
          f"tb_aesni_enabled={lib.tb_aesni_enabled()}", flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: ONLY the sharded serving path (--shards 4)")
    p.add_argument("--seed", type=int, default=20260926)
    args = p.parse_args(argv)
    shards = 4 if args.chips == 4 else 0

    from tigerbeetle_tpu import jaxenv

    # fsync before reply as shipped, default pipeline depth, no TB_* switch.
    env = {k: v for k, v in os.environ.items() if not k.startswith("TB_")}
    workdir = tempfile.mkdtemp(prefix="tb_chip_smoke_")
    report: dict = {}
    error = None
    try:
        rebuild_native()
        run(FULL, args.seed, shards, env, "tpu", workdir, report)
    except Exception as err:  # the boundary: report, then exit non-zero
        error = f"{type(err).__name__}: {err}"
        traceback.print_exc()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    # The parent stayed off JAX's backends: the server child was the only
    # process on the chip.
    if error is None and jaxenv.current_platform() is not None:
        error = "SmokeFailure: the parent initialized a JAX backend"
    for key in ("ready_s", "model_s", "seconds", "compared", "server"):
        if key in report:
            print(f"{key}: {json.dumps(report[key])}", flush=True)
    device = report.get("device") or {}
    last = {
        "ok": error is None,
        "device": {"platform": device.get("platform"),
                   "kind": device.get("device_kind"),
                   "count": device.get("count")},
    }
    if error is not None:
        last["error"] = error
    print(json.dumps(last), flush=True)
    return 0 if error is None else 1


if __name__ == "__main__":
    sys.exit(main())
