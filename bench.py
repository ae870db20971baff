"""Benchmark: create_transfers throughput on the device ledger.

Mirrors the reference benchmark workload (src/tigerbeetle/benchmark_load.zig:
13-17: 10k accounts, 10M transfers, 8190-transfer batches; prints
"load accepted = N tx/s"): distinct-id valid transfers between random account
pairs, committed batch after batch against the device-resident ledger via the
vectorized commit kernel.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
vs_baseline is relative to the reference's flagship ~1M tx/s claim
(README.md:133-134).

The timed region runs entirely on device: batches are generated by hashing the
batch/lane index (no H2D traffic), and all batches execute inside a single
jitted fori_loop (one dispatch). A parity pre-check replays the same generator
through the scalar oracle at small scale and compares result codes and
balances exactly.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def _log2_ceil(x: int) -> int:
    return max(10, (x - 1).bit_length())


def mix64_np(lo: np.ndarray, hi: int) -> np.ndarray:
    """Pure-numpy twin of u128.mix64 (identical bits; no device dispatch —
    the client-side generator is on the e2e measurement path)."""
    with np.errstate(over="ignore"):
        x = lo.astype(np.uint64) ^ (
            np.uint64(hi) * np.uint64(0x9E3779B97F4A7C15)
        )
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def harness_contention_snapshot():
    """Host-contention guard (ROADMAP open item 1: check host contention
    before timing): snapshot the load average and the set of OTHER
    runnable/uninterruptible processes right before a timed region.  The
    fields ride payload.harness so rounds are triaged on per_batch_us at
    MATCHING harness fields (count/lanes/n_cores/shards AND a quiet host)
    instead of chasing host drift — on the 1-core bench host one
    competing process halves every number.  ``contended`` is the loud
    flag: competing runnable work observed, or a 1-minute load average
    well above the core count (the bench process itself accounts for 1)."""
    n_cores = os.cpu_count() or 1
    try:
        load1 = round(os.getloadavg()[0], 2)
    except OSError:
        load1 = None
    competing = []
    me = os.getpid()
    try:
        for pid in os.listdir("/proc"):
            if not pid.isdigit() or int(pid) == me:
                continue
            try:
                with open(f"/proc/{pid}/stat", "rb") as f:
                    stat = f.read()
                # comm may contain spaces/parens: state is the first field
                # after the LAST ')'.
                after = stat.rsplit(b")", 1)[1].split()
                if after and after[0] in (b"R", b"D"):
                    comm = stat.split(b"(", 1)[1].rsplit(b")", 1)[0]
                    competing.append(comm.decode("utf-8", "replace")[:24])
            except (OSError, IndexError):
                continue
    except OSError:
        pass
    contended = bool(competing) or (
        load1 is not None and load1 > n_cores + 1.0
    )
    snap = {
        "load_avg": load1,
        "competing_procs": sorted(competing)[:8],
        "n_competing": len(competing),
        "contended": contended,
    }
    if contended:
        print(
            "# WARNING: bench host CONTENDED before the timed region "
            f"(load_avg={load1}, n_cores={n_cores}, "
            f"competing={sorted(competing)[:8]}): throughput numbers "
            "from this run measure contention, not the hot path — triage "
            "on per_batch_us at matching payload.harness fields only",
            file=sys.stderr,
        )
    return snap


def gen_batch_np(b: int, lanes: int, count: int, n_accounts: int, first_tid: int):
    """Host (numpy) mirror of the on-device batch generator, for parity checks."""
    from tigerbeetle_tpu import types

    gid = np.arange(lanes, dtype=np.uint64) + np.uint64(b * count)
    h1 = mix64_np(gid, 0x1234)
    h2 = mix64_np(gid, 0x9876)
    dr = h1 % np.uint64(n_accounts)
    off = np.uint64(1) + h2 % np.uint64(n_accounts - 1)
    cr = (dr + off) % np.uint64(n_accounts)
    amount = np.uint64(1) + ((h1 >> np.uint64(32)) & np.uint64(0xFFFF))

    batch = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
    batch["id_lo"] = first_tid + gid
    batch["debit_account_id_lo"] = dr + 1
    batch["credit_account_id_lo"] = cr + 1
    batch["amount_lo"] = amount
    batch["ledger"] = 1
    batch["code"] = 10
    return batch[:count]


def run_parity_check(n_accounts=64, batches=3, count=200):
    """Device kernels vs oracle on the benchmark generator, small scale."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import LedgerConfig
    from tigerbeetle_tpu.machine import TpuStateMachine
    from tigerbeetle_tpu.testing import model as M

    cfg = LedgerConfig(
        accounts_capacity_log2=10, transfers_capacity_log2=11,
        posted_capacity_log2=10,
    )
    dev = TpuStateMachine(cfg, batch_lanes=256)
    ref = M.ReferenceStateMachine()

    accounts = types.accounts_array(
        [types.account(id=i + 1, ledger=1, code=10) for i in range(n_accounts)]
    )
    got = dev.create_accounts(accounts, wall_clock_ns=1)
    want = ref.create_accounts([M.account_from_row(r) for r in accounts], 1)
    assert got == want, "account parity failure"

    for b in range(batches):
        batch = gen_batch_np(b, count, count, n_accounts, 1 << 32)
        got = dev.create_transfers(batch)
        want = ref.create_transfers([M.transfer_from_row(r) for r in batch])
        assert got == want, f"parity failure batch {b}: {got[:5]} vs {want[:5]}"
    assert dev.balances_snapshot() == ref.balances_snapshot(), "balance parity failure"
    return True


def _read_line(proc, timeout_s, what):
    """readline with a deadline: a hung child must fail the measurement,
    not stall the whole benchmark (the primary JSON line must always
    print)."""
    import select

    deadline = time.time() + timeout_s
    buf = b""
    fd = proc.stdout.fileno()
    while time.time() < deadline:
        ready, _, _ = select.select([fd], [], [], 0.25)
        if not ready:
            continue
        ch = os.read(fd, 4096)
        if not ch:
            break
        buf += ch
        if b"\n" in buf:
            return buf.split(b"\n", 1)[0].decode().strip()
    raise RuntimeError(f"{what}: no output within {timeout_s:.0f}s "
                       f"(got {buf[:120]!r})")


def _drive_load(addresses, seconds, n_clients, n_accounts, count, cluster):
    """Spawn load-generator subprocesses against a serving cluster and merge
    their measured spans.  Separate PROCESSES (the reference benchmark is its
    own binary): in-process driver threads would contend with the server loop
    for the GIL and under-measure it.  Returns {tx_s, lat_ms: {...}}."""
    import subprocess

    from tigerbeetle_tpu import jaxenv

    addr_arg = ",".join(f"{h}:{p}" for h, p in addresses)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--e2e-client",
             addr_arg, str(cluster), str(seconds), str(ci),
             str(n_accounts), str(count)],
            env=jaxenv.child_env(cpu=True),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
        )
        for ci in range(n_clients)
    ]
    spans = []
    try:
        # READY/GO barrier: interpreter startup is seconds and varies; the
        # measured windows must coincide or throughput under-counts.
        for p in procs:
            line = _read_line(p, 180.0, "e2e client startup")
            if line != "READY":
                raise RuntimeError(f"e2e client failed to start: {line!r}")
        for p in procs:
            p.stdin.write(b"GO\n")
            p.stdin.flush()
        for p in procs:
            out, _ = p.communicate(timeout=seconds + 240)
            if p.returncode != 0:
                raise RuntimeError(f"e2e client failed rc={p.returncode}")
            spans.append(json.loads(out.decode().strip().splitlines()[-1]))
    finally:
        # An orphaned generator would keep spinning through the NEXT e2e
        # measurement on this 1-core host, depressing its number.
        for p in procs:
            if p.poll() is None:
                p.kill()
    window = max(s["t1"] for s in spans) - min(s["t0"] for s in spans)
    lat = np.concatenate(
        [np.asarray(s["lat_ms"], dtype=np.float64) for s in spans]
    )
    # Batch-latency percentiles, as the reference benchmark prints
    # (benchmark_load.zig:377-392).
    pct = {}
    if lat.size:
        for p_ in (50, 90, 99, 100):
            pct[f"p{p_}"] = round(float(np.percentile(lat, p_)), 2)
    out = {
        "tx_s": sum(s["total"] for s in spans) / window,
        "total": sum(s["total"] for s in spans),
        "window_s": round(window, 3),
        "lat_ms": pct,
    }
    outliers = [o for s in spans for o in s.get("outliers", [])]
    if outliers:
        out["outliers"] = sorted(outliers, key=lambda o: o["at_s"])
    return out


def _setup_accounts(addresses, cluster, n_accounts, count):
    """Create the workload accounts + one untimed warm batch."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.client import Client

    setup = Client(addresses, cluster=cluster, timeout_s=120.0)
    try:
        accounts = types.accounts_array(
            [types.account(id=i + 1, ledger=1, code=10)
             for i in range(n_accounts)]
        )
        assert setup.create_accounts(accounts) == []
        # One untimed batch absorbs any residual first-dispatch cost.
        assert setup.create_transfers(
            gen_batch_np(0, count, count, n_accounts, 1 << 40)
        ) == []
    finally:
        setup.close()


def run_e2e(seconds=4.0, n_clients=4, host_engine=True):
    """End-to-end tx/s: real TCP + WAL fsync + commit + reply decode — the
    measurement path of the reference's benchmark_load.zig (its 1M tx/s is
    end-to-end, so the kernel headline needs this companion number).

    n_clients concurrent sessions (one in-flight request each, the protocol
    bound) keep the server's group-commit pipeline fed, exactly as the
    reference benchmark drives concurrent client batches
    (benchmark_load.zig:377-392).

    host_engine=True commits through the native C++ host engine
    (host_engine.py) — the latency-bound OLTP executor.  host_engine=False
    commits through the DEVICE kernel (TpuStateMachine): on TPU hardware
    that is the thesis path, and its end-to-end number is what proves the
    accelerator earns its place in the serving loop.
    Returns {tx_s, lat_ms}, or raises (caller reports null)."""
    import asyncio
    import shutil
    import tempfile
    import threading

    from tigerbeetle_tpu.config import LedgerConfig, ProcessConfig
    from tigerbeetle_tpu.host_engine import engine_available
    from tigerbeetle_tpu.net.bus import ReplicaServer
    from tigerbeetle_tpu.vsr.replica import Replica

    if host_engine and not engine_available():
        # Falling through to the device path would report the SAME executor
        # under two different metric names; a null beats a fabricated number.
        raise RuntimeError("native host engine unavailable (build failed?)")

    tmp = tempfile.mkdtemp(prefix="tb_bench_e2e_")
    path = os.path.join(tmp, "bench.tb")
    Replica.format(path, cluster=0xBE)
    # Table sized so the measured window needs no growth rehash (load factor
    # stays < 0.5 at ~8M transfers); warmup() pre-faults the pages.
    # lazy_index on the device path: eager per-commit index appends compile
    # a fresh level-merge program per new pyramid shape inside the measured
    # window; the write-only benchmark load never queries, so maintenance
    # is pure loss either way.
    led = LedgerConfig(
        accounts_capacity_log2=14, transfers_capacity_log2=24,
        posted_capacity_log2=14, lazy_index=not host_engine,
    )
    replica = Replica(
        path, ledger_config=led, batch_lanes=8192,
        host_engine=host_engine,
        # O_DIRECT WAL (the reference requires it, storage.zig:14+): buffered
        # writes absorb dirty-page writeback throttling in the serving loop
        # (measured 6.9 ms/MB buffered vs 0.6 ms/MB direct on this host).
        process_config=ProcessConfig(direct_io=True),
    )
    replica.open()
    replica.async_checkpoint = True  # the server mode (overlapped WAL/compute)
    replica.machine.warmup()

    box = {}
    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()

    async def boot():
        server = ReplicaServer(replica, "127.0.0.1", 0)
        box["port"] = await server.start()
        return server

    server = asyncio.run_coroutine_threadsafe(boot(), loop).result(30)
    try:
        n_accounts = 1024
        count = 8190
        addresses = [("127.0.0.1", box["port"])]
        # Stage labels: a failure must say WHERE (the round-4 e2e_device
        # run died as a bare TimeoutError with no trail).
        try:
            stage = "setup_accounts"
            _setup_accounts(addresses, 0xBE, n_accounts, count)
            stage = "drive_load"
            m = replica.machine
            d0, w0 = m.disp_count, m.disp_wait_s
            out = _drive_load(addresses, seconds, n_clients, n_accounts,
                              count, cluster=0xBE)
            if not host_engine:
                # How many device dispatches served the window and how long
                # the serving thread blocked on them.
                out["n_clients"] = n_clients
                out["dispatches"] = m.disp_count - d0
                out["dispatch_wait_s"] = round(m.disp_wait_s - w0, 3)
            return out
        except Exception as err:
            raise RuntimeError(f"e2e {stage}: {err!r}") from err
    finally:
        async def down():
            await server.close()

        asyncio.run_coroutine_threadsafe(down(), loop).result(15)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=5)
        replica.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_e2e_cluster(seconds=4.0, n_clients=2, n_replicas=3):
    """3-replica cluster end-to-end: TCP + ring replication + quorum commit
    + WAL on every replica (the reference's deployment shape is a multi-
    replica cluster, docs/deploy/hardware.md:29-40; its benchmark default is
    1 replica, so this is the number the reference does NOT publish).

    Each replica is its own OS process (python -m tigerbeetle_tpu start),
    exactly as deployed; commits run through the native host engine
    (--engine) so the measurement is consensus+network+WAL cost, not
    interpreter contention."""
    import shutil
    import subprocess
    import tempfile

    from tigerbeetle_tpu import jaxenv
    from tigerbeetle_tpu.host_engine import engine_available
    from tigerbeetle_tpu.vsr.replica import Replica

    if not engine_available():
        # The CLI would silently drop --engine and measure device-path
        # replicas under the cluster metric's name.
        raise RuntimeError("native host engine unavailable (build failed?)")

    tmp = tempfile.mkdtemp(prefix="tb_bench_cluster_")
    cluster = 0xC3
    # Reserve ports by binding momentarily (free_ports idiom).
    import socket as _socket

    socks = [_socket.socket() for _ in range(n_replicas)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    addresses = [("127.0.0.1", p) for p in ports]
    addr_arg = ",".join(f"{h}:{p}" for h, p in addresses)

    paths = [os.path.join(tmp, f"r{i}.tb") for i in range(n_replicas)]
    for i, p in enumerate(paths):
        Replica.format(p, cluster=cluster, replica=i,
                       replica_count=n_replicas)
    procs = []
    try:
        for i, p in enumerate(paths):
            procs.append(subprocess.Popen(
                # Preallocate for the measured load (the reference sizes
                # everything at format time, config.zig static allocation):
                # in-loop table growth is a doubling-cost rehash that blocks
                # the replica's IO loop — measured 96→797 ms dispatch stalls,
                # each one a cluster-wide liveness probe + latency spike.
                [sys.executable, "-m", "tigerbeetle_tpu", "start", p,
                 "--addresses", addr_arg, "--engine", "--direct-io",
                 "--cache-accounts-log2", "14",
                 "--cache-transfers-log2", "22", "--tick-ms", "2"],
                env=jaxenv.child_env(cpu=True),
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            ))
        # Readiness barrier: each replica prints "listening host:port" after
        # kernel warmup (JIT compile — allow minutes, not forever).
        for p in procs:
            line = _read_line(p, 300.0, "cluster replica startup")
            if "listening" not in line:
                raise RuntimeError(f"cluster replica failed to start: {line!r}")
        n_accounts = 1024
        count = 8190
        _setup_accounts(addresses, cluster, n_accounts, count)
        return _drive_load(addresses, seconds, n_clients, n_accounts, count,
                           cluster=cluster)
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def _e2e_client_main(argv) -> None:
    """Subprocess load generator for run_e2e* (one session, one in-flight
    request — the protocol bound; disjoint id space per client index)."""
    addr_arg, cluster, seconds, ci, n_accounts, count = (
        argv[0], int(argv[1]), float(argv[2]), int(argv[3]), int(argv[4]),
        int(argv[5]),
    )
    from tigerbeetle_tpu.cli import _parse_addresses
    from tigerbeetle_tpu.client import Client

    addresses = _parse_addresses(addr_arg)

    client = Client(addresses, cluster=cluster, timeout_s=60.0)
    first_tid = (1 << 40) + ((ci + 1) << 33)
    # One untimed batch warms the session + allocator.
    assert client.create_transfers(
        gen_batch_np(0, count, count, n_accounts, first_tid)
    ) == []
    print("READY", flush=True)
    assert sys.stdin.readline().strip() == "GO"
    # Template batch: per-iteration work is an id-column rewrite only (the
    # account pairs/amounts repeating across batches changes nothing the
    # server does — every id is still a distinct insert).  On a single-core
    # bench host the generator's CPU comes out of the server's budget.
    template = gen_batch_np(1, count, count, n_accounts, first_tid)
    base_ids = template["id_lo"].copy()
    # Untimed lead-in: loopback TCP windows and the group pipeline take
    # ~1 s to reach steady state; the reference benchmark likewise reports
    # steady-state load (benchmark_load.zig warmup discipline).
    lead = 1.0
    total, b = 0, 1
    lat_ms = []
    outliers = []
    fo_before = client.failover_count
    begin = time.time()
    t0 = begin + lead
    t_end = t0 + seconds
    while True:
        now = time.time()
        if now >= t_end:
            break
        template["id_lo"] = base_ids + np.uint64((b - 1) * count)
        req_t0 = time.time()
        assert client.create_transfers(template) == []
        req_t1 = time.time()
        if req_t1 >= t0:
            total += count
            lat_ms.append(round((req_t1 - req_t0) * 1e3, 2))
            # Outlier forensics: a tail spike must be attributable to a
            # wall-clock moment (correlate against TB_DEBUG_LOG replica
            # events) and to its cause (failover resends vs a slow reply).
            if (req_t1 - req_t0) > 1.0:
                outliers.append({
                    "at_s": round(req_t0 - t0, 2),
                    "ms": round((req_t1 - req_t0) * 1e3, 1),
                    "failovers": client.failover_count - fo_before,
                })
        fo_before = client.failover_count
        b += 1
    t1 = max(time.time(), t0)
    client.close()
    print(json.dumps({"total": total, "t0": t0, "t1": t1, "lat_ms": lat_ms,
                      "outliers": outliers}))


def run_kernel_profile(lanes=8192, count=8190, n_accounts=1024, batches=12):
    """Per-batch device cost of each commit-kernel variant on the SAME
    plain workload, plus Jacobi pass counts per workload class.

    The round-3 verdict called the claimed ~20x fast:general ratio
    unmeasured; this measures it, and the round-4 static phase gates
    (has_postvoid/has_history) before/after: general_full_us is the
    round-3 always-on kernel, general_gated_us is what plain batches pay
    now."""
    import functools

    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.ops import state_machine as sm
    from tigerbeetle_tpu.ops import transfer_full as tf

    def fresh_ledger():
        # Transfers table sized for 2*batches*count inserts (warm half +
        # timed half of the stacked prebuilt run) at < 0.5 load (a
        # saturating table measures probe-chain pathology, not the kernel).
        tr_log2 = max(16, (2 * batches * count * 2 - 1).bit_length())
        led = sm.make_ledger(1 << 12, 1 << tr_log2, 1 << 12)
        batch = np.zeros(lanes, dtype=types.ACCOUNT_DTYPE)
        batch["id_lo"][:n_accounts] = 1 + np.arange(n_accounts, dtype=np.uint64)
        batch["ledger"][:n_accounts] = 1
        batch["code"][:n_accounts] = 10
        soa = {k: jnp.asarray(v) for k, v in types.to_soa(batch).items()}
        led, _ = sm.create_accounts(
            led, soa, jnp.uint64(n_accounts), jnp.uint64(n_accounts)
        )
        return led

    def plain_soa(b):
        batch = gen_batch_np(b, lanes, count, n_accounts, (1 << 32) + b)
        padded = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
        padded[:count] = batch
        return {k: jnp.asarray(v) for k, v in types.to_soa(padded).items()}

    # Device-resident batches built BEFORE any timed window, STACKED so the
    # whole run fits in ONE dispatch (a per-batch dispatch loop measures the
    # host<->device round trip, not the kernel).
    n_prebuilt = 2 * batches
    stacked = {}
    for b in range(n_prebuilt):
        soa = plain_soa(b)
        for key, v in soa.items():
            stacked.setdefault(key, []).append(v)
    stacked = {key: jnp.stack(v) for key, v in stacked.items()}
    tss = jnp.asarray(
        [n_accounts + (b + 1) * count for b in range(n_prebuilt)],
        dtype=jnp.uint64,
    )
    jax.block_until_ready(stacked["id_lo"])

    def timed(step):
        def multi(led, b0, k):
            def body(i, led_):
                soa = {key: v[b0 + i] for key, v in stacked.items()}
                return step(led_, soa, jnp.uint64(count), tss[b0 + i])

            return jax.lax.fori_loop(0, k, body, led)

        run = jax.jit(multi, static_argnames=("k",), donate_argnames=("led",))
        led = fresh_ledger()
        # Warm with the SAME static k the timed call uses (a different k
        # would be a fresh trace+compile inside the timing) — commits
        # batches [0, batches); the timed call commits the second half.
        led = run(led, 0, batches)
        jax.block_until_ready(led.accounts.count)
        t0 = time.time()
        led = run(led, batches, batches)
        jax.block_until_ready(led.accounts.count)
        per = (time.time() - t0) / batches * 1e6  # us/batch
        del led
        return per

    def fast_step(led, soa, cnt, ts):
        # The serving kernel body (jit-inlined into the measuring loop —
        # the same composition multi_jit serves).
        led, _ = sm.create_transfers_impl(led, soa, cnt, ts)
        return led

    def general_step(gated):
        def step(led, soa, cnt, ts):
            led, _, _ = tf.create_transfers_full_impl(
                led, soa, cnt, ts,
                has_postvoid=not gated, has_history=not gated,
            )
            return led

        return step

    def waves_step(led, soa, cnt, ts):
        led, _, _, _wv = tf.create_transfers_full_impl(
            led, soa, cnt, ts, has_postvoid=False, has_history=False,
            use_waves=True,
        )
        return led

    out = {
        "fast_us": round(timed(fast_step), 1),
        "general_gated_us": round(timed(general_step(True)), 1),
        "general_full_us": round(timed(general_step(False)), 1),
        # The SAME gated shape with the conflict-index wave scheduler on:
        # the certified pass bound drops the Jacobi verification pass, so
        # this vs general_gated_us is the waves win on the plain mix.
        "general_gated_waves_us": round(timed(waves_step), 1),
    }
    out["fast_vs_general_full"] = round(out["general_full_us"] / out["fast_us"], 1)
    out["fast_vs_general_gated"] = round(
        out["general_gated_us"] / out["fast_us"], 1
    )
    out["gate_speedup"] = round(
        out["general_full_us"] / out["general_gated_us"], 2
    )
    out["waves_speedup"] = round(
        out["general_gated_us"] / out["general_gated_waves_us"], 2
    )

    # Jacobi pass counts by workload class (the fixpoint budget's reality).
    @functools.partial(jax.jit, static_argnames=("has_postvoid", "use_waves"))
    def passes_probe(led, soa, cnt, ts, has_postvoid=True, use_waves=False):
        n = soa["id_lo"].shape[0]
        lane = jnp.arange(n, dtype=jnp.int32)
        valid = lane < cnt.astype(jnp.int32)
        pv = (
            ((soa["flags"] & tf.TF_POST) != 0)
            | ((soa["flags"] & tf.TF_VOID) != 0)
        ) & valid
        ctx = tf.build_gather_ctx(led, soa, valid, pv,
                                  has_postvoid=has_postvoid)
        plan = tf._kernel_core(ctx, soa, cnt, ts, use_waves=use_waves)
        return plan.passes, plan.wave_bound, plan.wave_hist

    led = fresh_ledger()
    passes = {}
    waves = {}
    ts0 = jnp.uint64(n_accounts + 10 * count)

    def probe(led_, soa_, cnt_, ts_, has_postvoid=True):
        """(passes_off, waves entry): the off pass count keeps the
        jacobi_passes series comparable across rounds; the on entry adds
        the proved bound + wave-depth occupancy (docs/waves.md)."""
        p_off, _, _ = passes_probe(
            led_, soa_, cnt_, ts_, has_postvoid=has_postvoid
        )
        p_on, bound, hist = passes_probe(
            led_, soa_, cnt_, ts_, has_postvoid=has_postvoid, use_waves=True
        )
        hist = np.asarray(hist)
        total = max(int(hist.sum()), 1)
        return int(p_off), {
            "passes": int(p_on),
            "bound": int(bound),
            "wave0_pct": round(100 * int(hist[0]) / total, 1),
            "wave_hist": hist.tolist(),
        }

    passes["plain"], waves["plain"] = probe(
        led, plain_soa(0), jnp.uint64(count), ts0, has_postvoid=False
    )
    # Two-phase: half pendings, half posts of those pendings (in-batch refs).
    half = count // 2
    batch = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
    batch["id_lo"][:half] = (1 << 40) + np.arange(half, dtype=np.uint64)
    batch["debit_account_id_lo"][:half] = 1 + np.arange(half) % n_accounts
    batch["credit_account_id_lo"][:half] = 1 + (np.arange(half) + 7) % n_accounts
    batch["amount_lo"][:half] = 5
    batch["ledger"][:half] = 1
    batch["code"][:half] = 10
    batch["flags"][:half] = types.TransferFlags.PENDING
    batch["id_lo"][half:2 * half] = (1 << 41) + np.arange(half, dtype=np.uint64)
    batch["pending_id_lo"][half:2 * half] = (1 << 40) + np.arange(
        half, dtype=np.uint64
    )
    batch["flags"][half:2 * half] = types.TransferFlags.POST_PENDING_TRANSFER
    soa = {k: jnp.asarray(v) for k, v in types.to_soa(batch).items()}
    passes["two_phase_in_batch"], waves["two_phase_in_batch"] = probe(
        led, soa, jnp.uint64(2 * half), ts0 + jnp.uint64(count)
    )
    # Balancing chain on one account pair: each lane's clamp feeds the
    # next.  Account 1 must be FUNDED first (credits_posted > 0) or every
    # clamp saturates to zero on pass 1 and the probe measures an
    # all-reject batch instead of a cascade.
    k = min(32, count)
    fund = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
    fund["id_lo"][:k] = (1 << 43) + np.arange(k, dtype=np.uint64)
    fund["debit_account_id_lo"][:k] = 3
    fund["credit_account_id_lo"][:k] = 1
    fund["amount_lo"][:k] = 1 << 40
    fund["ledger"][:k] = 1
    fund["code"][:k] = 10
    fsoa = {k2: jnp.asarray(v) for k2, v in types.to_soa(fund).items()}
    led, _, kf = tf.create_transfers_full(
        led, fsoa, jnp.uint64(k), ts0 + jnp.uint64(count),
    )
    assert int(kf) == 0, "funding batch routed"
    batch = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
    batch["id_lo"][:k] = (1 << 42) + np.arange(k, dtype=np.uint64)
    batch["debit_account_id_lo"][:k] = 1
    batch["credit_account_id_lo"][:k] = 2
    batch["amount_lo"][:k] = 0
    batch["ledger"][:k] = 1
    batch["code"][:k] = 10
    batch["flags"][:k] = types.TransferFlags.BALANCING_DEBIT
    soa = {k2: jnp.asarray(v) for k2, v in types.to_soa(batch).items()}
    passes["balancing_chain"], waves["balancing_chain"] = probe(
        led, soa, jnp.uint64(k), ts0 + jnp.uint64(2 * count)
    )

    # Zipfian open-loop-shaped mix: hot accounts concentrate conflicts
    # (PR 6's generator shape), half plain + half posts of TABLE pendings
    # — the realistic mix the wave scheduler targets (fewer effective
    # passes with waves on is the tentpole's success criterion).
    half = count // 2
    hot = mix64_np(np.arange(count, dtype=np.uint64), 0x51F)
    u = (hot >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    zipf_acct = 1 + np.minimum(
        (n_accounts * u ** 3).astype(np.uint64), np.uint64(n_accounts - 1)
    )
    pend = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
    pend["id_lo"][:half] = (1 << 45) + np.arange(half, dtype=np.uint64)
    pend["debit_account_id_lo"][:half] = zipf_acct[:half]
    pend["credit_account_id_lo"][:half] = 1 + (zipf_acct[:half] % n_accounts)
    same = pend["credit_account_id_lo"][:half] == pend["debit_account_id_lo"][:half]
    pend["credit_account_id_lo"][:half] = np.where(
        same, 1 + (zipf_acct[:half] + 1) % n_accounts,
        pend["credit_account_id_lo"][:half],
    )
    pend["amount_lo"][:half] = 7
    pend["ledger"][:half] = 1
    pend["code"][:half] = 10
    pend["flags"][:half] = types.TransferFlags.PENDING
    psoa = {k2: jnp.asarray(v) for k2, v in types.to_soa(pend).items()}
    led, _, kfp = tf.create_transfers_full(
        led, psoa, jnp.uint64(half), ts0 + jnp.uint64(3 * count)
    )
    assert int(kfp) == 0, "zipf pending setup routed"
    mix = np.zeros(lanes, dtype=types.TRANSFER_DTYPE)
    mix["id_lo"][:half] = (1 << 46) + np.arange(half, dtype=np.uint64)
    mix["debit_account_id_lo"][:half] = zipf_acct[half:2 * half]
    mix["credit_account_id_lo"][:half] = 1 + (
        (zipf_acct[half:2 * half] + 3) % n_accounts
    )
    mix["amount_lo"][:half] = 3
    mix["ledger"][:half] = 1
    mix["code"][:half] = 10
    mix["id_lo"][half:2 * half] = (1 << 47) + np.arange(half, dtype=np.uint64)
    mix["pending_id_lo"][half:2 * half] = (1 << 45) + np.arange(
        half, dtype=np.uint64
    )
    mix["flags"][half:2 * half] = types.TransferFlags.POST_PENDING_TRANSFER
    msoa = {k2: jnp.asarray(v) for k2, v in types.to_soa(mix).items()}
    passes["zipf_mixed"], waves["zipf_mixed"] = probe(
        led, msoa, jnp.uint64(2 * half), ts0 + jnp.uint64(4 * count)
    )
    out["jacobi_passes"] = passes
    out["waves"] = waves
    return out


def run_pipeline_bench(depth, n_groups=32, n_clients=4, count=2048,
                       lanes=8192, scrub_interval=0,
                       merkle=False, attribution=False, fuse=False,
                       merkle_async=False, disjoint_accounts=False):
    """Direct serving-path measurement of the pipelined commit engine
    (docs/commit_pipeline.md): drives replica.on_request_group_pipelined
    with fixed groups of create_transfers requests, no TCP in the way, so
    the depth lever — deferred readbacks + fsync/compute overlap — is the
    ONLY thing that differs between sweep entries.

    The replica clock is pinned (time_ns=0): prepare timestamps then derive
    purely from event counts, so the reply bodies and the final ledger
    digest are comparable ACROSS depths — the sweep doubles as an identity
    check (tools/pipeline_smoke.py asserts it in CI).

    Returns {tx_s, p50_ms, p99_ms, per_batch_us, transfers, replies_sha,
    digest, pipeline: {counter deltas}} for one depth.

    ``fuse`` / ``merkle_async`` arm the PR 18 knobs (machine.fuse_batches
    / machine.merkle_async; docs/commit_pipeline.md fusion section);
    ``disjoint_accounts`` partitions the account pool per client so a
    group's batches carry disjoint conflict signatures — the open-loop
    non-conflicting mix the fusion sweep measures.  The mix changes the
    workload (and so replies_sha), so identity comparisons hold only
    WITHIN one mix setting."""
    import hashlib
    import shutil
    import tempfile

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import (
        ClusterConfig, LedgerConfig, ProcessConfig,
    )
    from tigerbeetle_tpu.obs.metrics import registry as _obs
    from tigerbeetle_tpu.obs.txtrace import NESTED_STAGES, txtrace
    from tigerbeetle_tpu.vsr import wire
    from tigerbeetle_tpu.vsr.replica import Replica

    tmp = tempfile.mkdtemp(prefix="tb_bench_pipe_")
    path = os.path.join(tmp, "pipe.tb")
    # Small-lane sweeps (the dispatch-overhead-bound regime the pipeline
    # targets) shrink the wire batch_max to match the kernel lane count.
    ccfg = ClusterConfig(
        message_size_max=lanes * 128 + 256, journal_slot_count=256,
    ) if lanes < 8192 else ClusterConfig()
    Replica.format(path, cluster=0x91, cluster_config=ccfg)
    led = LedgerConfig(
        accounts_capacity_log2=12,
        transfers_capacity_log2=max(
            14, (2 * (n_groups + 2) * n_clients * count - 1).bit_length()
        ),
        posted_capacity_log2=10, lazy_index=True,
    )
    replica = Replica(
        path, cluster_config=ccfg, ledger_config=led, batch_lanes=lanes,
        process_config=ProcessConfig(direct_io=True),
        time_ns=lambda: 0,  # pinned: cross-depth identity (docstring)
    )
    replica.open()
    replica.async_checkpoint = True  # server mode (overlapped WAL/ckpt)
    replica.pipeline_depth = depth
    replica.machine.fuse_batches = fuse
    replica.machine.merkle_async = merkle_async
    replica.machine.warmup()
    if scrub_interval:
        # Device fault domain armed at cadence N: the measured delta vs
        # interval 0 IS the robustness tax — mirror maintenance + digest
        # folds in the PR 4 mode, incremental touched-path updates + root
        # compares in merkle mode (docs/commitments.md quotes this sweep).
        replica.machine.scrub_interval = scrub_interval
        if merkle:
            replica.machine.merkle_enabled = True
            # Mirror OFF at every cadence: this sweep measures the TREE'S
            # tax alone (payload.merkle.overhead_vs_off vs the old
            # payload.scrub mirror numbers).
            replica.machine.scrub_paranoid = False
        replica.machine.scrub_arm()
        if merkle:
            # Untimed check: compiles the verify reduction BEFORE the
            # timed loop — at cadences > 1 the first check would
            # otherwise land its jit compile inside the measurement
            # (interval 1 gets it free from the warm group).
            replica.machine.scrub_check()

    def request(client_id, session, request_n, op, body):
        h = wire.new_header(
            wire.Command.request, cluster=0x91, client=client_id,
            request=request_n, session=session, operation=int(op),
        )
        h["size"] = wire.HEADER_SIZE + len(body)
        # Stamp a causal trace id exactly as the network client does
        # (client.py): under txtrace.sampling_scope the --trace-overhead
        # arm traces every request; otherwise this is one attr read + 0.
        trace = txtrace.maybe_trace(client_id & 0xFFFF)
        if trace:
            h["trace"] = trace
            txtrace.hop(trace, "client.request", phase="start",
                        request=request_n)
        h = wire.set_checksums(h, body)
        return h, body

    counters0 = {}
    if _obs.enabled:
        snap = _obs.snapshot()["counters"]
        counters0 = {
            k: v for k, v in snap.items()
            if k.startswith(("pipeline.", "fuse.", "merkle.lane."))
        }
    fsyncs = []
    try:
        n_accounts = 256
        # TWO client cohorts alternate groups: the protocol allows one
        # in-flight request per session, so consecutive commit groups come
        # from DIFFERENT sessions under sustained load — which is exactly
        # what lets group N+1 admit while group N's replies are pending.
        clients = [0x9100 + ci for ci in range(2 * n_clients)]
        sessions = {}
        for c in clients:
            replies, fs = replica.on_request_group_pipelined(
                [request(c, 0, 0, wire.Operation.register, b"")]
            )
            rh, _ = wire.decode_header(replies[0][0][:wire.HEADER_SIZE])
            sessions[c] = int(rh["commit"])
            if fs is not None:
                fs.result()
        accounts = np.zeros(n_accounts, dtype=types.ACCOUNT_DTYPE)
        accounts["id_lo"] = 1 + np.arange(n_accounts, dtype=np.uint64)
        accounts["ledger"] = 1
        accounts["code"] = 10
        replies, fs = replica.on_request_group_pipelined([request(
            clients[0], sessions[clients[0]], 1,
            wire.Operation.create_accounts, accounts.tobytes(),
        )])
        assert replies[0][0][256:] == b"", "account setup failed"
        if fs is not None:
            fs.result()

        def group_bodies(g):
            out = []
            for ci in range(n_clients):
                first = (1 << 33) + ((ci + 1) << 28) + g * count
                if disjoint_accounts:
                    # Per-client account partition: a group's batches
                    # carry disjoint conflict signatures (transfer ids
                    # are disjoint by ``first`` already), so the fusion
                    # planner coalesces the whole group.
                    per = n_accounts // n_clients
                    b = gen_batch_np(0, count, count, per, first)
                    shift = np.uint64(ci * per)
                    b["debit_account_id_lo"] += shift
                    b["credit_account_id_lo"] += shift
                    out.append(b.tobytes())
                else:
                    out.append(gen_batch_np(
                        0, count, count, n_accounts, first
                    ).tobytes())
            return out

        def cohort(g):
            half = clients[(g % 2) * n_clients:][:n_clients]
            return half

        # One untimed group absorbs residual first-dispatch cost.
        replies, fs = replica.on_request_group_pipelined(
            [
                request(c, sessions[c], 2, wire.Operation.create_transfers,
                        b)
                for c, b in zip(cohort(1), group_bodies(n_groups + 7))
            ],
            deferred_replies=True,
        )
        if fs is not None:
            fsyncs.append(fs)
        if scrub_interval:
            # Armed entries: settle the warm group before the clock
            # starts — its dispatch-lane closures carry the ONE-TIME
            # mirror/merkle kernel compiles (e.g. the first touched-path
            # update's jit), which would otherwise bleed several seconds
            # into the first timed group and misreport the steady-state
            # robustness tax.
            replica.pipeline_flush()
        import concurrent.futures as _cf

        from tigerbeetle_tpu import sanitize as _san

        sha = hashlib.sha256()
        lat_ms = []
        group_replies = [replies]
        # Recompile tripwire: the warm group above absorbed every
        # legitimate compile — a compile INSIDE the timed loop means
        # warmup bled into the clock (recorded per entry; the headline
        # payload.harness carries the main loop's twin).  The context
        # manager installs the compile listener itself, so smoke tools
        # calling run_pipeline_bench() directly (without bench main's
        # arming) still get real counts.  quiet: bench wording below.
        with _san.compile_tripwire(
            "pipeline-bench timed loop", raise_on_trip=False, quiet=True
        ) as timed_report:
            if attribution:
                # The stage ledger covers EXACTLY the timed loop: setup +
                # warmup stage observations (first-dispatch compiles would
                # dwarf steady-state device_execute) are discarded here.
                txtrace.reset_stages()
            t0 = time.monotonic()
            for g in range(n_groups):
                reqs = [
                    request(c, sessions[c], 3 + g // 2,
                            wire.Operation.create_transfers, b)
                    for c, b in zip(cohort(g), group_bodies(g))
                ]
                gt0 = time.monotonic()
                # The bus contract: replies may be a Future that comes due
                # with the NEXT group (cross-group overlap); per-group
                # latency is request-to-reply-release, captured by a done
                # callback.
                replies, fs = replica.on_request_group_pipelined(
                    reqs, deferred_replies=True
                )
                if isinstance(replies, _cf.Future):
                    replies.add_done_callback(
                        lambda _f, t=gt0: lat_ms.append(
                            (time.monotonic() - t) * 1e3
                        )
                    )
                else:
                    lat_ms.append((time.monotonic() - gt0) * 1e3)
                if fs is not None:
                    fsyncs.append(fs)
                group_replies.append(replies)
            replica.pipeline_flush()  # last group's readbacks come due
            for fs in fsyncs:
                # The reply barrier: durability before the clock stops.
                fs.result()
            elapsed = max(time.monotonic() - t0, 1e-9)
            if attribution:
                # Snapshot before anything after the clock can observe.
                stage_totals = txtrace.stage_totals()
        # None when unarmed (listener unavailable): see the main loop.
        timed_compiles = timed_report.compiles if timed_report.armed else None
        if timed_compiles:
            print(
                f"# WARNING: {timed_compiles} XLA compile(s) inside the "
                f"pipeline-bench timed loop (depth={depth}): warmup bled "
                "into the clock — tx_s/p99 for this entry are not "
                "steady-state",
                file=sys.stderr,
            )
        for g, replies in enumerate(group_replies):
            if isinstance(replies, _cf.Future):
                replies = replies.result()
            if g == 0:
                continue  # untimed warm group: excluded from the digest
            for rl in replies:
                assert rl, "request dropped"
                # Identity digest over reply CONTENT: the deterministic
                # header fields (op/commit/timestamp ride the pinned
                # clock) plus the result body — not just the body, which
                # the all-accepted workload keeps empty by construction.
                rh, _cmd = wire.decode_header(rl[0][:wire.HEADER_SIZE])
                for field in ("op", "commit", "timestamp", "request"):
                    sha.update(int(rh[field]).to_bytes(8, "little"))
                sha.update(rl[0][wire.HEADER_SIZE:])
                assert rl[0][256:] == b"", "unexpected result codes"
        lat_sorted = sorted(lat_ms)
        out = {
            "tx_s": round(n_groups * n_clients * count / elapsed, 1),
            "p50_ms": round(lat_sorted[len(lat_sorted) // 2], 2),
            "p99_ms": round(lat_sorted[int(len(lat_sorted) * 0.99)], 2),
            # One batch == one client request's dispatch; the flagship
            # per_batch_us headline (BENCH_r11: 28.9 ms/batch) divides
            # the same wall clock by the same batch count.
            "per_batch_us": round(
                elapsed * 1e6 / (n_groups * n_clients), 1
            ),
            "transfers": n_groups * n_clients * count,
            "replies_sha": sha.hexdigest(),
            "digest": f"{replica.machine.digest():#x}",
            "timed_compiles": timed_compiles,
        }
        if fuse or merkle_async or disjoint_accounts:
            out["knobs"] = {
                "fuse": bool(fuse), "merkle_async": bool(merkle_async),
                "disjoint_accounts": bool(disjoint_accounts),
            }
        if attribution:
            # Per-commit-batch stage ledger (docs/tracing.md): one batch =
            # one request's dispatch; the timed loop ran n_groups groups of
            # n_clients requests.  `coverage` is sum(stage us) / wall over
            # the stages that are not nested in another's block — ~1.0 at
            # depth 1 (serial path), legitimately ABOVE 1.0 at depth > 1
            # where overlapped stages (fsync under compute, lane-idle
            # dispatch_wait) each bill their own wall clock.
            batches = n_groups * n_clients
            attributed_us = sum(
                v["us"] for k, v in stage_totals.items()
                if k not in NESTED_STAGES
            )
            measured_us = elapsed * 1e6
            out["attribution"] = {
                "stages_us_per_batch": {
                    k: round(v["us"] / batches, 1)
                    for k, v in stage_totals.items()
                },
                "stage_counts": {
                    k: v["count"] for k, v in stage_totals.items()
                },
                "attributed_us_per_batch": round(attributed_us / batches, 1),
                "measured_us_per_batch": round(measured_us / batches, 1),
                "coverage": round(attributed_us / measured_us, 3),
            }
        if scrub_interval:
            out["scrub"] = {
                "interval": scrub_interval,
                "checks": replica.machine.scrub_checks,
                "mismatches": replica.machine.scrub_mismatches,
                "recoveries": replica.machine.device_recoveries,
            }
            if merkle:
                roots = replica.machine.merkle_roots()
                out["merkle"] = {
                    "updates": replica.machine.merkle_updates,
                    "rebuilds": replica.machine.merkle_rebuilds,
                    "mismatches": replica.machine.merkle_mismatches,
                    "roots": [f"{r:#x}" for r in roots] if roots else None,
                }
                if merkle_async:
                    # Commitment-lane accounting: settles (drain points)
                    # and lag ride the obs section below; the plain
                    # counter is always available.
                    out["merkle"]["settles"] = replica.machine.merkle_settles
        if _obs.enabled:
            snap = _obs.snapshot()["counters"]
            out["pipeline"] = {
                k.split("pipeline.", 1)[1]: v - counters0.get(k, 0)
                for k, v in snap.items()
                if k.startswith("pipeline.")
            }
            if fuse:
                out["fuse"] = {
                    k.split("fuse.", 1)[1]: v - counters0.get(k, 0)
                    for k, v in snap.items()
                    if k.startswith("fuse.")
                }
                hist = _obs.snapshot()["histograms"].get("fuse.fused_width")
                if hist:
                    out["fuse"]["width_p50"] = hist["p50"]
                    out["fuse"]["width_max"] = hist["max"]
            if merkle_async:
                out["merkle_lane"] = {
                    k.split("merkle.lane.", 1)[1]: v - counters0.get(k, 0)
                    for k, v in snap.items()
                    if k.startswith("merkle.lane.")
                }
                hist = _obs.snapshot()["histograms"].get(
                    "merkle.lane.lag_batches"
                )
                if hist:
                    out["merkle_lane"]["lag_p50"] = hist["p50"]
                    out["merkle_lane"]["lag_max"] = hist["max"]
        return out
    finally:
        replica.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_shard_sweep(shard_counts, n_accounts=256, count=1024, lanes=2048,
                    n_batches=12):
    """Sharded serving sweep (docs/sharding.md; ROADMAP open item 1): ONE
    pinned mixed workload — uniform plain transfers (high cross-shard
    fraction by construction: owners are hash bits) interleaved with
    table two-phase pending->post batches — committed through
    TpuStateMachine at each shard count on virtual CPU devices.

    Digests MUST be byte-identical across entries: the sweep doubles as
    the sharded-vs-single parity proof (tools/sharded_smoke.py asserts it
    in CI).  Per-entry: tx/s, per-shard tx/s, cross-shard fraction,
    sequential fallbacks.  Ratios within the sweep are what matter (every
    entry shares the shape and the 2-core host)."""
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import LedgerConfig
    from tigerbeetle_tpu.machine import TpuStateMachine

    import jax as _jax

    cfg = LedgerConfig(
        accounts_capacity_log2=12,
        transfers_capacity_log2=max(
            14, (4 * (n_batches + 2) * count - 1).bit_length()
        ),
        posted_capacity_log2=max(12, (2 * n_batches * count - 1).bit_length()),
    )

    # Deterministic batch stream, built once and replayed per entry.
    def build_batches():
        batches = []
        next_pend = []
        for b in range(n_batches):
            kind = b % 4
            if kind == 3 and next_pend:
                ids, base = next_pend
                batch = np.zeros(count, dtype=types.TRANSFER_DTYPE)
                batch["id_lo"] = base + (1 << 22) + np.arange(count, dtype=np.uint64)
                batch["pending_id_lo"] = ids
                batch["ledger"] = 1
                batch["code"] = 10
                batch["flags"] = np.uint16(
                    types.TransferFlags.POST_PENDING_TRANSFER
                )
                next_pend = []
            elif kind == 2:
                base = (1 << 41) + b * count
                batch = gen_batch_np(b, count, count, n_accounts, base)
                batch["flags"] = np.uint16(types.TransferFlags.PENDING)
                next_pend = (batch["id_lo"].copy(), base)
            else:
                batch = gen_batch_np(
                    b, count, count, n_accounts, (1 << 40) + b * count
                )
            batches.append(batch)
        return batches

    batches = build_batches()
    accounts = np.zeros(n_accounts, dtype=types.ACCOUNT_DTYPE)
    accounts["id_lo"] = 1 + np.arange(n_accounts, dtype=np.uint64)
    accounts["ledger"] = 1
    accounts["code"] = 10

    out = {}
    digests = {}
    for s in shard_counts:
        if s >= 2 and len(_jax.devices()) < s:
            print(f"# shard sweep: {s} shards need {s} devices, have "
                  f"{len(_jax.devices())}; skipped", file=sys.stderr)
            out[str(s)] = None
            continue
        m = TpuStateMachine(cfg, batch_lanes=lanes,
                            shards=0 if s <= 1 else s)
        assert m.create_accounts(accounts, wall_clock_ns=1) == []
        # Untimed warm batch per kernel shape (plain + pending + post):
        # the single-device full kernel jit-specializes on has_postvoid,
        # so the post batch MUST be warmed too or the s=1 entry would pay
        # that compile inside the timed loop (biasing the ratios).  Warm
        # ids (and the posts' pending refs) shift into a disjoint band;
        # the warm posts resolve the warm pendings, deterministically and
        # identically for every sweep entry.
        for warm in build_batches()[:4]:
            w = warm.copy()
            w["id_lo"] = w["id_lo"] + (1 << 50)
            pend_ref = w["pending_id_lo"] != 0
            w["pending_id_lo"][pend_ref] += 1 << 50
            m.create_transfers(w)
        total = 0
        fails = 0
        t0 = time.time()
        for batch in batches:
            res = m.create_transfers(batch)
            total += len(batch)
            fails += len(res)
        elapsed = max(time.time() - t0, 1e-9)
        digests[str(s)] = f"{m.digest():#x}"
        tx_s = total / elapsed
        cross = (
            m.shard_lanes_cross / m.shard_lanes_total
            if m.shard_lanes_total else 0.0
        )
        out[str(s)] = {
            "tx_s": round(tx_s, 1),
            "per_shard_tx_s": round(tx_s / max(s, 1), 1),
            "cross_shard_frac": round(cross, 4),
            "seq_fallbacks": m.shard_seq_fallbacks,
            "result_failures": fails,
            "digest": digests[str(s)],
            "transfers": total,
        }
    live = {k: v for k, v in out.items() if v}
    if len({v["digest"] for v in live.values()}) > 1:
        raise AssertionError(f"shard sweep digest divergence: {digests}")
    return {
        "sweep": out,
        "digest_identical": len(live) > 1,
        "workload": "plain+two_phase mixed, uniform accounts "
                    f"({n_batches}x{count} lanes)",
        "unit_note": "virtual CPU devices; ratios within the sweep only",
    }


def run_offered_load(multiple, seed=7, requests=8):
    """Overload degradation point (docs/fault_domains.md overload domain):
    drive the REAL consensus cluster (sim/cluster.py on virtual time — one
    tick = 10 ms) with a client cohort offering ``multiple``x the pipeline
    capacity, overload control ON, and measure what the control surface
    actually does: the shed rate (fraction of request attempts answered
    with a retryable busy instead of admitted) and the admitted-request
    p99 (send -> reply, including busy-backoff waits).  Deterministic by
    seed, so the sweep measures the degradation curve rather than host
    noise; latencies are virtual-tick-derived (10 ms/tick), labeled as
    such."""
    import dataclasses as _dc
    import shutil
    import tempfile

    from tigerbeetle_tpu.config import TEST_MIN
    from tigerbeetle_tpu.obs.metrics import registry as _obs
    from tigerbeetle_tpu.sim.cluster import SimCluster
    from tigerbeetle_tpu.sim.network import PacketSimulator

    pc = TEST_MIN.pipeline_prepare_queue_max
    flood_n = max(1, int(multiple * pc))
    config = _dc.replace(TEST_MIN, clients_max=max(96, flood_n + 16))
    tmp = tempfile.mkdtemp(prefix="tb_bench_overload_")
    try:
        cluster = SimCluster(
            tmp, n_replicas=3, n_clients=1, seed=seed,
            requests_per_client=2, config=config,
            net=PacketSimulator(seed=seed + 1, delay_mean=1, delay_max=6),
            overload={
                "queue_cap": 4 * pc, "dispatch_budget": pc,
                "priority": True, "signal": True,
            },
        )
        # POLITE cohort (aggressive=False): the degradation curve models
        # clients that honor busy backpressure — the closed-loop behavior
        # a serving stack actually exhibits at 2-4x offered load.
        ids = cluster.add_flood_clients(
            flood_n, seed, n_requests=requests, retry_ticks=40,
            start_tick=50, aggressive=False,
        )
        ok = cluster.run_until(
            lambda: cluster.clients_done(), max_ticks=120_000,
        )
        stats = cluster.overload_stats()
        lat = sorted(
            t for cid in ids for t in cluster.clients[cid].latencies
        )
        # Round trips include session registers; `completed` counts the
        # workload requests proper (the liveness denominator).
        completed = sum(
            cluster.clients[cid].requests_done for cid in ids
        )
        # Busy replies seen by the FLOOD cohort only — `lat` below is
        # cohort-scoped too, so shed_rate compares like with like (the
        # base cluster client's busy replies would inflate it).
        busy = sum(cluster.clients[cid].busy_seen for cid in ids)
        out = {
            "offered_x": multiple,
            "clients": flood_n,
            "completed": completed,
            "round_trips": len(lat),
            "drained": bool(ok),
            "busy_replies": busy,
            "shed_rate": round(busy / max(busy + len(lat), 1), 4),
            "admitted_p50_ms": (
                lat[len(lat) // 2] * 10 if lat else None
            ),
            "admitted_p99_ms": (
                # Nearest-rank p99 (ceil(0.99n)-1): int(0.99n) selects the
                # MAX sample whenever 0.99n is integral (e.g. n=100).
                lat[max(0, -(-len(lat) * 99 // 100) - 1)] * 10
                if lat else None
            ),
            "queue_depth_peak": stats["depth_peak"],
            "shed_by_class": stats["shed_by_class"],
            "unit_note": "virtual ticks x 10 ms (deterministic sim)",
        }
        if _obs.enabled:
            _obs.counter("overload.bench.busy_replies").inc(busy)
            _obs.counter("overload.bench.shed").inc(stats["shed"])
            _obs.gauge("overload.bench.queue_depth_peak").set(
                stats["depth_peak"]
            )
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_auth_bench(seed=7, n_clients=4, requests=8, mac_iters=20_000):
    """Wire-auth robustness tax (docs/observability.md auth.* rows): the
    SAME small consensus workload with per-replica MAC keys off and on
    (strict), plus a pure keyed-MAC microbench over a pinned 256-byte
    header.  MACs are computed in zero VIRTUAL time, so the two arms must
    agree on every tick-derived number — the identity check below — and
    the tax shows up only in host wall time (min of two runs per arm, to
    shave scheduler noise on a shared host)."""
    import dataclasses as _dc
    import shutil
    import tempfile
    import time as _time

    from tigerbeetle_tpu.config import TEST_MIN
    from tigerbeetle_tpu.sim.cluster import SimCluster
    from tigerbeetle_tpu.sim.network import PacketSimulator
    from tigerbeetle_tpu.vsr import wire
    from tigerbeetle_tpu.vsr.auth import Keychain

    config = _dc.replace(TEST_MIN, clients_max=max(96, n_clients + 16))

    def one_arm(auth, reps=3):
        best = None
        fingerprint = None
        for _rep in range(reps):
            tmp = tempfile.mkdtemp(prefix="tb_bench_auth_")
            try:
                t0 = _time.perf_counter()
                cluster = SimCluster(
                    tmp, n_replicas=3, n_clients=n_clients, seed=seed,
                    requests_per_client=requests, config=config,
                    net=PacketSimulator(
                        seed=seed + 1, delay_mean=1, delay_max=6,
                    ),
                    auth=auth,
                )
                drained = cluster.run_until(
                    lambda: cluster.clients_done(), max_ticks=60_000,
                )
                wall = _time.perf_counter() - t0
                fingerprint = (
                    bool(drained),
                    tuple(sorted(
                        (cid, tuple(c.latencies))
                        for cid, c in cluster.clients.items()
                    )),
                )
                best = wall if best is None else min(best, wall)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        return best, fingerprint

    one_arm(None, reps=1)  # warmup: imports + first-construction costs
    wall_off, fp_off = one_arm(None)
    wall_on, fp_on = one_arm({"strict": True, "seed": seed})

    # Microbench: stamp + verify of one source-authenticated heartbeat
    # frame — the per-frame commit-path cost, free of cluster scheduling.
    keychain = Keychain(cluster=1, seed=seed)
    h = wire.new_header(wire.Command.commit, cluster=1, view=1, commit=3)
    h["replica"] = 1
    frame = wire.encode(h, b"")
    t0 = _time.perf_counter()
    for _ in range(mac_iters):
        stamped = keychain.stamp(frame)
    stamp_s = _time.perf_counter() - t0
    sh = wire.decode_header(stamped)[0]
    t0 = _time.perf_counter()
    for _ in range(mac_iters):
        ok = keychain.verify(sh)
    verify_s = _time.perf_counter() - t0
    assert ok, "auth microbench: stamped frame must verify"

    return {
        "wall_off_s": round(wall_off, 4),
        "wall_on_s": round(wall_on, 4),
        "overhead_vs_off": round(wall_on / wall_off, 3) if wall_off else None,
        # Tick-derived identity: auth must not perturb virtual time.
        "identity_vs_off": fp_on == fp_off,
        "mac_stamp_us": round(stamp_s / mac_iters * 1e6, 3),
        "mac_verify_us": round(verify_s / mac_iters * 1e6, 3),
        "unit_note": "wall seconds (host): virtual-time sim, MAC cost "
                     "is host-side only",
    }


def run_attribution_bench(depth=1, n_groups=12, n_clients=4, count=2048):
    """Commit latency attribution (docs/tracing.md): the serving-path
    pipeline bench re-run under ``txtrace.attribution_scope()`` so every
    commit stage (wal_fsync, dispatch_wait, device_execute, readback, ...)
    bills its duration to the stage ledger, surfaced as
    ``payload.attribution``.

    Default depth 1: the serial path, where stages do not overlap and the
    attributed per-batch sum must reconcile with the measured per-batch
    wall (tests/test_txtrace.py pins the tolerance).  At depth > 1 the
    ledger instead SHOWS the overlap: attributed > measured is the
    pipeline's fsync/compute win, itemised."""
    from tigerbeetle_tpu.obs.txtrace import txtrace

    with txtrace.attribution_scope():
        entry = run_pipeline_bench(
            depth, n_groups=n_groups, n_clients=n_clients, count=count,
            attribution=True,
        )
    out = dict(entry["attribution"])
    out["depth"] = depth
    out["tx_s"] = entry["tx_s"]
    return out


def run_trace_overhead_bench(depth=None, n_groups=12, n_clients=4,
                             count=2048, reps=3):
    """Causal-tracing tax (docs/tracing.md): the SAME serving-path
    pipeline workload with tracing OFF and ON — sampling forced to 1/1
    (every request stamped) with the host tracer's Chrome buffer armed,
    the worst case — best-of-N wall per arm.

    Identity: replies_sha hashes only the deterministic reply content
    (op/commit/timestamp/request + body) and the ledger digest is
    device state, so trace ids riding the header's carved bytes must not
    perturb either — ``identity_vs_off`` asserts the off/on arms agree."""
    from tigerbeetle_tpu.machine import pipeline_depth_default
    from tigerbeetle_tpu.obs.txtrace import txtrace
    from tigerbeetle_tpu.utils.tracer import tracer

    if depth is None:
        depth = pipeline_depth_default()

    def one_arm(traced):
        best = None
        fingerprint = None
        events = 0
        for _ in range(reps):
            if traced:
                prev_backend = tracer.backend
                tracer.enable("json")
                t0 = time.monotonic()
                with txtrace.sampling_scope(every=1):
                    entry = run_pipeline_bench(
                        depth, n_groups=n_groups, n_clients=n_clients,
                        count=count,
                    )
                wall = time.monotonic() - t0
                events = len(tracer.drain())  # flow events, then discard
                tracer.backend = prev_backend
            else:
                t0 = time.monotonic()
                entry = run_pipeline_bench(
                    depth, n_groups=n_groups, n_clients=n_clients,
                    count=count,
                )
                wall = time.monotonic() - t0
            fingerprint = (entry["replies_sha"], entry["digest"])
            best = wall if best is None else min(best, wall)
        return best, fingerprint, events

    one_arm(False)  # warmup arm: first-construction + compile costs
    wall_off, fp_off, _ = one_arm(False)
    wall_on, fp_on, flow_events = one_arm(True)

    return {
        "depth": depth,
        "wall_off_s": round(wall_off, 4),
        "wall_on_s": round(wall_on, 4),
        "overhead_vs_off": round(wall_on / wall_off, 3) if wall_off else None,
        "identity_vs_off": fp_on == fp_off,
        "replies_sha": fp_off[0],
        "flow_events": flow_events,
        "unit_note": "wall seconds (host); ON arm = sample 1/1 + Chrome "
                     "buffer armed (worst case)",
    }


def run_openloop_workload(
    rate, seed=7, n_clients=64, hot_accounts=256, arrival="poisson",
    max_ticks=120_000,
):
    """Open-loop production-shaped traffic (sim/openloop.py: Zipfian hot
    accounts, seeded arrival process, two-phase + query mix over many
    client ids) against the REAL consensus cluster with overload control
    on — the `--workload zipf` sweep point.  Unlike the closed-loop
    offered-load flood, arrivals do not wait for replies, so the reported
    arrival→reply latency includes open-loop queueing delay: the number a
    serving stack actually exhibits under production traffic.
    Deterministic by seed; latencies are virtual-tick-derived, labeled."""
    import dataclasses as _dc
    import shutil
    import tempfile

    from tigerbeetle_tpu.config import TEST_MIN
    from tigerbeetle_tpu.sim.cluster import SimCluster
    from tigerbeetle_tpu.sim.network import PacketSimulator
    from tigerbeetle_tpu.sim.openloop import OpenLoopGen, zipf_skew

    pc = TEST_MIN.pipeline_prepare_queue_max
    config = _dc.replace(TEST_MIN, clients_max=max(96, n_clients + 16))
    tmp = tempfile.mkdtemp(prefix="tb_bench_openloop_")
    try:
        cluster = SimCluster(
            tmp, n_replicas=3, n_clients=1, seed=seed,
            requests_per_client=2, config=config,
            net=PacketSimulator(seed=seed + 1, delay_mean=1, delay_max=6),
            overload={
                "queue_cap": 4 * pc, "dispatch_budget": pc,
                "priority": True, "signal": True,
            },
        )
        gen = OpenLoopGen(
            seed ^ 0x09E7, n_clients=n_clients, hot_accounts=hot_accounts,
            arrival=arrival, rate=rate, start_tick=50, horizon=3_000,
            batch=4,
        )
        ids = gen.attach(cluster)
        ok = cluster.run_until(
            lambda: cluster.clients_done(), max_ticks=max_ticks,
        )
        stats = cluster.overload_stats()
        qlat = sorted(
            t for cid in ids for t in cluster.clients[cid].queue_latencies
        )
        completed = sum(cluster.clients[cid].requests_done for cid in ids)
        busy = sum(cluster.clients[cid].busy_seen for cid in ids)
        return {
            "workload": "zipf_openloop",
            "arrival": arrival,
            "rate_per_tick": rate,
            "clients": len(ids),
            "offered_requests": gen.total_requests,
            "completed": completed,
            "drained": bool(ok),
            "busy_replies": busy,
            "shed_rate": round(busy / max(busy + completed, 1), 4),
            "zipf_top10pct_touch_share": round(zipf_skew(gen), 4),
            "arrival_to_reply_p50_ms": (
                qlat[len(qlat) // 2] * 10 if qlat else None
            ),
            "arrival_to_reply_p99_ms": (
                qlat[max(0, -(-len(qlat) * 99 // 100) - 1)] * 10
                if qlat else None
            ),
            "queue_depth_peak": stats["depth_peak"],
            "shed_by_class": stats["shed_by_class"],
            "unit_note": "virtual ticks x 10 ms (deterministic sim)",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_reconfig_bench(seed=7, rate=0.3, n_clients=24, hot_accounts=64):
    """Live-reshape tax (docs/reconfiguration.md): the SAME seeded
    open-loop workload on a 2-shard consensus cluster, served twice —
    once steady, once with an online 2 -> 4 shard split armed mid-flood
    on every replica (one Merkle-verified chunk pumped per tick while
    serving continues).  Per-arm arrival->reply p50/p99 + shed rate, the
    split arm's migrated bytes vs a full state transfer, and identity:
    the final ledger digest AND the per-op digest stream must be
    byte-identical across arms — the split is invisible to clients and
    to state, or the number does not count.  Lands as payload.reconfig;
    needs >= 4 virtual devices (--reconfig implies --force-cpu)."""
    import dataclasses as _dc
    import hashlib
    import shutil
    import tempfile

    import jax as _jax

    from tigerbeetle_tpu.config import TEST_MIN
    from tigerbeetle_tpu.sim.cluster import SimCluster
    from tigerbeetle_tpu.sim.network import PacketSimulator
    from tigerbeetle_tpu.sim.openloop import OpenLoopGen

    if len(_jax.devices()) < 4:
        raise RuntimeError(
            "payload.reconfig needs >= 4 devices for the 2 -> 4 split"
        )

    RESHARD_AT = 200
    pc = TEST_MIN.pipeline_prepare_queue_max
    config = _dc.replace(TEST_MIN, clients_max=max(96, n_clients + 16))

    def one_arm(split):
        tmp = tempfile.mkdtemp(prefix="tb_bench_reconfig_")
        prev_shards = os.environ.get("TB_SHARDS")
        os.environ["TB_SHARDS"] = "2"
        try:
            cluster = SimCluster(
                tmp, n_replicas=3, n_clients=1, seed=seed,
                requests_per_client=2, config=config,
                net=PacketSimulator(seed=seed + 1, delay_mean=1,
                                    delay_max=6),
                overload={
                    "queue_cap": 4 * pc, "dispatch_budget": pc,
                    "priority": True, "signal": True,
                },
            )
            gen = OpenLoopGen(
                seed ^ 0x2ECF, n_clients=n_clients,
                hot_accounts=hot_accounts, arrival="poisson", rate=rate,
                start_tick=50, horizon=1_500, batch=4,
            )
            ids = gen.attach(cluster)
            dead = set()
            for _ in range(60_000):
                cluster.step()
                if split and cluster.t >= RESHARD_AT:
                    for i, (r, alive) in enumerate(
                        zip(cluster.replicas, cluster.alive)
                    ):
                        if not alive or i in dead:
                            continue
                        m = r.machine
                        if m.shards == 2 and not m.reshard_active:
                            if not m.reshard_begin(
                                4, verify=True, chunk_rows=16
                            ):
                                dead.add(i)
                        if m.reshard_active and (
                            m.reshard_step(1) == "abandoned"
                        ):
                            dead.add(i)
                if cluster.clients_done():
                    break
            drained = cluster.clients_done()
            # Let the backups drain their commit backlog (heartbeats),
            # and pump any split that is still mid-flight to completion —
            # identity is judged on settled state.
            guard = 0
            while split and any(
                r.machine.reshard_active for r, a in
                zip(cluster.replicas, cluster.alive) if a
            ):
                cluster.step()
                for i, (r, alive) in enumerate(
                    zip(cluster.replicas, cluster.alive)
                ):
                    if alive and i not in dead and r.machine.reshard_active:
                        if r.machine.reshard_step(1) == "abandoned":
                            dead.add(i)
                guard += 1
                assert guard < 10_000, "split did not finish"
            cluster.run_until(cluster.converged, max_ticks=10_000)
            cluster.check_converged()
            cluster.check_conservation()
            stats = cluster.overload_stats()
            qlat = sorted(
                t for cid in ids
                for t in cluster.clients[cid].queue_latencies
            )
            completed = sum(
                cluster.clients[cid].requests_done for cid in ids
            )
            busy = sum(cluster.clients[cid].busy_seen for cid in ids)
            reshard: dict = {}
            for r, alive in zip(cluster.replicas, cluster.alive):
                if not alive:
                    continue
                for k, v in r.machine.reshard_stats.items():
                    reshard[k] = reshard.get(k, 0) + v
            # Per-op digest stream (layout-invariant wrap-sum): identical
            # across arms iff every committed op left identical CONTENT —
            # a stronger identity than the final digest alone.
            log = cluster.hash_logs[0]
            sha = hashlib.sha256()
            for op in sorted(log.digests):
                sha.update(f"{op}:{log.digests[op]:032x};".encode())
            return {
                "drained": bool(drained),
                "shards_final": sorted(
                    r.machine.shards for r, a in
                    zip(cluster.replicas, cluster.alive) if a
                ),
                "completed": completed,
                "busy_replies": busy,
                "shed_rate": round(busy / max(busy + completed, 1), 4),
                "arrival_to_reply_p50_ms": (
                    qlat[len(qlat) // 2] * 10 if qlat else None
                ),
                "arrival_to_reply_p99_ms": (
                    qlat[max(0, -(-len(qlat) * 99 // 100) - 1)] * 10
                    if qlat else None
                ),
                "queue_depth_peak": stats["depth_peak"],
                "reshard": reshard,
                "digest": f"{cluster.replicas[0].machine.digest():#x}",
                "ops_sha": sha.hexdigest(),
            }
        finally:
            if prev_shards is None:
                os.environ.pop("TB_SHARDS", None)
            else:
                os.environ["TB_SHARDS"] = prev_shards
            shutil.rmtree(tmp, ignore_errors=True)

    steady = one_arm(split=False)
    live = one_arm(split=True)
    r = live["reshard"]
    return {
        "workload": "zipf_openloop under TB_SHARDS=2",
        "rate_per_tick": rate,
        "clients": n_clients,
        "steady": steady,
        "split": live,
        "split_tax": {
            "p99_ratio": (
                round(live["arrival_to_reply_p99_ms"]
                      / steady["arrival_to_reply_p99_ms"], 2)
                if steady["arrival_to_reply_p99_ms"]
                and live["arrival_to_reply_p99_ms"] else None
            ),
            "shed_rate_delta": round(
                live["shed_rate"] - steady["shed_rate"], 4
            ),
        },
        "migration": {
            "bytes_migrated": r.get("bytes_migrated", 0),
            "bytes_full_transfer": r.get("bytes_full", 0),
            "delta_fraction": (
                round(r.get("bytes_migrated", 0)
                      / max(r.get("bytes_full", 1), 1), 4)
            ),
            "chunks": r.get("chunks", 0),
            "splits_completed": r.get("splits_completed", 0),
        },
        "identity_vs_steady": (
            live["digest"] == steady["digest"]
            and live["ops_sha"] == steady["ops_sha"]
        ),
        "unit_note": "virtual ticks x 10 ms (deterministic sim)",
    }


def run_workload_sweep(seed=7):
    """`--workload zipf`: the open-loop Zipfian generator at two arrival
    rates, side by side with the UNIFORM closed-loop flood at a comparable
    offered multiple — so the artifact records what realistic traffic does
    to the shed/latency surface vs the synthetic flood that tuned it."""
    sweep = {
        "zipf_1x": run_openloop_workload(1.0, seed=seed),
        "zipf_2x": run_openloop_workload(2.0, seed=seed),
        "zipf_burst": run_openloop_workload(
            2.0, seed=seed, arrival="burst"
        ),
        "uniform_2x": run_offered_load(2, seed=seed, requests=6),
    }
    z, u = sweep["zipf_2x"], sweep["uniform_2x"]
    sweep["comparison"] = {
        "zipf_vs_uniform_shed_rate": [z["shed_rate"], u["shed_rate"]],
        "zipf_p99_arrival_to_reply_ms": z["arrival_to_reply_p99_ms"],
        "uniform_p99_admitted_ms": u["admitted_p99_ms"],
        "note": (
            "zipf p99 counts open-loop queueing from ARRIVAL; uniform "
            "p99 counts from first send of a closed-loop request"
        ),
    }
    return sweep


def _e2e_val(e2e, key):
    return round(e2e[key]["tx_s"], 1) if e2e.get(key) else None


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--e2e-client":
        _e2e_client_main(sys.argv[2:])
        return
    p = argparse.ArgumentParser()
    p.add_argument("--transfers", type=int, default=10_000_000)
    p.add_argument("--accounts", type=int, default=10_000)
    p.add_argument("--count", type=int, default=8190)  # batch_max (SURVEY §2.1)
    p.add_argument("--lanes", type=int, default=8192)
    p.add_argument("--skip-parity", action="store_true")
    p.add_argument("--force-cpu", action="store_true",
                   help="skip accelerator init; run on host CPU")
    p.add_argument("--two-phase", action="store_true",
                   help="BASELINE config 3: each batch is half pending "
                        "creates + half posts of those pendings (in-batch "
                        "two-phase resolution)")
    p.add_argument("--limits", action="store_true",
                   help="every account carries debits_must_not_exceed_credits "
                        "(pre-funded so nothing rejects): the round-3 "
                        "vectorized balance-limit path, same workload shape "
                        "as the flagship for a direct ratio")
    p.add_argument("--skip-e2e", action="store_true",
                   help="omit the end-to-end TCP+WAL companion measurement")
    p.add_argument("--e2e-device-sweep", action="store_true",
                   help="sweep the device executor's group size "
                        "(n_clients 1/4/16/32)")
    p.add_argument("--skip-kernel-profile", action="store_true",
                   help="omit the per-variant kernel cost + Jacobi-pass "
                        "instrumentation")
    p.add_argument("--pipeline-depth", default=None, metavar="D1,D2,...",
                   help="sweep the serving-path commit-pipeline depth "
                        "(e.g. 1,2,4): per-depth tx/s and p50/p99 land in "
                        "the reps block, with reply/ledger digests so CI "
                        "can assert depth-identity (tools/pipeline_smoke)")
    p.add_argument("--merkle-scrub", action="store_true",
                   help="with --scrub-interval: also sweep the same "
                        "cadences under the Merkle commitment tree "
                        "(TB_MERKLE mode, mirror off) -> "
                        "payload.merkle.overhead_vs_off next to the "
                        "mirror's number (docs/commitments.md)")
    p.add_argument("--scrub-interval", default=None, metavar="N1,N2,...",
                   help="sweep the device fault domain's scrub cadence "
                        "(e.g. 0,8,1; 0 = off): per-interval serving tx/s "
                        "on a reduced fixed-shape workload so the scrub "
                        "overhead — mirror maintenance + digest folds — is "
                        "measured, not guessed (docs/fault_domains.md)")
    p.add_argument("--shards", default=None, metavar="N1,N2,...",
                   help="sweep the sharded serving mode (TB_SHARDS; "
                        "docs/sharding.md) over shard counts (e.g. 1,2,8) "
                        "on xla_force_host_platform_device_count virtual "
                        "CPU devices: per-entry tx/s, per-shard tx/s, "
                        "cross-shard fraction, and the ledger digest land "
                        "in the primary JSON line — digests must be "
                        "byte-identical across entries (the sweep doubles "
                        "as the sharded-vs-single parity proof).  Implies "
                        "--force-cpu (virtual devices are a CPU-backend "
                        "construct)")
    p.add_argument("--async-shards", type=int, default=None, metavar="N",
                   help="with --pipeline-depth: additionally run the SAME "
                        "depth sweep under TB_SHARDS=N on virtual CPU "
                        "devices — the async sharded commit engine "
                        "(grouped/deferred stacking over the mesh, "
                        "docs/sharding.md composition).  Each depth gets "
                        "the 15 ms emulated-readback-RTT twin; replies "
                        "and digests must match the unsharded sweep "
                        "bit for bit.  Implies --force-cpu")
    p.add_argument("--fuse-batches", action="store_true",
                   help="sweep the cross-batch conflict-fusion knob "
                        "(TB_FUSE; docs/commit_pipeline.md): off/fuse arms "
                        "on a disjoint-accounts mix, per-arm per_batch_us "
                        "+ fused-run width histogram; replies_sha/digest "
                        "must match the off arm bit for bit.  Combine with "
                        "--merkle-async for the 4-arm matrix -> "
                        "payload.fusion (BENCH_r12)")
    p.add_argument("--merkle-async", action="store_true",
                   help="sweep the deferred commitment lane "
                        "(TB_MERKLE_ASYNC; docs/commitments.md): the same "
                        "mix with the Merkle path refresh trailing the "
                        "dispatch closure, commitment-lane lag recorded; "
                        "roots/replies/digest must match the sync arm")
    p.add_argument("--workload", default=None, choices=["zipf"],
                   help="run the open-loop production-workload sweep "
                        "(sim/openloop.py: Zipfian hot accounts, seeded "
                        "arrival process, two-phase + query mix) at two "
                        "arrival rates plus a burst point, side by side "
                        "with the uniform closed-loop flood — shed rate "
                        "and arrival->reply latency per point")
    p.add_argument("--reconfig", action="store_true",
                   help="measure the live-reshape tax: the same seeded "
                        "open-loop workload on a 2-shard cluster served "
                        "steady and with an online 2->4 split pumped "
                        "mid-flood (docs/reconfiguration.md) — per-arm "
                        "p50/p99 + shed rate, migrated-bytes vs a full "
                        "transfer, digest/per-op identity across arms -> "
                        "payload.reconfig.  Implies --force-cpu (virtual "
                        "devices)")
    p.add_argument("--offered-load", default=None, metavar="X1,X2,...",
                   help="sweep offered load as multiples of pipeline "
                        "capacity (e.g. 1,2,4) against the consensus "
                        "cluster with overload control ON: per-point shed "
                        "rate + admitted-request p50/p99 land in the "
                        "primary JSON line so the degradation curve is "
                        "measured, not guessed (docs/fault_domains.md)")
    p.add_argument("--auth-overhead", action="store_true",
                   help="measure the wire-auth robustness tax: the same "
                        "small consensus workload with per-replica MAC "
                        "keys off and on (strict), plus a keyed-MAC "
                        "stamp/verify microbench — lands as payload.auth "
                        "with overhead_vs_off (docs/fault_domains.md "
                        "Byzantine domain)")
    p.add_argument("--trace-overhead", action="store_true",
                   help="measure the causal-tracing tax: the serving-path "
                        "pipeline workload with tracing off and on "
                        "(sample 1/1 + Chrome buffer armed, worst case), "
                        "plus the per-commit-batch stage attribution "
                        "ledger at depth 1 — lands as payload.trace with "
                        "overhead_vs_off/identity_vs_off and "
                        "payload.attribution (docs/tracing.md)")
    p.add_argument("--metrics-json", default=None, metavar="PATH",
                   help="enable the obs metrics registry and dump the "
                        "snapshot (commit-latency percentiles, batch-size "
                        "histograms, jit compile counts) to PATH; key "
                        "series also ride the primary JSON line")
    args = p.parse_args()

    from tigerbeetle_tpu import jaxenv

    # Persistent compilation cache, shared with the server, tools and tests.
    jaxenv.enable_compile_cache()

    # Observability opt-ins, armed BEFORE backend init so warmup compiles
    # and the first dispatches are accounted too:
    # - --metrics-json / TB_METRICS_PATH: the obs registry + jit compile
    #   listener; the snapshot lands in the file and (key series) in the
    #   primary JSON line;
    # - TB_TRACE=json: jax.profiler device capture spanning the whole run,
    #   merged with the host tracer's spans into ONE Chrome trace at
    #   TB_TRACE_PATH (default tb_trace.json) — open it in Perfetto.
    metrics_path = args.metrics_json or os.environ.get("TB_METRICS_PATH")
    obs_registry = None
    if metrics_path:
        from tigerbeetle_tpu.obs.metrics import registry as obs_registry

        obs_registry.enable()
    # The compile listener is armed UNCONDITIONALLY (one int add per
    # compile): jaxenv.compile_count() feeds the timed-loop recompile
    # tripwire below whether or not the registry is recording.
    jaxenv.instrument_compiles()

    if args.shards or args.async_shards or args.reconfig:
        # The shard sweeps run on virtual CPU devices
        # (xla_force_host_platform_device_count): force enough of them
        # BEFORE any backend init — the flag parse is once-per-process.
        n_need = max(
            ([int(x) for x in args.shards.split(",") if x.strip()]
             if args.shards else [])
            + [args.async_shards or 1, 1]
            + ([4] if args.reconfig else [])  # the 2 -> 4 live split
        )
        if not args.force_cpu:
            print("# --shards/--async-shards/--reconfig imply "
                  "--force-cpu (virtual devices)", file=sys.stderr)
        jaxenv.force_cpu(max(1, n_need))
        platform = "cpu"
    elif args.force_cpu:
        jaxenv.force_cpu()
        platform = "cpu"
    else:
        # Strict: a measurement path that finds no chip fails before it
        # measures anything (--force-cpu is the explicit CPU rehearsal).
        platform = jaxenv.backend_info()[0]
        if platform != "tpu":
            sys.exit(f"bench.py: platform is {platform!r}, not 'tpu'; pass "
                     "--force-cpu for a CPU rehearsal")
    print(f"# platform={platform}", file=sys.stderr)

    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu import types, u128
    from tigerbeetle_tpu.ops import state_machine as sm
    from tigerbeetle_tpu.utils.tracer import tracer

    if args.count > args.lanes:
        sys.exit(f"--count {args.count} exceeds --lanes {args.lanes}")
    if args.two_phase:
        args.count -= args.count % 2  # pendings and posts pair up exactly

    n_accounts = args.accounts
    n_batches = (args.transfers + args.count - 1) // args.count
    if n_batches < 3:
        sys.exit("--transfers too small: need >= 3 batches (2 warmup + timed)")

    # Device capture starts after every argument-validation sys.exit (a
    # profiler session left running would poison the next capture).  The
    # atexit hook stops it on ANY exit path — the ~500 lines below contain
    # assertion exits that must not strand a live jax profiler session
    # (__exit__ is idempotent: active is cleared on first stop).
    capture = None
    prof_dir = None
    if tracer.enabled:
        import atexit as _atexit
        import tempfile as _tempfile

        from tigerbeetle_tpu.obs import profile as obs_profile

        prof_dir = _tempfile.mkdtemp(prefix="tb_bench_prof_")
        capture = obs_profile.DeviceCapture(prof_dir)

        def _cleanup_prof_dir() -> None:
            import shutil as _shutil

            _shutil.rmtree(prof_dir, ignore_errors=True)

        # LIFO: the trace must STOP before its directory is removed, so
        # the rmtree registers first and the capture stop second.
        _atexit.register(_cleanup_prof_dir)
        capture.__enter__()
        _atexit.register(capture.__exit__)

    # The entire setup + timed region below performs ZERO D2H: validation
    # failures accumulate on device and every host-side assert (and the
    # parity check) runs after timing.
    total_transfers = n_batches * args.count
    first_tid = 1 << 32

    tracer.start("bench.setup")
    acc_cap = 1 << _log2_ceil(n_accounts * 2)
    tr_cap = 1 << _log2_ceil(int(total_transfers * 2))
    # Two-phase: every post records a fulfillment keyed by the pending's
    # timestamp, so the posted table must hold total/2 entries.
    posted_cap = (tr_cap // 2) if args.two_phase else (1 << 10)
    ledger = sm.make_ledger(acc_cap, tr_cap, posted_cap)

    # --- accounts (failures accumulate on device; asserted after timing) ---
    acc_fails = jnp.uint64(0)
    created = 0
    # --limits: every workload account is limit-flagged; one extra unflagged
    # funder account (id n_accounts+1) pre-credits them so the steady-state
    # workload never rejects (the checks still run per event).
    n_create = n_accounts + 1 if args.limits else n_accounts
    while created < n_create:
        n = min(args.count, n_create - created)
        batch = np.zeros(args.lanes, dtype=types.ACCOUNT_DTYPE)
        batch["id_lo"][:n] = 1 + created + np.arange(n, dtype=np.uint64)
        batch["ledger"][:n] = 1
        batch["code"][:n] = 10
        if args.limits:
            lim = types.AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
            batch["flags"][:n] = np.where(
                batch["id_lo"][:n] <= n_accounts, np.uint16(lim), np.uint16(0)
            )
        soa = {k: jnp.asarray(v) for k, v in types.to_soa(batch).items()}
        ledger, codes = sm.create_accounts(
            ledger, soa, jnp.uint64(n), jnp.uint64(created + n)
        )
        lane = jnp.arange(args.lanes, dtype=jnp.uint64)
        acc_fails = acc_fails + jnp.sum(
            ((codes != 0) & (lane < jnp.uint64(n))).astype(jnp.uint64)
        )
        created += n

    if args.limits:
        # Funding pass through the full kernel: credit each limit account
        # 2^40 from the funder (credits never trigger a debit limit).
        from tigerbeetle_tpu.ops import transfer_full as tf

        funded = 0
        fund_fails = jnp.uint64(0)
        while funded < n_accounts:
            n = min(args.count, n_accounts - funded)
            batch = np.zeros(args.lanes, dtype=types.TRANSFER_DTYPE)
            batch["id_lo"][:n] = (1 << 31) + funded + np.arange(n, dtype=np.uint64)
            batch["debit_account_id_lo"][:n] = n_accounts + 1
            batch["credit_account_id_lo"][:n] = (
                1 + funded + np.arange(n, dtype=np.uint64)
            )
            batch["amount_lo"][:n] = 1 << 40
            batch["ledger"][:n] = 1
            batch["code"][:n] = 10
            soa = {k: jnp.asarray(v) for k, v in types.to_soa(batch).items()}
            ledger, codes, kflags = tf.create_transfers_full(
                ledger, soa, jnp.uint64(n),
                jnp.uint64(n_create + funded + n),
            )
            lane = jnp.arange(args.lanes, dtype=jnp.uint64)
            fund_fails = fund_fails + jnp.sum(
                ((codes != 0) & (lane < jnp.uint64(n))).astype(jnp.uint64)
            ) + jnp.where(kflags != 0, jnp.uint64(1 << 32), 0)
            funded += n
        # NO D2H assert here (see the zero-D2H note above): asserted after
        # timing, under its own name (kflags routes ride the 1<<32 band).

    # --- the timed transfer loop, entirely on device ---
    lanes, count = args.lanes, args.count
    ts0 = (2 * n_accounts + 1) if args.limits else n_accounts

    def mix_workload(gid):
        """Shared (debit, credit, amount) derivation — keep in sync with the
        host parity mirror gen_batch_np."""
        h1 = u128.mix64(gid, jnp.uint64(0x1234))
        h2 = u128.mix64(gid, jnp.uint64(0x9876))
        dr = h1 % jnp.uint64(n_accounts)
        off = jnp.uint64(1) + h2 % jnp.uint64(n_accounts - 1)
        cr = (dr + off) % jnp.uint64(n_accounts)
        amount = jnp.uint64(1) + ((h1 >> jnp.uint64(32)) & jnp.uint64(0xFFFF))
        return dr, cr, amount

    def gen_batch(b):
        lane = jnp.arange(lanes, dtype=jnp.uint64)
        gid = b.astype(jnp.uint64) * jnp.uint64(count) + lane
        dr, cr, amount = mix_workload(gid)
        active = lane < jnp.uint64(count)
        z64 = jnp.zeros((lanes,), jnp.uint64)
        z32 = jnp.zeros((lanes,), jnp.uint32)
        return {
            "id_lo": jnp.where(active, jnp.uint64(first_tid) + gid, 0),
            "id_hi": z64,
            "debit_account_id_lo": jnp.where(active, dr + 1, 0),
            "debit_account_id_hi": z64,
            "credit_account_id_lo": jnp.where(active, cr + 1, 0),
            "credit_account_id_hi": z64,
            "amount_lo": jnp.where(active, amount, 0),
            "amount_hi": z64,
            "pending_id_lo": z64,
            "pending_id_hi": z64,
            "user_data_128_lo": z64,
            "user_data_128_hi": z64,
            "user_data_64": z64,
            "user_data_32": z32,
            "timeout": z32,
            "ledger": jnp.where(active, jnp.uint32(1), z32),
            "code": jnp.where(active, jnp.uint32(10), z32),
            "flags": z32,
            "timestamp": z64,
        }

    def gen_two_phase(b):
        """Half pending creates, half posts of THOSE pendings: exercises the
        full kernel's in-batch two-phase resolution (ProtoBeetle's two-phase
        benchmark shape, docs/HISTORY.md:23-27)."""
        half = count // 2
        lane = jnp.arange(lanes, dtype=jnp.uint64)
        base = b.astype(jnp.uint64) * jnp.uint64(count)
        # Pendings occupy [0, half); posts [half, 2*half). With odd count the
        # final lane is padding (see the count -= count % 2 guard in main).
        is_post = lane >= jnp.uint64(half)
        gid = base + jnp.where(is_post, lane - jnp.uint64(half), lane)
        dr, cr, amount = mix_workload(gid)
        active = lane < jnp.uint64(2 * half)
        tid = jnp.uint64(first_tid) + base + lane
        pending_tid = jnp.uint64(first_tid) + base + (lane - jnp.uint64(half))
        z64 = jnp.zeros((lanes,), jnp.uint64)
        z32 = jnp.zeros((lanes,), jnp.uint32)
        from tigerbeetle_tpu.ops.state_machine import TF_PENDING, TF_POST

        return {
            "id_lo": jnp.where(active, tid, 0), "id_hi": z64,
            "debit_account_id_lo": jnp.where(active & ~is_post, dr + 1, 0),
            "debit_account_id_hi": z64,
            "credit_account_id_lo": jnp.where(active & ~is_post, cr + 1, 0),
            "credit_account_id_hi": z64,
            "amount_lo": jnp.where(active & ~is_post, amount, 0),
            "amount_hi": z64,
            "pending_id_lo": jnp.where(active & is_post, pending_tid, 0),
            "pending_id_hi": z64,
            "user_data_128_lo": z64, "user_data_128_hi": z64,
            "user_data_64": z64, "user_data_32": z32, "timeout": z32,
            "ledger": jnp.where(active & ~is_post, jnp.uint32(1), z32),
            "code": jnp.where(active & ~is_post, jnp.uint32(10), z32),
            "flags": jnp.where(
                active,
                jnp.where(is_post, jnp.uint32(TF_POST), jnp.uint32(TF_PENDING)),
                z32,
            ),
            "timestamp": z64,
        }

    if args.two_phase or args.limits:
        from tigerbeetle_tpu.ops import transfer_full as tf

        gen_full = gen_two_phase if args.two_phase else gen_batch

        def step(ledger, fails, b):
            batch = gen_full(b)
            ts = jnp.uint64(ts0) + (b.astype(jnp.uint64) + 1) * jnp.uint64(count)
            ledger, codes, kflags = tf.create_transfers_full_impl(
                ledger, batch, jnp.uint64(count), ts
            )
            lane = jnp.arange(lanes, dtype=jnp.uint64)
            fails = fails + jnp.sum(
                ((codes != 0) & (lane < jnp.uint64(count))).astype(jnp.uint64)
            ) + jnp.where(kflags != 0, jnp.uint64(1 << 32), 0)
            return ledger, fails
    else:
        def step(ledger, fails, b):
            batch = gen_batch(b)
            ts = jnp.uint64(ts0) + (b.astype(jnp.uint64) + 1) * jnp.uint64(count)
            ledger, codes = sm.create_transfers_impl(
                ledger, batch, jnp.uint64(count), ts
            )
            lane = jnp.arange(lanes, dtype=jnp.uint64)
            fails = fails + jnp.sum(
                ((codes != 0) & (lane < jnp.uint64(count))).astype(jnp.uint64)
            )
            return ledger, fails

    # K batches per dispatch inside one donated fori_loop: per-batch device
    # compute is ~7 us (the batch working set is VMEM-resident; HBM traffic is
    # only the probed table rows), so a per-batch dispatch's host round trip
    # would dominate. Donation keeps the
    # multi-GB tables in place across loop iterations (measured: no carry
    # copy at 2^25-slot tables).
    def multi(ledger, fails, b0, k):
        def body(i, carry):
            led, f = carry
            return step(led, f, b0 + i.astype(jnp.uint64))

        return jax.lax.fori_loop(0, k, body, (ledger, fails))

    multi_jit = jax.jit(
        multi, static_argnames=("k",), donate_argnames=("ledger", "fails")
    )

    disp = 20 if n_batches >= 40 else 2
    k = max(1, n_batches // disp)

    tracer.stop("bench.setup")
    # Warmup dispatch: compiles the loop and commits batches [0, k).
    # (fails stays on device -- see the no-D2H note above.)
    fails = jnp.uint64(0)
    t0 = time.time()
    with tracer.span("bench.warmup_dispatch", batches=k):
        ledger, fails = multi_jit(ledger, fails, jnp.uint64(0), k)
        jax.block_until_ready(fails)
    fails_w_dev = fails
    compile_s = time.time() - t0

    remaining = (n_batches - k) // k * k
    fails = jnp.uint64(0)
    dispatch_s = []  # per-dispatch wall times: the variance forensic record
    # Host-contention guard: snapshot AFTER the warmup compile (a
    # competing process appearing during the minutes-long compile must
    # not be missed), immediately before the clock starts; the fields
    # land in payload.harness.
    harness_guard = harness_contention_snapshot()
    # Recompile tripwire (the payload.harness twin of the warmup
    # compile_s field): the warmup dispatch above compiled the loop, so
    # any compile inside the timed region means the clock includes jit
    # work — per_batch_us is then not a hot-path number.  quiet=True:
    # the bench prints its own per_batch_us-specific warning below.
    from tigerbeetle_tpu import sanitize as _san

    with _san.compile_tripwire(
        "bench.timed_loop", raise_on_trip=False, quiet=True
    ) as timed_report:
        tracer.start("bench.timed_loop")
        t0 = time.time()
        for b0 in range(k, k + remaining, k):
            td = time.time()
            with tracer.span("bench.dispatch", b0=b0, batches=k):
                ledger, fails = multi_jit(ledger, fails, jnp.uint64(b0), k)
                # Completion sync per dispatch (no D2H copy): keeps the
                # async dispatch queue shallow; one sync per k batches is
                # noise.
                jax.block_until_ready(fails)
            dispatch_s.append(time.time() - td)
        jax.block_until_ready(fails)
        elapsed = time.time() - t0
        tracer.stop("bench.timed_loop", batches=remaining)
    # None (JSON null) when the listener could not install: a vacuous
    # zero must stay distinguishable from a VERIFIED compile-free loop in
    # the persisted BENCH artifacts rounds are triaged on.
    timed_compiles = timed_report.compiles if timed_report.armed else None
    if timed_compiles:
        print(
            f"# WARNING: {timed_compiles} XLA compile(s) inside the timed "
            "loop — warmup bled into the clock; per_batch_us from this "
            "run includes jit time and is NOT comparable across rounds "
            "(payload.harness.timed_compiles records it)",
            file=sys.stderr,
        )

    # Repetition split: the one timed run is segmented into 3 reps with
    # per-rep throughput + the occupancy each rep STARTED at (analytic —
    # inserts are deterministic, so no mid-run D2H is needed).  A healthy
    # chip shows <20% spread; an occupancy cliff shows
    # up as a monotonic or step pattern in rep_tx_s / dispatch percentiles.
    n_disp = len(dispatch_s)
    reps_report = None
    if n_disp >= 3:
        seg = n_disp // 3
        rep_tx_s = []
        for r in range(3):
            chunk = dispatch_s[r * seg: (r + 1) * seg]
            rep_tx_s.append(round(seg * k * count / sum(chunk), 1))
        ds = sorted(dispatch_s)
        occ0 = (k * count) / tr_cap  # after warmup
        reps_report = {
            "rep_tx_s": rep_tx_s,
            "rep_spread": round(
                (max(rep_tx_s) - min(rep_tx_s)) / max(rep_tx_s), 3
            ),
            "rep_start_occupancy": [
                round(occ0 + r * seg * k * count / tr_cap, 3)
                for r in range(3)
            ],
            "dispatch_ms": {
                "p50": round(ds[n_disp // 2] * 1e3, 2),
                "p90": round(ds[int(n_disp * 0.9)] * 1e3, 2),
                "max": round(ds[-1] * 1e3, 2),
            },
            "batches_per_dispatch": k,
            "batch_mix": (
                "two_phase_half_pending_half_post" if args.two_phase
                else "plain_limit_flagged" if args.limits else "plain"
            ),
        }

    # ---- post-timing validation (D2H transfers are safe from here on) ----
    total_fails = int(fails) + int(fails_w_dev)
    assert total_fails == 0, f"unexpected transfer failures: {total_fails}"
    assert int(acc_fails) == 0, f"account creation failures: {int(acc_fails)}"
    if args.limits:
        ff = int(fund_fails)
        assert ff == 0, (
            f"limits funding: {ff & ((1 << 32) - 1)} code failures, "
            f"{ff >> 32} kernel-routed batches"
        )
    assert not bool(np.asarray(ledger.transfers.probe_overflow).any())
    # Conservation oracle over the full run: total posted debits == credits
    # over the FULL u128 (one shared definition: utils/conservation.py).
    from tigerbeetle_tpu.utils.conservation import u128_field_total

    dpo = u128_field_total(ledger.accounts, "debits_posted")
    cpo = u128_field_total(ledger.accounts, "credits_posted")
    assert dpo == cpo and dpo > 0, f"conservation failure: {dpo} != {cpo}"
    dp = u128_field_total(ledger.accounts, "debits_pending")
    cp = u128_field_total(ledger.accounts, "credits_pending")
    assert dp == cp, f"pending conservation failure: {dp} != {cp}"
    if not args.skip_parity:
        with tracer.span("bench.parity_check"):
            run_parity_check()

    tx_s = remaining * count / elapsed

    # Companion END-TO-END numbers (TCP + WAL + reply): the reference's
    # headline is measured there, so report all serving paths — host-engine
    # executor, device-kernel executor, and the 3-replica cluster.  Never
    # let any of them kill the primary metric.
    e2e = {}
    if not args.skip_e2e and not args.two_phase and not args.limits:
        for key, runner in (
            ("e2e_tcp_wal", lambda: run_e2e(host_engine=True)),
            # 8 concurrent sessions: the device path's grouped commit
            # (machine.commit_group_fast) amortizes one dispatch + readback
            # across the whole commit group, so group size
            # IS the device executor's concurrency lever.
            ("e2e_device", lambda: run_e2e(host_engine=False, n_clients=8)),
            ("e2e_cluster", run_e2e_cluster),
        ):
            try:
                with tracer.span(f"bench.{key}"):
                    e2e[key] = runner()
            except Exception as err:  # noqa: BLE001 — the headline must print
                print(f"# {key} measurement failed: {err!r}", file=sys.stderr)
                e2e[key] = None
        if args.e2e_device_sweep:
            # Group-size sweep: concurrency bounds the
            # grouped-commit size, so n_clients IS the device executor's
            # dispatch-amortization lever.  (8 is the standard entry above.)
            for g in (1, 4, 16, 32):
                try:
                    e2e[f"e2e_device_g{g}"] = run_e2e(
                        host_engine=False, n_clients=g, seconds=3.0
                    )
                except Exception as err:  # noqa: BLE001
                    print(f"# e2e_device_g{g} failed: {err!r}",
                          file=sys.stderr)
                    e2e[f"e2e_device_g{g}"] = None

    kernel_profile = None
    if not args.skip_kernel_profile and not args.two_phase and not args.limits:
        try:
            with tracer.span("bench.kernel_profile"):
                kernel_profile = run_kernel_profile()
        except Exception as err:  # noqa: BLE001 — the headline must print
            print(f"# kernel profile failed: {err!r}", file=sys.stderr)

    # Commit-pipeline depth sweep (docs/commit_pipeline.md): per-depth
    # serving-path tx/s + latency percentiles, riding the reps block.
    pipeline_sweep = None
    if args.pipeline_depth:
        pipeline_sweep = {}
        for d in [int(x) for x in args.pipeline_depth.split(",") if x.strip()]:
            try:
                with tracer.span(f"bench.pipeline_depth_{d}"):
                    pipeline_sweep[str(d)] = run_pipeline_bench(max(1, d))
            except Exception as err:  # noqa: BLE001 — headline must print
                print(f"# pipeline depth {d} failed: {err!r}",
                      file=sys.stderr)
                pipeline_sweep[str(d)] = None
        reps_report = dict(reps_report or {})
        reps_report["pipeline_sweep"] = pipeline_sweep

    # Async sharded commit engine sweep (docs/sharding.md composition):
    # the SAME pipeline-depth sweep under TB_SHARDS=N — grouped/deferred
    # commit stacking over the mesh.
    # The replica clock is pinned inside run_pipeline_bench, so replies
    # and digests must equal the unsharded sweep's bit for bit (the
    # async smoke asserts it in CI).
    async_sharded_sweep = None
    if args.async_shards and args.pipeline_depth:
        # Dispatch/sync-bound shape (lanes 2048, count 1024): the kernel
        # pad is lanes-wide, so the default 8192-lane shape is
        # compute-bound on a CPU host.
        shape = dict(count=1024, lanes=2048)
        async_sharded_sweep = {"shape": shape}
        guard = harness_contention_snapshot()
        prev_shards = os.environ.get("TB_SHARDS")
        guard_after = None
        try:
            # Unsharded depth-1 baseline at the SAME shape: the identity
            # anchor every sharded entry must match bit for bit.
            os.environ["TB_SHARDS"] = "0"
            with tracer.span("bench.async_shards_baseline"):
                async_sharded_sweep["baseline_unsharded_d1"] = (
                    run_pipeline_bench(1, **shape)
                )
            os.environ["TB_SHARDS"] = str(args.async_shards)
            for d in [
                int(x) for x in args.pipeline_depth.split(",") if x.strip()
            ]:
                try:
                    with tracer.span(f"bench.async_shards_depth_{d}"):
                        async_sharded_sweep[str(d)] = run_pipeline_bench(
                            max(1, d), **shape
                        )
                except Exception as err:  # noqa: BLE001 — headline must print
                    print(f"# async shards depth {d} failed: {err!r}",
                          file=sys.stderr)
                    async_sharded_sweep[str(d)] = None
        finally:
            if prev_shards is None:
                os.environ.pop("TB_SHARDS", None)
            else:
                os.environ["TB_SHARDS"] = prev_shards
            # Bracket the whole sweep: contention appearing mid-sweep
            # (it spans many timed entries) must not hide behind a clean
            # entry snapshot.
            guard_after = harness_contention_snapshot()
        async_sharded_sweep["harness_guard"] = guard
        async_sharded_sweep["harness_guard_after"] = guard_after
        reps_report = dict(reps_report or {})
        reps_report["async_sharded_sweep"] = async_sharded_sweep

    # Scrub-cadence sweep (docs/fault_domains.md): the robustness tax of
    # the device fault domain, measured on a REDUCED fixed-shape serving
    # workload (the authoritative mirror applies every commit through the
    # scalar oracle, so full-size sweeps would take minutes; ratios within
    # the sweep are what matter and every entry shares the shape).
    scrub_sweep = None
    if args.scrub_interval:
        scrub_sweep = {}
        for interval in [
            int(x) for x in args.scrub_interval.split(",") if x.strip()
        ]:
            try:
                with tracer.span(f"bench.scrub_interval_{interval}"):
                    scrub_sweep[str(interval)] = run_pipeline_bench(
                        2, n_groups=12, n_clients=2, count=1024,
                        scrub_interval=max(0, interval),
                    )
            except Exception as err:  # noqa: BLE001 — headline must print
                print(f"# scrub interval {interval} failed: {err!r}",
                      file=sys.stderr)
                scrub_sweep[str(interval)] = None
        reps_report = dict(reps_report or {})
        reps_report["scrub_sweep"] = scrub_sweep

    # Merkle-commitment sweep (docs/commitments.md; ROADMAP item 3): the
    # SAME cadences with the incremental tree as the check substrate and
    # the host mirror off — payload.merkle.overhead_vs_off lands next to
    # the old mirror tax so the two robustness prices are comparable.
    merkle_sweep = None
    if args.merkle_scrub and args.scrub_interval:
        merkle_sweep = {}
        for interval in [
            int(x) for x in args.scrub_interval.split(",") if x.strip()
        ]:
            if interval <= 0:
                continue  # merkle-off == scrub-off: reuse scrub_sweep["0"]
            try:
                with tracer.span(f"bench.merkle_interval_{interval}"):
                    merkle_sweep[str(interval)] = run_pipeline_bench(
                        2, n_groups=12, n_clients=2, count=1024,
                        scrub_interval=interval, merkle=True,
                    )
            except Exception as err:  # noqa: BLE001 — headline must print
                print(f"# merkle interval {interval} failed: {err!r}",
                      file=sys.stderr)
                merkle_sweep[str(interval)] = None
        reps_report = dict(reps_report or {})
        reps_report["merkle_sweep"] = merkle_sweep

    # Cross-batch fusion + deferred commitment lane (docs/commit_pipeline.md
    # fusion section; docs/commitments.md deferred lane): the off / fuse /
    # async / both arms on a DISJOINT-accounts mix — per-client account
    # ranges don't overlap, so the conflict planner can actually fuse
    # (the default shared-pool mix collapses to conflict_rejects, which
    # the fusion smoke asserts separately).  Merkle is armed in every arm
    # so the deferred lane has real work to defer.  Each arm is bracketed
    # by its own contention guard; replies_sha and the ledger digest must
    # be bit-identical across all four arms (same mix, same pinned clock).
    fusion_sweep = None
    if args.fuse_batches or args.merkle_async:
        # count/lanes match the r11 flagship harness fields so the
        # per_batch_us delta is comparable to the 28.9 ms/batch headline.
        fshape = dict(n_groups=6, n_clients=4, count=1024, lanes=8192,
                      scrub_interval=1000, merkle=True,
                      disjoint_accounts=True)
        arms = [("off", False, False)]
        if args.fuse_batches:
            arms.append(("fuse", True, False))
        if args.merkle_async:
            arms.append(("async", False, True))
        if args.fuse_batches and args.merkle_async:
            arms.append(("both", True, True))
        fusion_sweep = {"shape": fshape}
        for arm_name, fuse_on, async_on in arms:
            guard = harness_contention_snapshot()
            try:
                with tracer.span(f"bench.fusion_{arm_name}"):
                    entry = run_pipeline_bench(
                        2, fuse=fuse_on, merkle_async=async_on, **fshape
                    )
            except Exception as err:  # noqa: BLE001 — headline must print
                print(f"# fusion arm {arm_name} failed: {err!r}",
                      file=sys.stderr)
                entry = None
            if entry is not None:
                entry["harness_guard"] = guard
                entry["harness_guard_after"] = harness_contention_snapshot()
            fusion_sweep[arm_name] = entry
        reps_report = dict(reps_report or {})
        reps_report["fusion_sweep"] = fusion_sweep

    # Wire-auth tax (Byzantine fault domain, docs/fault_domains.md): MAC
    # stamp + verify on every source-authenticated frame, measured on the
    # deterministic sim cluster on vs off — payload.auth.overhead_vs_off.
    auth_bench = None
    if args.auth_overhead:
        try:
            with tracer.span("bench.auth_overhead"):
                auth_bench = run_auth_bench()
        except Exception as err:  # noqa: BLE001 — headline must print
            print(f"# auth overhead bench failed: {err!r}", file=sys.stderr)
            auth_bench = None
        reps_report = dict(reps_report or {})
        reps_report["auth_bench"] = auth_bench

    # Causal-tracing tax + commit-stage attribution (docs/tracing.md):
    # worst-case sampled tracing on vs off on the serving-path pipeline
    # bench (identity asserted via replies_sha/digest), plus the depth-1
    # stage ledger that names the dominant per_batch_us term.
    trace_bench = None
    attribution_bench = None
    if args.trace_overhead:
        try:
            with tracer.span("bench.trace_overhead"):
                trace_bench = run_trace_overhead_bench()
        except Exception as err:  # noqa: BLE001 — headline must print
            print(f"# trace overhead bench failed: {err!r}", file=sys.stderr)
            trace_bench = None
        try:
            with tracer.span("bench.attribution"):
                attribution_bench = run_attribution_bench()
        except Exception as err:  # noqa: BLE001 — headline must print
            print(f"# attribution bench failed: {err!r}", file=sys.stderr)
            attribution_bench = None
        reps_report = dict(reps_report or {})
        reps_report["trace_bench"] = trace_bench
        reps_report["attribution_bench"] = attribution_bench

    # Offered-load sweep (overload fault domain): the degradation curve of
    # the overload-control surface — shed rate + admitted p99 per offered
    # multiple — on the deterministic sim cluster (virtual time).
    overload_sweep = None
    if args.offered_load:
        overload_sweep = {}
        for x in [
            float(v) for v in args.offered_load.split(",") if v.strip()
        ]:
            key = f"{x:g}x"
            try:
                with tracer.span(f"bench.offered_load_{key}"):
                    overload_sweep[key] = run_offered_load(x)
            except Exception as err:  # noqa: BLE001 — headline must print
                print(f"# offered load {key} failed: {err!r}",
                      file=sys.stderr)
                overload_sweep[key] = None
        reps_report = dict(reps_report or {})
        reps_report["offered_load_sweep"] = overload_sweep

    # Sharded serving sweep (ROADMAP open item 1, docs/sharding.md): the
    # pinned mixed workload at each TB_SHARDS count on virtual devices;
    # digest identity across entries is asserted inside the runner.
    shard_sweep = None
    if args.shards:
        counts_list = [int(x) for x in args.shards.split(",") if x.strip()]
        try:
            with tracer.span("bench.shard_sweep"):
                shard_sweep = run_shard_sweep(counts_list)
        except Exception as err:  # noqa: BLE001 — headline must print
            print(f"# shard sweep failed: {err!r}", file=sys.stderr)
            shard_sweep = None
        reps_report = dict(reps_report or {})
        reps_report["shard_sweep"] = shard_sweep

    # Open-loop production-workload sweep (ROADMAP open item 5): Zipfian
    # open-loop traffic vs the uniform closed-loop flood, on the
    # deterministic sim cluster.
    workload_sweep = None
    if args.workload == "zipf":
        try:
            with tracer.span("bench.workload_zipf"):
                workload_sweep = run_workload_sweep()
        except Exception as err:  # noqa: BLE001 — headline must print
            print(f"# workload sweep failed: {err!r}", file=sys.stderr)
            workload_sweep = None
        reps_report = dict(reps_report or {})
        reps_report["workload_sweep"] = workload_sweep

    # Live-reshape tax (docs/reconfiguration.md): steady vs online-split
    # arms of the same seeded open-loop workload -> payload.reconfig.
    reconfig_bench = None
    if args.reconfig:
        try:
            with tracer.span("bench.reconfig"):
                reconfig_bench = run_reconfig_bench()
        except Exception as err:  # noqa: BLE001 — headline must print
            print(f"# reconfig bench failed: {err!r}", file=sys.stderr)
            reconfig_bench = None

    # Provenance: the number is only meaningful with its platform (a run
    # without --force-cpu never gets here off-TPU).  roofline:
    # first-principles v5e-1 prediction (utils/roofline.py).
    from tigerbeetle_tpu.utils import roofline

    payload = {
                "metric": (
                    "two_phase_transfers_throughput"
                    if args.two_phase
                    else "limit_transfers_throughput"
                    if args.limits
                    else "create_transfers_throughput"
                ),
                "value": round(tx_s, 1),
                "unit": "tx/s",
                "vs_baseline": round(tx_s / 1_000_000, 3),
                "e2e_tcp_wal_tx_s": _e2e_val(e2e, "e2e_tcp_wal"),
                "e2e_device_tx_s": _e2e_val(e2e, "e2e_device"),
                "e2e_cluster_tx_s": _e2e_val(e2e, "e2e_cluster"),
                "e2e_lat_ms": {
                    k: v["lat_ms"] for k, v in e2e.items() if v
                },
                # Device-executor forensics.
                "e2e_device_detail": {
                    k: {
                        "tx_s": round(v["tx_s"], 1),
                        "n_clients": v.get("n_clients"),
                        "dispatches": v.get("dispatches"),
                        "dispatch_wait_s": v.get("dispatch_wait_s"),
                    }
                    for k, v in e2e.items()
                    if v and k.startswith("e2e_device")
                } or None,
                # All server+client processes share this host's cores; the
                # cluster number on a 1-core bench host measures contention,
                # not consensus (each replica is one core in deployment,
                # docs/deploy/hardware.md:29-40).
                "n_cores": os.cpu_count(),
                "platform": jax.devices()[0].platform,
                "per_batch_us": round(elapsed / max(remaining, 1) * 1e6, 1),
                # Harness shape, separated from hot-path cost so rounds are
                # comparable: tx/s = count / per_batch — a smaller --count
                # (BENCH_r07 ran 1024) collapses tx/s at IDENTICAL
                # per-batch cost (harness drift, not a hot-path
                # regression), and per_batch_us itself is only comparable
                # at equal n_cores/platform (BENCH_r06 vs r03: the CPU
                # fallback host shrank).  Cross-round triage: compare
                # per_batch_us first, at matching harness fields.
                "harness": {
                    "count": count,
                    "lanes": lanes,
                    "accounts": n_accounts,
                    "timed_batches": remaining,
                    "batches_per_dispatch": k,
                    "n_cores": os.cpu_count(),
                    # Sharded-mode provenance (PR 8): rounds are only
                    # comparable at matching shard counts, and a sweep on
                    # virtual devices must never be read as a multi-chip
                    # number.
                    "shards": int(os.environ.get("TB_SHARDS", "0") or 0),
                    "mesh_devices": len(jax.devices()),
                    "tx_s_is_count_over_per_batch": True,
                    # Recompile tripwire: XLA compiles observed INSIDE the
                    # timed loop (must be 0 for per_batch_us to be a
                    # hot-path number; nonzero prints a loud warning;
                    # null = the compile listener could not install, so
                    # the loop was NOT verified compile-free).
                    "timed_compiles": timed_compiles,
                    # Contention guard snapshot (taken right before the
                    # timed region): load_avg / competing_procs /
                    # contended — a contended run's tx/s measures the
                    # host, not the hot path.
                    **harness_guard,
                },
                "reps": reps_report,
                "kernel_profile": kernel_profile,
                "roofline": roofline.report(count),
    }
    # Commit-pipeline overlap stats ride the primary line: the depth the
    # serving path runs at, and (when swept) the per-depth speedup.
    from tigerbeetle_tpu.machine import pipeline_depth_default

    payload["pipeline"] = {"depth": pipeline_depth_default()}
    if reconfig_bench:
        payload["reconfig"] = reconfig_bench
    if scrub_sweep:
        # Headline scrub block: per-interval tx/s + the overhead ratio vs
        # the scrub-off entry of the SAME reduced shape (never vs the
        # full-size headline).
        payload["scrub"] = {
            "sweep": {
                k: ({"tx_s": v["tx_s"], "p50_ms": v["p50_ms"],
                     "checks": (v.get("scrub") or {}).get("checks", 0)}
                    if v else None)
                for k, v in scrub_sweep.items()
            },
        }
        off = scrub_sweep.get("0")
        if off:
            payload["scrub"]["overhead_vs_off"] = {
                k: round(off["tx_s"] / v["tx_s"], 2)
                for k, v in scrub_sweep.items()
                if v and k != "0" and v["tx_s"]
            }
        if merkle_sweep:
            # The tree's robustness tax at the same cadences, vs the SAME
            # scrub-off entry — directly comparable with the mirror's
            # overhead_vs_off above (ROADMAP 3: drop the ~1.6x toward 1.0x).
            payload["merkle"] = {
                "sweep": {
                    k: ({"tx_s": v["tx_s"], "p50_ms": v["p50_ms"],
                         "checks": (v.get("scrub") or {}).get("checks", 0),
                         "updates": (v.get("merkle") or {}).get("updates", 0)}
                        if v else None)
                    for k, v in merkle_sweep.items()
                },
            }
            if off:
                payload["merkle"]["overhead_vs_off"] = {
                    k: round(off["tx_s"] / v["tx_s"], 2)
                    for k, v in merkle_sweep.items()
                    if v and v["tx_s"]
                }
                # Off-path identity: the merkle-armed entries must commit
                # the exact same replies/state as the scrub-off run.
                payload["merkle"]["identity_vs_off"] = all(
                    v["replies_sha"] == off["replies_sha"]
                    and v["digest"] == off["digest"]
                    for v in merkle_sweep.values() if v
                )
    if fusion_sweep:
        f_off = fusion_sweep.get("off")
        payload["fusion"] = {
            "shape": fusion_sweep.get("shape"),
            "arms": {
                k: (
                    {
                        "per_batch_us": v.get("per_batch_us"),
                        "tx_s": v.get("tx_s"),
                        "fuse": v.get("fuse"),
                        "merkle_lane": v.get("merkle_lane"),
                        "harness_guard": v.get("harness_guard"),
                        "harness_guard_after": v.get("harness_guard_after"),
                    }
                    if v else None
                )
                for k, v in fusion_sweep.items() if k != "shape"
            },
        }
        if f_off:
            # Reply/state identity across arms: same mix + pinned clock,
            # so every arm must commit the exact same ledger.
            payload["fusion"]["identity_vs_off"] = all(
                v["replies_sha"] == f_off["replies_sha"]
                and v["digest"] == f_off["digest"]
                for k, v in fusion_sweep.items()
                if v and k not in ("shape", "off")
            )
            payload["fusion"]["speedup_vs_off"] = {
                k: round(f_off["per_batch_us"] / v["per_batch_us"], 2)
                for k, v in fusion_sweep.items()
                if v and k not in ("shape", "off") and v.get("per_batch_us")
            }
    if auth_bench:
        payload["auth"] = auth_bench
    if trace_bench:
        payload["trace"] = trace_bench
    if attribution_bench:
        payload["attribution"] = attribution_bench
    if pipeline_sweep:
        payload["pipeline"]["sweep"] = {
            d: (
                {
                    "tx_s": v["tx_s"], "p50_ms": v["p50_ms"],
                    "p99_ms": v["p99_ms"],
                }
                if v else None
            )
            for d, v in pipeline_sweep.items()
        }
        base = pipeline_sweep.get("1")
        if base and len(pipeline_sweep) > 1:
            others = [
                v for d, v in pipeline_sweep.items() if v and d != "1"
            ]
            if others:
                payload["pipeline"]["speedup_vs_depth1"] = round(
                    max(v["tx_s"] for v in others) / base["tx_s"], 2
                )
    if async_sharded_sweep:
        entries = {
            d: v for d, v in async_sharded_sweep.items()
            if d.isdigit() and v
        }
        payload["pipeline"]["sharded"] = {
            "shards": args.async_shards,
            "shape": async_sharded_sweep.get("shape"),
            "harness_guard": async_sharded_sweep.get("harness_guard"),
            "sweep": {
                d: {
                    "tx_s": v["tx_s"], "p50_ms": v["p50_ms"],
                    "p99_ms": v["p99_ms"],
                }
                for d, v in entries.items()
            },
        }
        base = entries.get("1")
        others = [v for d, v in entries.items() if d != "1"]
        if base and others:
            payload["pipeline"]["sharded"]["speedup_vs_depth1"] = round(
                max(v["tx_s"] for v in others) / base["tx_s"], 2
            )
        anchor = async_sharded_sweep.get("baseline_unsharded_d1")
        if anchor:
            # False when NO sharded entry survived: an all-failed sweep
            # must not read as a passed parity proof (vacuous all()).
            payload["pipeline"]["sharded"]["identity_vs_unsharded"] = bool(
                entries
            ) and all(
                v["replies_sha"] == anchor["replies_sha"]
                and v["digest"] == anchor["digest"]
                for v in entries.values()
            )
    if shard_sweep:
        # Headline sharding block: per-shard commit-lane throughput, the
        # cross-shard settle fraction, and the parity digest.
        payload["sharding"] = shard_sweep
    if overload_sweep:
        # Headline overload block: the degradation curve — shed rate and
        # admitted-request percentiles per offered-load multiple.
        payload["overload"] = {
            "sweep": {
                k: (
                    {
                        "shed_rate": v["shed_rate"],
                        "admitted_p50_ms": v["admitted_p50_ms"],
                        "admitted_p99_ms": v["admitted_p99_ms"],
                        "busy_replies": v["busy_replies"],
                        "completed": v["completed"],
                    }
                    if v else None
                )
                for k, v in overload_sweep.items()
            },
            "unit_note": "deterministic sim, virtual ticks x 10 ms",
        }

    # Histogram percentiles ride ALONGSIDE tx/s (a single scalar cannot say
    # WHY a number moved; the percentiles + compile counts can).
    if obs_registry is not None:
        snap = obs_registry.snapshot()
        hists, counters = snap["histograms"], snap["counters"]

        def pcts(name):
            h = hists.get(name)
            if not h or not h.get("count"):
                return None
            return {"count": h["count"], "p50": h.get("p50"),
                    "p95": h.get("p95"), "p99": h.get("p99"),
                    "max": h.get("max"), "unit": h.get("unit")}

        payload["metrics"] = {
            "commit_latency_us": pcts("replica.commit_us"),
            "prefetch_latency_us": pcts("replica.prefetch_us"),
            "batch_size_events": pcts("replica.batch_events"),
            "batch_fill_pct": pcts("ops.batch_fill_pct"),
            "net_group_size": pcts("net.group_size"),
            "dispatch_wait_us": pcts("ops.dispatch_wait_us"),
            "jit_compiles": counters.get("jit.compiles", 0),
            "jit_compile_ms": pcts("jit.compile_ms"),
            "device_dispatches": counters.get("ops.dispatch", 0),
            # Commit-pipeline occupancy + stall forensics
            # (docs/commit_pipeline.md "Reading the metrics").
            "pipeline_inflight": pcts("pipeline.inflight"),
            "pipeline_dispatches": counters.get("pipeline.dispatches", 0),
            "pipeline_resolves": counters.get("pipeline.resolves", 0),
            "pipeline_stalls": {
                name.split("pipeline.stall.", 1)[1]: v
                for name, v in counters.items()
                if name.startswith("pipeline.stall.")
            },
            # Per-shard commit-lane occupancy/stall forensics (the async
            # sharded engine; docs/observability.md pipeline.shard.* rows).
            "pipeline_shard_inflight": pcts("pipeline.shard.inflight"),
            "pipeline_shard_dispatches": counters.get(
                "pipeline.shard.dispatches", 0
            ),
            "pipeline_shard_resolves": counters.get(
                "pipeline.shard.resolves", 0
            ),
            "pipeline_shard_lanes": {
                (name.split("pipeline.shard.lanes.", 1)[1]
                 if name.startswith("pipeline.shard.lanes.")
                 else "total"): v
                for name, v in counters.items()
                if name.startswith("pipeline.shard.lanes")
            } or None,
            "pipeline_shard_stalls": {
                name.split("pipeline.shard.stall.", 1)[1]: v
                for name, v in counters.items()
                if name.startswith("pipeline.shard.stall.")
            },
        }
        if args.metrics_json:
            obs_registry.dump(args.metrics_json)
            print(f"# metrics snapshot: {args.metrics_json}",
                  file=sys.stderr)

    # Host+device trace merge (TB_TRACE=json): one Chrome trace where a
    # bench/commit span lines up with its XLA dispatch.
    if capture is not None:
        import shutil as _shutil

        from tigerbeetle_tpu.obs import profile as obs_profile

        capture.__exit__()
        trace_path = os.environ.get("TB_TRACE_PATH", "tb_trace.json")
        try:
            stats = obs_profile.merge_with_tracer(capture, trace_path)
            print(f"# merged trace: {trace_path} "
                  f"({stats['host_events']} host + "
                  f"{stats['device_events']} device events"
                  + (f", {stats['device_events_dropped']} device "
                     "micro-ops dropped to the size budget"
                     if stats.get("device_events_dropped") else "")
                  + (f"; device capture degraded: "
                     f"{stats['device_capture_error']}"
                     if stats.get("device_capture_error") else "")
                  + ")", file=sys.stderr)
        except OSError as err:
            print(f"# trace merge failed: {err!r}", file=sys.stderr)
        _shutil.rmtree(prof_dir, ignore_errors=True)

    print(json.dumps(payload))
    print(
        f"# {remaining} batches x {count} transfers in {elapsed:.2f}s "
        f"(compile+warmup {compile_s:.1f}s), {len(jax.devices())} device(s), "
        f"platform={jax.devices()[0].platform}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
