"""A server that stops on ONE SIGTERM (ROADMAP B-I.9).

`run_server` takes the signal as a callback of its loop (an exception thrown
into whatever the main thread is executing can lose a task's wakeup),
cancels `ReplicaServer.serve_forever`, whose way out does not wait for
connections to detach (`Server.wait_closed()` since Python 3.12 does), then
writes the exit-time dumps and leaves with code 143 without the
interpreter's slow finalization (PERF.md section 6, PR 26)."""

import asyncio
import json
import signal
import subprocess
import sys
import threading
import time

import pytest

from test_net import _readline_with_timeout
from test_pipeline import accounts_batch, batch
from tigerbeetle_tpu import jaxenv
from tigerbeetle_tpu.client import Client
from tigerbeetle_tpu.config import ClusterConfig, LedgerConfig
from tigerbeetle_tpu.net.bus import ReplicaServer
from tigerbeetle_tpu.vsr.replica import Replica

SESSIONS = 8


@pytest.mark.parametrize("sessions", ["closed", "open"])
def test_server_child_exits_143_on_one_sigterm(tmp_path, sessions):
    """Eight sessions that have sent requests and closed / that are still
    open: one SIGTERM, exit code 143 within 5 s, the atexit dump written."""
    path = str(tmp_path / "stop.tb")
    metrics = str(tmp_path / "exit.json")
    env = jaxenv.child_env(cpu=True, n_devices=1)
    py = [sys.executable, "-m", "tigerbeetle_tpu"]
    subprocess.run(py + ["format", path, "--cluster", "0"], check=True,
                   env=env, capture_output=True, timeout=120)
    proc = subprocess.Popen(
        py + ["start", path, "--addresses", "127.0.0.1:0", "--no-engine",
              "--cache-accounts-log2", "10", "--cache-transfers-log2", "12",
              "--metrics-json", metrics],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env,
    )
    clients = []
    try:
        line = _readline_with_timeout(proc, 300)
        assert line.startswith("listening"), line
        port = int(line.strip().rsplit(":", 1)[1])
        clients = [Client([("127.0.0.1", port)], cluster=0, timeout_s=60,
                          client_id=0x900 + 2 * k + 1)
                   for k in range(SESSIONS)]
        assert clients[0].create_accounts(accounts_batch()) == []

        def session(k):
            for r in range(2):
                assert clients[k].create_transfers(
                    batch(10_000 * (k + 1) + 100 * r, 8)) == []

        threads = [threading.Thread(target=session, args=(k,), daemon=True)
                   for k in range(SESSIONS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
        if sessions == "closed":
            for c in clients:
                c.close()  # the EOFs and the signal race, as in a bench run
        t0 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(5) == 143
        assert time.monotonic() - t0 < 5
        with open(metrics) as f:  # the atexit dump, whole
            assert json.load(f)["counters"]["net.requests"] >= 2 * SESSIONS
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(30)
        for c in clients:
            c.close()


def test_serve_forever_ends_beside_a_transport_that_never_detaches(tmp_path):
    """The failing mode itself: a transport counted as attached whose
    `connection_lost` never comes.  Cancelled (what `asyncio.run` does with
    the SystemExit), `serve_forever` closes and returns; awaiting
    `wait_closed()` there would never."""
    config = ClusterConfig(message_size_max=8192, journal_slot_count=64)
    path = str(tmp_path / "leak.tb")
    Replica.format(path, cluster=1, cluster_config=config)
    replica = Replica(
        path, cluster_config=config, batch_lanes=64,
        ledger_config=LedgerConfig(
            accounts_capacity_log2=10, transfers_capacity_log2=12,
            posted_capacity_log2=10, max_probe=1 << 10))
    replica.open()

    async def main():
        server = ReplicaServer(replica, "127.0.0.1", 0)
        await server.start()
        serving = asyncio.ensure_future(server.serve_forever())
        await asyncio.sleep(0.05)
        # Attached, never to detach (asyncio's own count of connections;
        # 3.13 passes the transport along).
        attach, detach = server._server._attach, server._server._detach
        leaked = [object()] * (attach.__code__.co_argcount - 1)
        attach(*leaked)
        serving.cancel()
        done, _pending = await asyncio.wait([serving], timeout=5)
        if serving not in done:
            detach(*leaked)  # let the stuck task end, then fail
            await asyncio.wait([serving], timeout=5)
            pytest.fail("serve_forever waits for a transport to detach")
        assert serving.cancelled() and not server._server.is_serving()

    try:
        asyncio.run(main())
    finally:
        replica.close()
