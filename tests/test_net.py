"""End-to-end network tests: client <-> TCP server <-> replica, plus the repl
and CLI surfaces (reference analogue: integration_tests.zig black-box ring)."""

import io
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from tigerbeetle_tpu import repl, types
from tigerbeetle_tpu.client import Client, ClientEvicted
from tigerbeetle_tpu.config import ClusterConfig, LedgerConfig
from tigerbeetle_tpu.net.bus import run_server
from tigerbeetle_tpu.vsr.replica import Replica

TEST_CONFIG = ClusterConfig(message_size_max=8192, journal_slot_count=64)
TEST_LEDGER = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10, max_probe=1 << 10,
)
CLUSTER = 0xC1


@pytest.fixture
def server(tmp_path):
    """A live replica served over TCP on an ephemeral port (daemon thread)."""
    path = str(tmp_path / "net.tb")
    Replica.format(path, cluster=CLUSTER, cluster_config=TEST_CONFIG)
    replica = Replica(path, cluster_config=TEST_CONFIG,
                      ledger_config=TEST_LEDGER, batch_lanes=64)
    replica.open()
    box = {}
    ready = threading.Event()
    thread = threading.Thread(
        target=run_server,
        args=(replica, "127.0.0.1", 0),
        kwargs=dict(ready_callback=lambda p: (box.update(port=p), ready.set())),
        daemon=True,
    )
    thread.start()
    assert ready.wait(30)
    yield [("127.0.0.1", box["port"])]


def make_client(server):
    return Client(server, cluster=CLUSTER, config=TEST_CONFIG, timeout_s=10)


class TestClientServer:
    def test_full_flow(self, server):
        client = make_client(server)
        accounts = np.zeros(3, dtype=types.ACCOUNT_DTYPE)
        accounts["id_lo"] = [1, 2, 3]
        accounts["ledger"] = 7
        accounts["code"] = 10
        assert client.create_accounts(accounts) == []

        transfers = np.zeros(2, dtype=types.TRANSFER_DTYPE)
        transfers["id_lo"] = [100, 101]
        transfers["debit_account_id_lo"] = [1, 2]
        transfers["credit_account_id_lo"] = [2, 3]
        transfers["amount_lo"] = [500, 200]
        transfers["ledger"] = 7
        transfers["code"] = 10
        assert client.create_transfers(transfers) == []

        rows = client.lookup_accounts([1, 2, 3])
        assert len(rows) == 3
        assert int(rows[1]["debits_posted_lo"]) == 200
        assert int(rows[1]["credits_posted_lo"]) == 500

        trows = client.lookup_transfers([100, 999])
        assert len(trows) == 1
        assert int(trows[0]["amount_lo"]) == 500
        client.close()

    def test_failure_results_roundtrip(self, server):
        client = make_client(server)
        accounts = np.zeros(2, dtype=types.ACCOUNT_DTYPE)
        accounts["id_lo"] = [10, 0]  # second: id_must_not_be_zero
        accounts["ledger"] = 1
        accounts["code"] = 1
        results = client.create_accounts(accounts)
        assert results == [(1, int(types.CreateAccountResult.id_must_not_be_zero))]
        client.close()

    def test_two_clients_sessions(self, server):
        c1, c2 = make_client(server), make_client(server)
        a = np.zeros(1, dtype=types.ACCOUNT_DTYPE)
        a["id_lo"] = 50
        a["ledger"] = 1
        a["code"] = 1
        assert c1.create_accounts(a) == []
        # Same id from the second client: exists (sessions are independent).
        assert c2.create_accounts(a) == [(0, int(types.CreateAccountResult.exists))]
        assert c1.session != c2.session
        c1.close()
        c2.close()

    def test_reconnect_resends(self, server):
        client = make_client(server)
        a = np.zeros(1, dtype=types.ACCOUNT_DTYPE)
        a["id_lo"] = 60
        a["ledger"] = 1
        a["code"] = 1
        assert client.create_accounts(a) == []
        client.close()  # drop TCP; session state is client-side
        rows = client.lookup_accounts([60])  # reconnects transparently
        assert len(rows) == 1
        client.close()

    def test_malformed_request_dropped_not_journaled(self, server):
        """A malformed body must be rejected before the WAL write — else
        replay would wedge the replica forever."""
        import socket as socket_mod

        from tigerbeetle_tpu.vsr import wire as w

        client = make_client(server)
        client.register()
        # Hand-craft a create_accounts request whose body is not a multiple
        # of 128 bytes (bypassing the client library's checks).
        h = w.new_header(
            w.Command.request, cluster=CLUSTER, client=client.client_id,
            request=1, session=client.session, parent=client.parent,
            operation=int(w.Operation.create_accounts),
        )
        bad = w.encode(h, b"x" * 100)
        sock = socket_mod.create_connection(server[0], timeout=5)
        sock.sendall(bad)
        sock.settimeout(1.0)
        with pytest.raises(TimeoutError):
            sock.recv(1)  # dropped silently: no reply, no crash
        sock.close()
        # The server is still healthy and the op was NOT journaled: the next
        # valid request commits fine.
        a = np.zeros(1, dtype=types.ACCOUNT_DTYPE)
        a["id_lo"] = 80
        a["ledger"] = 1
        a["code"] = 1
        assert client.create_accounts(a) == []
        client.close()

    def test_stale_session_evicted(self, server):
        client = make_client(server)
        client.register()
        client.session += 99  # corrupt the session number
        a = np.zeros(1, dtype=types.ACCOUNT_DTYPE)
        a["id_lo"] = 70
        a["ledger"] = 1
        a["code"] = 1
        with pytest.raises(ClientEvicted):
            client.create_accounts(a)
        client.close()


class TestStatsdEmission:
    def test_server_emits_request_event_latency_samples(self, tmp_path):
        """The StatsD path stays wired through the group-commit server:
        requests/events counters and request_ms timings arrive over UDP
        (net/bus._emit_stats)."""
        import socket as socket_mod

        from tigerbeetle_tpu.utils.statsd import StatsD

        recv = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
        recv.bind(("127.0.0.1", 0))
        recv.settimeout(0.5)
        udp_port = recv.getsockname()[1]

        path = str(tmp_path / "statsd.tb")
        Replica.format(path, cluster=CLUSTER, cluster_config=TEST_CONFIG)
        replica = Replica(path, cluster_config=TEST_CONFIG,
                          ledger_config=TEST_LEDGER, batch_lanes=64)
        replica.open()
        box = {}
        ready = threading.Event()
        thread = threading.Thread(
            target=run_server, args=(replica, "127.0.0.1", 0),
            kwargs=dict(
                ready_callback=lambda p: (box.update(port=p), ready.set()),
                statsd=StatsD("127.0.0.1", udp_port, prefix="tb"),
            ),
            daemon=True,
        )
        thread.start()
        assert ready.wait(30)

        client = Client([("127.0.0.1", box["port"])], cluster=CLUSTER,
                        config=TEST_CONFIG, timeout_s=10)
        accounts = np.zeros(3, dtype=types.ACCOUNT_DTYPE)
        accounts["id_lo"] = [1, 2, 3]
        accounts["ledger"] = 1
        accounts["code"] = 10
        assert client.create_accounts(accounts) == []
        transfers = np.zeros(2, dtype=types.TRANSFER_DTYPE)
        transfers["id_lo"] = [100, 101]
        transfers["debit_account_id_lo"] = [1, 2]
        transfers["credit_account_id_lo"] = [2, 3]
        transfers["amount_lo"] = [5, 6]
        transfers["ledger"] = 1
        transfers["code"] = 10
        assert client.create_transfers(transfers) == []
        client.close()

        samples = []
        deadline = __import__("time").time() + 5.0
        while __import__("time").time() < deadline:
            try:
                samples.append(recv.recv(2048).decode())
            except TimeoutError:
                pass
            if (
                sum(
                    int(s.split(":")[1].split("|")[0])
                    for s in samples if s.startswith("tb.events:")
                ) >= 5
                and any(s.startswith("tb.request_ms:") for s in samples)
            ):
                break
        recv.close()
        assert any(
            s.startswith("tb.requests:") and s.endswith("|c")
            for s in samples
        ), samples
        # 3 account + 2 transfer events, possibly split across groups; >=
        # (not ==) because a client timeout-resend legitimately re-counts.
        event_counts = [
            int(s.split(":")[1].split("|")[0])
            for s in samples if s.startswith("tb.events:")
        ]
        assert sum(event_counts) >= 5, samples
        assert any(
            s.startswith("tb.request_ms:") and s.endswith("|ms")
            for s in samples
        ), samples


class TestRepl:
    def test_statements(self, server):
        client = make_client(server)
        out = io.StringIO()
        repl.execute_statement(
            client,
            "create_accounts id=1 ledger=700 code=10, id=2 ledger=700 code=10",
            out,
        )
        repl.execute_statement(
            client,
            "create_transfers id=5 debit_account_id=1 credit_account_id=2 "
            "amount=125 ledger=700 code=10",
            out,
        )
        repl.execute_statement(client, "lookup_accounts id=1, id=2", out)
        text = out.getvalue()
        assert "ok" in text
        assert "debits_posted=125" in text
        assert "credits_posted=125" in text
        client.close()

    def test_parse_errors(self):
        with pytest.raises(ValueError, match="unknown operation"):
            repl.parse_statement("create_account id=1")
        with pytest.raises(ValueError, match="field=value"):
            repl.parse_statement("create_accounts id")
        with pytest.raises(ValueError, match="unknown flag"):
            repl.build_accounts([{"id": "1", "flags": "bogus"}])

    def test_flags_parse(self):
        batch = repl.build_transfers(
            [{"id": "9", "flags": "linked|pending", "amount": "1"}]
        )
        assert batch[0]["flags"] == int(
            types.TransferFlags.LINKED | types.TransferFlags.PENDING
        )


def _readline_with_timeout(proc, timeout_s):
    """Read one stdout line without wedging the suite: a bounded wait +
    skip beats an infinite readline on a child that never gets ready."""
    box = {}

    def reader():
        box["line"] = proc.stdout.readline()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        proc.kill()
        pytest.skip(
            f"spawned server produced no output in {timeout_s}s "
            "(interpreter startup stalled in this image)"
        )
    return box["line"]


@pytest.mark.slow
class TestCliSubprocess:
    def test_format_start_repl_roundtrip(self, tmp_path):
        """Black-box: CLI format + start (subprocess) + repl one-shot."""
        from tigerbeetle_tpu import jaxenv

        path = str(tmp_path / "cli.tb")
        # child_env pins the child interpreter to one CPU device.
        env = jaxenv.child_env(cpu=True, n_devices=1)
        fmt = subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu", "format", path,
             "--cluster", "0xD1"],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert fmt.returncode == 0, fmt.stderr

        proc = subprocess.Popen(
            [sys.executable, "-m", "tigerbeetle_tpu", "start", path,
             "--addresses", "127.0.0.1:0",
             "--cache-accounts-log2", "10"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        try:
            line = _readline_with_timeout(proc, 180)
            assert line.startswith("listening"), (line, proc.stderr.read())
            port = int(line.strip().rsplit(":", 1)[1])

            one_shot = (
                "create_accounts id=1 ledger=1 code=1, id=2 ledger=1 code=1;"
                "create_transfers id=3 debit_account_id=1 credit_account_id=2 "
                "amount=42 ledger=1 code=1;"
                "lookup_accounts id=2"
            )
            out = subprocess.run(
                [sys.executable, "-m", "tigerbeetle_tpu", "repl",
                 "--cluster", "0xD1", "--addresses", f"127.0.0.1:{port}",
                 "--command", one_shot],
                capture_output=True, text=True, env=env, timeout=300,
            )
            assert out.returncode == 0, out.stderr
            assert "credits_posted=42" in out.stdout
        finally:
            proc.terminate()
            proc.wait(timeout=10)
