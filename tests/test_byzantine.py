"""Byzantine replica fault domain (docs/fault_domains.md, fifth domain).

Layers under test:

- vsr/wire.py: reason-tagged rejection taxonomy (WireError), strict
  trailing-byte and empty-body checksum verification, the
  decode_unverified negative-control parser, and the source-authenticated
  command set;
- net/bus.py read_message: a bad BODY under a valid header is skipped and
  counted without severing the connection (a malformed frame must not let
  a malicious peer poison an honest link); a bad header still drops it;
- sim/cluster.py: transport source authentication (impersonated votes
  drop-and-count), the ByzantineActor's forgery mechanics, and the
  lying-reply oracle wiring;
- vsr/consensus.py: from-primary well-formedness, commit-checksum
  anchoring, certified backup commits, and fork eviction — equivocation
  is detected and repaired, never executed;
- sim/openloop.py: the deterministic open-loop generator (Zipfian skew,
  arrival processes, bit-identical scripts under a fixed seed);
- sim/vopr.py run_byzantine_seed: the pinned on/off proof (slow).
"""

import asyncio

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.sim import PacketSimulator, SimCluster
from tigerbeetle_tpu.sim.cluster import ByzantineActor
from tigerbeetle_tpu.sim.openloop import OpenLoopGen, zipf_skew
from tigerbeetle_tpu.testing.auditor import AuditError
from tigerbeetle_tpu.vsr import wire

CLUSTER_ID = 7


# ---------------------------------------------------------------------------
# wire: the satellite ingress audit (regression test per fixed path)
# ---------------------------------------------------------------------------


class TestWireStrictness:
    def _frame(self, body=b""):
        h = wire.new_header(
            wire.Command.ping, cluster=CLUSTER_ID,
            checkpoint_op=3, ping_timestamp_monotonic=9,
        )
        return wire.encode(h, body)

    def test_trailing_bytes_rejected(self):
        buf = self._frame() + b"x"
        with pytest.raises(ValueError) as e:
            wire.decode(buf)
        assert e.value.reason == "trailing_bytes"

    def test_empty_body_stale_checksum_body_rejected(self):
        """A header-only frame whose checksum_body is stale verifies its
        HEADER checksum (which covers the stale field) but must still be
        rejected: the fixed silent-acceptance path."""
        h = wire.new_header(wire.Command.ping, cluster=CLUSTER_ID)
        h["checksum_body_lo"] = 0xDEAD  # stale: != checksum(b"")
        from tigerbeetle_tpu.vsr.checksum import checksum as cs

        c = cs(h.tobytes()[16:])
        h["checksum_lo"] = c & 0xFFFF_FFFF_FFFF_FFFF
        h["checksum_hi"] = c >> 64
        buf = h.tobytes()
        decoded, _ = wire.decode_header(buf)  # header checksum passes
        with pytest.raises(ValueError) as e:
            wire.verify_body(decoded, b"")
        assert e.value.reason == "body_checksum"
        with pytest.raises(ValueError):
            wire.decode(buf)

    def test_reason_slugs_stable(self):
        cases = {
            b"short": "short_header",
            b"\x00" * 256: "header_checksum",
        }
        for buf, reason in cases.items():
            with pytest.raises(ValueError) as e:
                wire.decode_header(buf)
            assert e.value.reason == reason

    def test_decode_unverified_parses_corrupt_frames(self):
        buf = bytearray(self._frame(b"hello"))
        buf[258] ^= 0xFF  # corrupt the body
        with pytest.raises(ValueError):
            wire.decode(bytes(buf))
        h, command, body = wire.decode_unverified(bytes(buf))
        assert command == wire.Command.ping
        assert len(body) == 5  # parsed despite the corruption

    def test_source_authenticated_set_excludes_relayed(self):
        for relayed in (wire.Command.prepare, wire.Command.request,
                        wire.Command.reply, wire.Command.eviction,
                        wire.Command.busy):
            assert relayed not in wire.SOURCE_AUTHENTICATED_COMMANDS
        for direct in (wire.Command.prepare_ok, wire.Command.commit,
                       wire.Command.do_view_change, wire.Command.ping):
            assert direct in wire.SOURCE_AUTHENTICATED_COMMANDS


# ---------------------------------------------------------------------------
# net/bus.read_message: malformed bodies must not poison the connection
# ---------------------------------------------------------------------------


def _feed_reader(chunks: bytes):
    """Call from INSIDE the coroutine that _run drives: StreamReader() binds
    the running loop, and with none it asks the policy for one — which on
    Python 3.12 raises once an earlier test on this worker ran asyncio.run."""
    reader = asyncio.StreamReader()
    reader.feed_data(chunks)
    reader.feed_eof()
    return reader


class TestReadMessage:
    def _run(self, coro):
        return asyncio.new_event_loop().run_until_complete(coro)

    def test_bad_body_skipped_connection_survives(self):
        from tigerbeetle_tpu.net.bus import read_message

        good = wire.encode(
            wire.new_header(wire.Command.ping, cluster=1), b""
        )
        bad = bytearray(wire.encode(
            wire.new_header(wire.Command.ping, cluster=1), b"payload"
        ))
        bad[258] ^= 1  # body bit flip: header stays valid
        rejects = []

        async def go():
            return await read_message(
                _feed_reader(bytes(bad) + good), 1 << 20,
                on_reject=rejects.append,
            )

        msg = self._run(go())
        assert msg is not None, "the good frame after the bad one is served"
        assert msg[1] == wire.Command.ping
        assert rejects == ["body_checksum"]

    def test_empty_body_stale_checksum_rejected_and_skipped(self):
        from tigerbeetle_tpu.net.bus import read_message
        from tigerbeetle_tpu.vsr.checksum import checksum as cs

        h = wire.new_header(wire.Command.ping, cluster=1)
        h["checksum_body_lo"] = 0xFEED  # stale empty-body checksum
        c = cs(h.tobytes()[16:])
        h["checksum_lo"] = c & 0xFFFF_FFFF_FFFF_FFFF
        h["checksum_hi"] = c >> 64
        good = wire.encode(wire.new_header(wire.Command.ping, cluster=1))
        rejects = []

        async def go():
            return await read_message(
                _feed_reader(h.tobytes() + good), 1 << 20,
                on_reject=rejects.append,
            )

        msg = self._run(go())
        assert msg is not None and rejects == ["body_checksum"]

    def test_bad_header_still_drops_connection(self):
        from tigerbeetle_tpu.net.bus import FrameError, read_message

        async def go():
            reader = _feed_reader(b"\x00" * 256)
            await read_message(reader, 1 << 20)

        with pytest.raises(FrameError):
            self._run(go())


# ---------------------------------------------------------------------------
# sim source authentication + consensus well-formedness
# ---------------------------------------------------------------------------


def make_cluster(tmp_path, seed=5, n=3, clients=1, requests=2, **kw):
    return SimCluster(
        str(tmp_path), n_replicas=n, n_clients=clients, seed=seed,
        requests_per_client=requests,
        net=PacketSimulator(seed=seed + 1, delay_mean=1, delay_max=4),
        **kw,
    )


class TestSourceAuth:
    def test_impersonated_vote_rejected(self, tmp_path):
        cluster = make_cluster(tmp_path)
        cluster.run(50)
        # Replica 2 forges a prepare_ok claiming to be replica 1.
        forged = wire.new_header(
            wire.Command.prepare_ok, cluster=CLUSTER_ID,
            prepare_checksum=1, client=0, op=1, commit=0,
        )
        forged["replica"] = 1
        cluster.net.send(
            ("replica", 2), ("replica", 0), wire.encode(forged), cluster.t
        )
        cluster.run(20)
        assert cluster.rejected_frames.get("impersonation", 0) >= 1

    def test_honest_run_rejects_nothing(self, tmp_path):
        cluster = make_cluster(tmp_path, seed=6)
        ok = cluster.run_until(
            lambda: cluster.clients_done() and cluster.converged(),
            max_ticks=30_000,
        )
        assert ok
        assert cluster.rejected_frames == {}

    def test_prepare_from_non_primary_rejected(self, tmp_path):
        # The process-global registry must not LEAK enabled past this
        # test: a later statsd-wired server would flush every counter
        # accumulated since (hundreds of UDP packets per flush), flooding
        # unrelated tests' sockets — found when the flood grew enough to
        # drop test_cluster_net's one load-bearing events datagram.
        registry.enable()
        try:
            before = registry.counter("byzantine.rejected.not_primary").value
            cluster = make_cluster(tmp_path, seed=8)
            cluster.run(50)
            # A prepare claiming replica 2 prepared it in view 0
            # (primary 0): ill-formed regardless of transport source.
            forged = wire.new_header(
                wire.Command.prepare, cluster=CLUSTER_ID, view=0,
                parent=1, request_checksum=2, client=3, op=99, commit=0,
                timestamp=4, request=1,
                operation=int(wire.Operation.create_accounts),
            )
            forged["replica"] = 2
            cluster.net.send(
                ("replica", 2), ("replica", 1), wire.encode(forged, b""),
                cluster.t,
            )
            cluster.run(20)
            after = registry.counter(
                "byzantine.rejected.not_primary"
            ).value
            assert after > before
        finally:
            registry.reset()
            registry.disable()


# ---------------------------------------------------------------------------
# ByzantineActor mechanics
# ---------------------------------------------------------------------------


class TestByzantineActor:
    def _actor(self, **kw):
        return ByzantineActor(
            replica=1, n_replicas=3, cluster_id=CLUSTER_ID, seed=99, **kw
        )

    def _prepare_frame(self, body=b"\x01" * 128):
        h = wire.new_header(
            wire.Command.prepare, cluster=CLUSTER_ID, view=0,
            parent=11, request_checksum=22, client=33, op=5, commit=4,
            timestamp=55, request=2,
            operation=int(wire.Operation.create_transfers),
        )
        h["replica"] = 0
        return wire.encode(h, body)

    def test_stale_body_frame_passes_header_fails_body(self):
        actor = self._actor()
        h, _, body = wire.decode(self._prepare_frame())
        frame = actor._stale_body_frame(h, actor._flip(body))
        wire.decode_header(frame)  # header checksum verifies
        with pytest.raises(ValueError) as e:
            wire.decode(frame)
        assert e.value.reason == "body_checksum"

    def test_equivocate_emits_conflicting_valid_frames(self):
        actor = self._actor(kinds={"equivocate"}, rate=1.0)
        out = actor.transform([(("replica", 2), self._prepare_frame())], 10)
        assert len(out) == 2
        decoded = [wire.decode(m) for _dst, m in out]  # both fully valid
        ops = {int(h["op"]) for h, _c, _b in decoded}
        assert ops == {5}, "same op number"
        checksums = {wire.header_checksum(h) for h, _c, _b in decoded}
        assert len(checksums) == 2, "conflicting content"
        dsts = {dst for dst, _m in out}
        assert len(dsts) == 2, "sent to different peers"

    def test_forged_reply_is_a_lie_with_stale_body(self):
        actor = self._actor(kinds={"lie_reply"}, rate=1.0)
        h, _, body = wire.decode(self._prepare_frame())
        actor.observe_ingress(
            h, wire.Command.prepare, body, self._prepare_frame(), 10
        )
        out = actor.inject(10)
        assert out and out[0][0] == ("client", 33)
        frame = out[0][1]
        fh, fc = wire.decode_header(frame)
        assert fc == wire.Command.reply
        with pytest.raises(ValueError):
            wire.decode(frame)  # stale body checksum: defended at decode

    def test_window_bounds_attacks(self):
        actor = self._actor(kinds={"equivocate"}, rate=1.0, window=(5, 10))
        frame = self._prepare_frame()
        assert len(actor.transform([(("replica", 2), frame)], 4)) == 1
        assert len(actor.transform([(("replica", 2), frame)], 7)) == 2
        assert len(actor.transform([(("replica", 2), frame)], 10)) == 1


# ---------------------------------------------------------------------------
# equivocation end to end: detected, repaired, never executed
# ---------------------------------------------------------------------------


class TestEquivocationContained:
    def test_small_cluster_survives_equivocation(self, tmp_path):
        cluster = make_cluster(
            tmp_path, seed=21, clients=2, requests=10,
            byzantine={
                "replica": 1, "kinds": {"equivocate", "corrupt"},
                "rate": 0.5, "window": (5, 2000),
            },
        )
        ok = cluster.run_until(
            lambda: cluster.clients_done() and cluster.converged(),
            max_ticks=60_000,
        )
        assert ok, "no convergence under equivocation"
        cluster.check_converged()
        cluster.check_conservation()
        attacked = sum(cluster._byz.attacks.values())
        assert attacked > 0, "the schedule never attacked"
        # Corrupt frames were rejected at decode; any equivocation that
        # landed was contained (auditor green by construction here).
        assert cluster.rejected_frames.get("body_checksum", 0) > 0


# ---------------------------------------------------------------------------
# open-loop generator
# ---------------------------------------------------------------------------


class TestOpenLoopGen:
    def test_deterministic_under_fixed_seed(self):
        a = OpenLoopGen(123, n_clients=8, hot_accounts=32, rate=1.0)
        b = OpenLoopGen(123, n_clients=8, hot_accounts=32, rate=1.0)
        assert a.total_requests == b.total_requests
        assert a.scripts == b.scripts  # byte-identical bodies + ticks

    def test_different_seeds_differ(self):
        a = OpenLoopGen(123, n_clients=8, hot_accounts=32, rate=1.0)
        c = OpenLoopGen(124, n_clients=8, hot_accounts=32, rate=1.0)
        assert a.scripts != c.scripts

    def test_zipf_skew_concentrates_on_hot_accounts(self):
        gen = OpenLoopGen(7, n_clients=8, hot_accounts=100, rate=1.0,
                          zipf_s=1.2)
        share = zipf_skew(gen)
        assert share > 0.3, (
            f"top-10% accounts take {share:.2f} of touches; uniform ~0.1"
        )

    def test_arrival_processes(self):
        for arrival in ("poisson", "uniform", "burst"):
            gen = OpenLoopGen(
                9, n_clients=4, hot_accounts=16, rate=0.5, arrival=arrival,
                horizon=800,
            )
            ticks = sorted(
                t for s in gen.scripts for t, _op, _b in s
            )
            assert ticks, arrival
            assert ticks[-1] < 800
            assert gen.total_requests > 10

    def test_mixed_operations_present(self):
        gen = OpenLoopGen(11, n_clients=8, hot_accounts=32, rate=1.5,
                          two_phase_rate=0.5, query_rate=0.3)
        ops = [op for s in gen.scripts for _t, op, _b in s]
        assert wire.Operation.create_accounts in ops
        assert wire.Operation.create_transfers in ops
        assert wire.Operation.lookup_accounts in ops
        # Two-phase second legs exist: a transfer row with a pending_id.
        has_resolve = False
        for s in gen.scripts:
            for _t, op, body in s:
                if op != wire.Operation.create_transfers:
                    continue
                rows = np.frombuffer(body, dtype=types.TRANSFER_DTYPE)
                if (rows["pending_id_lo"] != 0).any():
                    has_resolve = True
        assert has_resolve

    @pytest.mark.slow  # ~13s; runs whole in the ci integration tier
    def test_attach_drives_real_cluster(self, tmp_path):
        cluster = make_cluster(tmp_path, seed=31, clients=1, requests=2)
        gen = OpenLoopGen(31, n_clients=4, hot_accounts=16, rate=0.3,
                          horizon=400)
        ids = gen.attach(cluster)
        assert ids
        ok = cluster.run_until(
            lambda: cluster.clients_done() and cluster.converged(),
            max_ticks=60_000,
        )
        assert ok
        done = sum(cluster.clients[c].requests_done for c in ids)
        assert done == gen.total_requests
        # Open-loop latency accounting recorded arrival->reply samples.
        assert any(cluster.clients[c].queue_latencies for c in ids)


# ---------------------------------------------------------------------------
# the pinned VOPR proof (slow: full 6-replica run, on + off)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestVoprByzantine:
    def test_pinned_seed_defended_passes(self):
        from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_byzantine_seed

        r = run_byzantine_seed(42, ticks=2_600)
        assert r.exit_code == EXIT_PASSED, r.reason
        assert sum(r.attacks.values()) > 0
        assert r.rejected.get("body_checksum", 0) > 0
        assert r.rejected.get("impersonation", 0) > 0
        assert r.equivocations_detected > 0
        assert r.openloop_requests > 0

    def test_pinned_seed_no_verify_fails_safety(self):
        from tigerbeetle_tpu.sim.vopr import (
            EXIT_CORRECTNESS, run_byzantine_seed,
        )

        r = run_byzantine_seed(42, ticks=2_600, verify=False)
        assert r.exit_code == EXIT_CORRECTNESS, (
            f"verification off must fail the safety oracle: {r.reason}"
        )
