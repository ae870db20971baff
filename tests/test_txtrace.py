"""Causal tracing + attribution + flight recorder (obs/txtrace.py).

Covers the three coupled pieces of the tracing layer (docs/tracing.md):
flow sampling/emission (trace ids riding the wire's carved header bytes,
hops across replica pid rows), the commit-stage attribution ledger
(stage sums must reconcile against measured wall time on the serial
path), and the bounded blackbox ring (overwrite semantics, postmortem
dumps, VOPR failing seeds carrying per-replica history).
"""

import json
import threading
import time

import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import (
    REPLICA_PID_BASE,
    STAGES,
    Blackbox,
    dump_blackboxes,
    parse_sample,
    txtrace,
)
from tigerbeetle_tpu.utils.tracer import tracer


@pytest.fixture
def json_tracer():
    """Enable the host tracer for a test, always restore + drain after
    (tracer and txtrace are process-global singletons)."""
    prev = tracer.backend
    tracer.enable("json")
    tracer.drain()
    try:
        yield tracer
    finally:
        tracer.backend = prev
        tracer.drain()


# -- sampling ----------------------------------------------------------------


def test_parse_sample_grammar():
    assert parse_sample("") == 0
    assert parse_sample("0") == 0
    assert parse_sample("1/64") == 64
    assert parse_sample("64") == 64
    assert parse_sample(" 1/8 ") == 8
    # Malformed values read as off, never raise (server import path).
    assert parse_sample("banana") == 0
    assert parse_sample("2/64") == 0
    assert parse_sample("1/") == 0


def test_maybe_trace_counter_sampling():
    with txtrace.sampling_scope(every=3):
        ids = [txtrace.maybe_trace(key=7) for _ in range(9)]
    # Every third request is traced, the rest ride the legacy wire.
    assert sum(1 for t in ids if t) == 3
    assert all(t == 0 for i, t in enumerate(ids) if (i + 1) % 3)
    traced = [t for t in ids if t]
    assert len(set(traced)) == len(traced)  # fresh id per sample
    assert all(0 < t < 1 << 64 for t in traced)


def test_sampling_off_is_zero_and_scope_restores():
    prev = txtrace.sample_every
    with txtrace.sampling_scope(every=0):
        assert txtrace.maybe_trace() == 0
        assert not txtrace.sampling
    assert txtrace.sample_every == prev


# -- flow emission -----------------------------------------------------------


def test_hop_noop_untraced_or_tracer_off(json_tracer):
    txtrace.hop(0, "client.request", phase="start")  # untraced frame
    assert json_tracer.drain() == []
    json_tracer.backend = "none"
    txtrace.hop(12345, "client.request", phase="start")  # tracer off
    json_tracer.enable("json")
    assert json_tracer.drain() == []


def test_hop_emits_slice_plus_flow_on_replica_pid(json_tracer):
    trace = 0xDECAF
    txtrace.hop(trace, "client.request", phase="start", request=3)
    txtrace.hop(trace, "replica.prepare", phase="step", replica=1, op=9)
    txtrace.hop(trace, "client.reply", phase="end")
    events = json_tracer.drain()
    slices = [e for e in events if e.get("cat") == "txtrace"]
    flows = [e for e in events if e.get("cat") == "txflow"]
    assert [e["name"] for e in slices] == [
        "client.request", "replica.prepare", "client.reply",
    ]
    # Every slice is bound to the chain by the trace id in its args.
    assert all(int(e["args"]["trace"], 16) == trace for e in slices)
    # The flow arrows: one s, one t, one f (terminated), same id.
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert all(e["id"] == trace for e in flows)
    assert flows[-1]["bp"] == "e"
    # Replica hops land on the synthetic per-replica process row.
    assert slices[1]["pid"] == REPLICA_PID_BASE + 1
    assert slices[0]["pid"] != slices[1]["pid"]


def test_span_records_real_duration(json_tracer):
    with txtrace.span(77, "replica.execute", replica=0):
        time.sleep(0.002)
    events = json_tracer.drain()
    sl = [e for e in events if e.get("cat") == "txtrace"]
    assert len(sl) == 1 and sl[0]["dur"] >= 1_000  # >= 1 ms in us


# -- attribution -------------------------------------------------------------


def test_stage_ledger_reconciles_against_wall():
    with txtrace.attribution_scope():
        t0 = time.perf_counter_ns()
        for _ in range(3):
            with txtrace.stage("wal_fsync"):
                time.sleep(0.004)
        with txtrace.stage("device_execute"):
            time.sleep(0.006)
        wall_us = (time.perf_counter_ns() - t0) / 1e3
        totals = txtrace.stage_totals()
    assert totals["wal_fsync"]["count"] == 3
    assert totals["device_execute"]["count"] == 1
    attributed = sum(v["us"] for v in totals.values())
    # The serial path: stage sums reconcile against measured wall time.
    assert attributed == pytest.approx(wall_us, rel=0.10)
    assert set(totals) <= set(STAGES)


@pytest.mark.parametrize("site", [
    dict(name="device_execute"),
    dict(name="prepare", n=3),
    dict(name="readback", seq=9),
    dict(name="reply_release", seq=4, n=8),
    dict(name=None),
])
def test_stage_free_when_inactive(monkeypatch, site):
    """Inactive, every site gets the SAME no-op, whatever it passes: no
    clock read, nothing allocated, no annotation opened."""
    from tigerbeetle_tpu.obs import txtrace as txtrace_mod

    assert not txtrace.active
    reads = []

    class Clock:
        def __getattr__(self, name):
            reads.append(name)
            return getattr(time, name)

    monkeypatch.setattr(txtrace_mod, "time", Clock())
    monkeypatch.setattr(
        txtrace_mod, "_StageSpan",
        lambda *a: pytest.fail("an inactive stage built a span"))

    class NoThreadState:
        def __getattr__(self, name):
            pytest.fail(f"an inactive stage read the thread's {name}")

    monkeypatch.setattr(txtrace_mod, "_thread", NoThreadState())
    off = txtrace.stage(**site)
    assert off is txtrace.stage("grow") is txtrace_mod._STAGE_OFF
    with off:
        with txtrace.stage(**site):  # re-entrant
            pass
    assert reads == []
    monkeypatch.undo()
    txtrace.stage_observe("readback", 123.0)  # guard is the CALLER's job
    with txtrace.attribution_scope() as t:  # reset=True clears any residue
        assert t.stage_totals() == {}
        # stage(None): a site with no stage to bill stays free when active.
        assert txtrace.stage(None) is off


def test_stage_nests_and_bills_each_name_once():
    with txtrace.attribution_scope():
        with txtrace.stage("device_execute", seq=3):
            with txtrace.stage("grow", seq=3):
                time.sleep(0.002)
            with txtrace.stage("dispatch", seq=3, n=2):
                time.sleep(0.002)
        totals = txtrace.stage_totals()
    assert {k: v["count"] for k, v in totals.items()} == {
        "device_execute": 1, "grow": 1, "dispatch": 1}
    assert totals["device_execute"]["us"] >= (
        totals["grow"]["us"] + totals["dispatch"]["us"])
    assert set(totals) <= set(STAGES)
    # A sum that wants wall time takes the self times: on one thread they
    # add up to the top-level span, and a parent's leaves its children out.
    assert sum(v["self_us"] for v in totals.values()) == pytest.approx(
        totals["device_execute"]["us"], abs=0.5)
    assert totals["device_execute"]["self_us"] == pytest.approx(
        totals["device_execute"]["us"] - totals["grow"]["us"]
        - totals["dispatch"]["us"], abs=0.5)
    assert totals["grow"]["self_us"] == totals["grow"]["us"] >= 2000


def _self_counters(snapshot):
    prefix = "txtrace.self_us."
    return {name[len(prefix):]: value
            for name, value in snapshot["counters"].items()
            if name.startswith(prefix)}


def test_self_times_of_one_thread_sum_to_its_top_level_spans():
    """`stage_h2d` is top-level on the grouped route and a child on the
    blocking ones: either way its time is counted once."""
    with registry.enabled_scope(), txtrace.attribution_scope():
        with txtrace.stage("commit_group"):
            with txtrace.stage("stage_h2d"):          # top-level staging
                time.sleep(0.002)
            with txtrace.stage("device_execute"):
                with txtrace.stage("general_commit"):
                    with txtrace.stage("stage_h2d"):  # the route's own
                        time.sleep(0.003)
                    with txtrace.stage("full_sync"):
                        time.sleep(0.001)
            time.sleep(0.001)
        with txtrace.stage("reply_release"):
            time.sleep(0.001)
        totals = txtrace.stage_totals()
        snapshot = registry.snapshot()
    top = totals["commit_group"]["us"] + totals["reply_release"]["us"]
    assert sum(v["self_us"] for v in totals.values()) == pytest.approx(
        top, abs=1.0)
    assert totals["stage_h2d"]["count"] == 2
    assert totals["stage_h2d"]["self_us"] == totals["stage_h2d"]["us"] >= 5000
    # A span's self time leaves its children out.  A sleep lasts at least
    # what it was asked for and, on a loaded machine, milliseconds more: the
    # children's sleeps bound a self time from above, never to +-0.9 ms.
    general, group = totals["general_commit"], totals["commit_group"]
    assert 0 <= general["self_us"] <= general["us"] - 3000 - 1000
    assert 1000 <= group["self_us"] <= group["us"] - 2000 - 3000 - 1000
    # The registry keeps the same self times, by the thread's role (this
    # one: any thread that no pool named is `serving`), whole microseconds.
    selfs = _self_counters(snapshot)
    assert set(selfs) == {"serving." + name for name in totals}
    for name, v in totals.items():
        assert selfs["serving." + name] == pytest.approx(
            v["self_us"], abs=v["count"])
    # `txtrace.stage.<name>` holds what it held: durations, one a span.
    assert snapshot["histograms"]["txtrace.stage.stage_h2d"]["count"] == 2
    assert snapshot["counters"]["serve.busy_us"] == pytest.approx(top, abs=2)


def test_an_exception_unwinds_the_span_stack():
    from tigerbeetle_tpu.obs import txtrace as txtrace_mod

    with txtrace.attribution_scope():
        with pytest.raises(RuntimeError):
            with txtrace.stage("commit_group"):
                with txtrace.stage("prepare"):
                    time.sleep(0.001)
                    raise RuntimeError("mid-span")
        assert txtrace_mod._thread.stack == []
        with txtrace.stage("reply_release"):  # a new top-level span
            time.sleep(0.001)
        totals = txtrace.stage_totals()
    assert {k: v["count"] for k, v in totals.items()} == {
        "commit_group": 1, "prepare": 1, "reply_release": 1}
    assert totals["commit_group"]["self_us"] == pytest.approx(
        totals["commit_group"]["us"] - totals["prepare"]["us"], abs=0.5)
    assert totals["reply_release"]["self_us"] == totals["reply_release"]["us"]


@pytest.mark.parametrize("thread_name, role", [
    ("tb-dispatch_0", "lane"),
    ("tb-wal-fsync_0", "io"),
    ("tb-checkpoint", "checkpoint"),
    ("Thread-7 (serve)", "serving"),
])
def test_a_span_lands_under_its_threads_role(thread_name, role):
    """The same name on two threads: one histogram of durations as before,
    the self time by role, and for `device_execute` alone (a deferred
    closure against a blocking commit) the duration by role too."""
    def work():
        with txtrace.stage("device_execute", seq=5):
            with txtrace.stage("dispatch", seq=5):
                time.sleep(0.001)
            with txtrace.stage("merkle_refresh", seq=5):
                pass

    with registry.enabled_scope():
        worker = threading.Thread(target=work, name=thread_name)
        worker.start()
        worker.join()
        work()                      # and here, on the main thread
        snapshot = registry.snapshot()
    histograms = snapshot["histograms"]
    roles = {role, "serving"}
    both = 2 if role == "serving" else 1
    assert set(_self_counters(snapshot)) == {
        f"{r}.{name}" for r in roles
        for name in ("device_execute", "dispatch", "merkle_refresh")}
    by_role = sorted(n for n in histograms if n.count(".") == 3)
    assert by_role == sorted(
        f"txtrace.stage.device_execute.{r}" for r in roles)
    assert histograms["txtrace.stage.dispatch"]["count"] == 2
    assert histograms["txtrace.stage.device_execute"]["count"] == 2
    assert histograms[
        f"txtrace.stage.device_execute.{role}"]["count"] == both
    assert sum(histograms[f"txtrace.stage.device_execute.{r}"]["sum"]
               for r in roles) == pytest.approx(
        histograms["txtrace.stage.device_execute"]["sum"])


def test_device_wait_counts_the_serving_threads_own_sleep_only():
    """Device wait is the SELF time of `dispatch_wait`, `readback` and
    `full_sync` on role `serving` (`serving_work_pct` sums those three
    counters): a lane thread's lands under its own role, a child's time is
    not counted twice, and the selector's span is neither busy nor wait."""
    def lane():
        with txtrace.stage("readback"):
            time.sleep(0.002)

    with registry.enabled_scope(), txtrace.attribution_scope():
        with txtrace.stage("pipeline_flush"):
            with txtrace.stage("dispatch_wait"):
                time.sleep(0.002)
            with txtrace.stage("readback"):
                time.sleep(0.001)
            with txtrace.stage("phase_b"):
                time.sleep(0.001)
        with txtrace.stage("full_sync"):
            with txtrace.stage("grow"):        # not a wait: left out
                time.sleep(0.002)
            time.sleep(0.001)
        totals = txtrace.stage_totals()
        worker = threading.Thread(target=lane, name="tb-dispatch_0")
        worker.start()
        worker.join()
        busy = registry.snapshot()["counters"]["serve.busy_us"]
        txtrace.stage_observe("loop_wait", 1500.0)
        counters = registry.snapshot()["counters"]
    selfs = _self_counters({"counters": counters})
    wait = sum(selfs["serving." + name]
               for name in ("dispatch_wait", "readback", "full_sync"))
    want = (totals["dispatch_wait"]["us"] + totals["readback"]["us"]
            + totals["full_sync"]["self_us"])
    assert wait == pytest.approx(want, abs=3)
    assert 4000 <= wait < totals["full_sync"]["us"] + (
        totals["dispatch_wait"]["us"] + totals["readback"]["us"])
    assert selfs["lane.readback"] >= 2000
    assert selfs["serving.loop_wait"] == 1500
    assert counters["serve.busy_us"] == busy == int(
        totals["pipeline_flush"]["us"])
    # No second series says what the self times say already.
    assert {n for n in counters if n.startswith("serve.")} == {
        "serve.busy_us"}


def test_machine_commit_bills_device_execute():
    cfg = LedgerConfig(
        accounts_capacity_log2=8, transfers_capacity_log2=10,
        posted_capacity_log2=8,
    )
    m = TpuStateMachine(cfg, batch_lanes=16)
    accounts = types.accounts_array(
        [types.account(id=i + 1, ledger=1, code=10) for i in range(4)]
    )
    assert m.create_accounts(accounts, wall_clock_ns=1000) == []
    batch = types.transfers_array([
        types.transfer(id=100 + i, debit_account_id=1 + i % 4,
                       credit_account_id=1 + (i + 1) % 4, amount=5,
                       ledger=1, code=10)
        for i in range(8)
    ])
    m.commit_batch("create_transfers", batch, timestamp=2_000)  # warm up
    with txtrace.attribution_scope():
        t0 = time.perf_counter_ns()
        batch2 = types.transfers_array([
            types.transfer(id=200 + i, debit_account_id=1 + i % 4,
                           credit_account_id=1 + (i + 1) % 4, amount=5,
                           ledger=1, code=10)
            for i in range(8)
        ])
        m.commit_batch("create_transfers", batch2, timestamp=3_000)
        wall_us = (time.perf_counter_ns() - t0) / 1e3
        totals = txtrace.stage_totals()
    # The whole blocking commit routes through ONE device_execute stage
    # block (XLA-CPU executes the jitted call synchronously inside it).
    assert totals["device_execute"]["count"] == 1
    assert 0 < totals["device_execute"]["us"] <= wall_us * 1.05


# -- blackbox ----------------------------------------------------------------


def test_blackbox_ring_overwrites_oldest():
    box = Blackbox("r0", cap=8)
    for i in range(20):
        box.record("prepare", op=i)
    assert box.seq == 20
    snap = box.snapshot()
    assert len(snap) == 8
    assert [e["seq"] for e in snap] == list(range(12, 20))
    assert [e["op"] for e in snap] == list(range(12, 20))
    text = box.dump_text()
    assert "20 events recorded, 8 retained (cap 8), 12 lost" in text
    # One JSON line per retained event after the header.
    lines = text.strip().split("\n")
    assert len(lines) == 9
    assert json.loads(lines[1])["seq"] == 12


def test_dump_blackboxes_writes_files(tmp_path):
    boxes = [Blackbox("r0", cap=4), None, Blackbox("r2", cap=4)]
    boxes[0].record("commit", op=1)
    boxes[2].record("view_change", view=2)
    paths = dump_blackboxes(boxes, str(tmp_path))
    assert [p.rsplit("/", 1)[1] for p in paths] == [
        "blackbox_r0.txt", "blackbox_r2.txt",
    ]
    body = (tmp_path / "blackbox_r2.txt").read_text()
    assert "view_change" in body and "# blackbox r2:" in body
    # Best-effort: unwritable directory yields no paths, never raises.
    assert dump_blackboxes(boxes, str(tmp_path / "missing" / "nested")) == []


# -- one request, one flow, across every replica -----------------------------

# Every member must appear in a state-machine request's flow, in causal
# order (client stamp -> consensus ingress -> kernel execution -> reply
# release -> client receipt).
EXPECTED_CHAIN = (
    "client.request", "consensus.ingress", "replica.prepare",
    "consensus.commit", "replica.execute", "replica.reply", "client.reply",
)


def test_cluster_request_is_one_flow_across_three_replicas(tmp_path,
                                                           json_tracer):
    from tigerbeetle_tpu.sim.cluster import SimCluster

    with txtrace.sampling_scope(every=1):
        sim = SimCluster(str(tmp_path), n_replicas=3, n_clients=2, seed=7)
        assert sim.run_until(sim.clients_done, max_ticks=20_000)
    events = sorted(
        (e for e in json_tracer.drain()
         if e.get("cat") in ("txtrace", "txflow")),
        key=lambda e: e["ts"],
    )
    chains = {}
    for e in events:
        if e["cat"] == "txtrace":
            chains.setdefault(int(e["args"]["trace"], 16), []).append(e)
    # Registers legitimately skip replica.execute: take the requests that
    # carry the whole chain, and of those the one on the most replicas.
    full = {
        t: evs for t, evs in chains.items()
        if set(EXPECTED_CHAIN) <= {e["name"] for e in evs}
    }
    assert full, sorted({e["name"] for evs in chains.values() for e in evs})
    trace, evs = max(full.items(), key=lambda kv: len(
        {e["pid"] for e in kv[1] if e["pid"] >= REPLICA_PID_BASE}))
    assert len({e["pid"] for e in evs if e["pid"] >= REPLICA_PID_BASE}) >= 3
    names = [e["name"] for e in evs]
    firsts = [names.index(n) for n in EXPECTED_CHAIN]
    assert firsts == sorted(firsts), list(zip(EXPECTED_CHAIN, firsts))
    # One arrow: it starts at the client's stamp and finishes once, at the
    # client's receipt (backups emit step hops after it).
    phases = [e["ph"] for e in events
              if e["cat"] == "txflow" and e["id"] == trace]
    assert phases[0] == "s" and phases.count("s") == 1, phases
    assert phases.count("f") == 1, phases


def test_sampling_every_request_serves_the_untraced_bytes(tmp_path,
                                                          json_tracer):
    """Every request sampled and the tracer recording: reply bodies,
    digest and balances equal the unsampled run's, and the sampled run
    did emit flow events."""
    from test_pipeline import ReplicaHarness, _mixed_stream

    def served(name):
        h = ReplicaHarness(str(tmp_path), name, 2, False)
        bodies, _, _ = _mixed_stream(h)
        out = bodies, h.r.machine.digest(), h.r.machine.balances_snapshot()
        h.close()
        return out

    off = served("unsampled")
    assert not any(e.get("cat") == "txflow" for e in json_tracer.drain())
    with txtrace.sampling_scope(every=1):
        assert served("sampled") == off
    assert any(e.get("cat") == "txflow" for e in json_tracer.drain())


# -- VOPR integration --------------------------------------------------------


def test_vopr_pinned_seed_green_with_tracing_on(tmp_path, json_tracer):
    """Tracing every request must not shift a pinned schedule: seed 1's
    3k-tick run (pinned green in test_vopr.py) stays green with the
    tracer recording and sampling at 1/1, and the run emits flow
    events across replica pid rows."""
    from tigerbeetle_tpu.sim.vopr import EXIT_PASSED, run_seed

    with txtrace.sampling_scope(every=1):
        result = run_seed(1, workdir=str(tmp_path), ticks=3_000)
    assert result.exit_code == EXIT_PASSED, result
    assert result.commits > 0
    events = json_tracer.drain()
    flows = [e for e in events if e.get("cat") == "txflow"]
    assert flows, "traced run emitted no flow events"
    replica_pids = {
        e["pid"] for e in events
        if e.get("cat") == "txtrace" and e["pid"] >= REPLICA_PID_BASE
    }
    assert len(replica_pids) >= 2  # chain crosses replica rows


@pytest.mark.parametrize("through", ["run_seed", "cli"])
def test_vopr_failing_seed_carries_blackboxes(tmp_path, monkeypatch, through):
    """A failing seed attaches every seat's flight-recorder dump, and the
    real CLI writes them next to the viz grid.  Forced cheaply: too few
    ticks to converge -> liveness failure."""
    from tigerbeetle_tpu import cli, jaxenv
    from tigerbeetle_tpu.sim import vopr

    run_seed = vopr.run_seed
    if through == "run_seed":
        result = run_seed(3, workdir=str(tmp_path), ticks=40, settle_ticks=1)
        assert result.exit_code != vopr.EXIT_PASSED
        boxes = result.blackboxes
    else:
        monkeypatch.setattr(vopr, "run_seed", lambda seed, **kw: run_seed(
            seed, **{**kw, "ticks": 40, "settle_ticks": 1}))
        # conftest pinned this process to the CPU: the CLI's own pin would
        # reset the backends under every later test of this worker.
        monkeypatch.setattr(jaxenv, "force_cpu", lambda n=None: None)
        monkeypatch.chdir(tmp_path)
        assert cli.main(["vopr", "--seed", "3", "--vopr-viz"]) != 0
        assert (tmp_path / "vopr_viz_3.txt").exists()
        boxes = {p.stem[len("blackbox_3_"):]: p.read_text()
                 for p in tmp_path.glob("blackbox_3_r*.txt")}
    assert boxes, "failing seed carried no blackbox dumps"
    for name, text in boxes.items():
        assert text.startswith(f"# blackbox {name}:")
        assert "events recorded" in text.splitlines()[0]
