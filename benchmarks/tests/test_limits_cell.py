"""The cell `hot-limits-s8`: where it is listed, the arithmetic of its mix
reckoned from its two files (ops against the checkpoint interval, index
levels, rows against slots, the clock before the cap, bytes), a CPU
rehearsal of the same mix at 30 customers a district and 64 events a request
(`cpu_cell.py`), the reference with the limit check taken out reading not
correct on the same plan, and its four per-layer readers on known
arithmetic, None where there is nothing to read."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.generators import tpcc_payment  # noqa: E402
from benchmarks.harness import check, hazard_bytes_model  # noqa: E402
from benchmarks.harness import bytes_model  # noqa: E402
from benchmarks.harness.drive import Sent  # noqa: E402
from benchmarks.reference import ledger as reference  # noqa: E402

CELL = "hot-limits-s8"
OLDER = ("default-plain-s8", "twophase-resolve-s8", "default-plain-shard4",
         "twophase-resolve-shard4", "bigstate-s8")
NEW_READERS = ("rejected_lane_pct", "seq_in_window",
               "waves_unscheduled_pct", "hazard_roofline")
ALSO = ("general_kernel_ms", "general_commit_ms", "general_sync_ms",
        "general_passes", "blocking_commit_ms", "index_ms_per_batch",
        "kernel_ms_per_batch")
# Spans of the deferred routes (a lone fast request's read-back, a lane
# closure and its join): every request here commits on the blocking general
# route, the spans never close in the window and their readers return nothing.
SILENT = ("readback_wait_ms", "lane_closure_ms", "lane_join_ms")
CHECKPOINT_OPS = 983             # config.py vsr_checkpoint_interval
LOOKUP_MAX = 8190


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return _load("BENCHMARK.json")


@pytest.fixture(scope="module")
def cell(bench):
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (listed,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    return {"entry": entry, "listed": listed, "config": _load(listed["file"]),
            "mix": _load("benchmarks", "traffic", entry["traffic"] + ".json")}


# -- where it is listed ------------------------------------------------------------

def test_the_cell_is_listed_where_its_traced_run_reads(bench, cell):
    """Membership only: a later cell or metric appended leaves this as it
    is."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert CELL in listed[name]["workloads"], name
        assert listed[name]["moves"] == "accepted_tx_s"
    for name in ALSO:
        assert CELL in listed[name]["workloads"], name
    for name, metric in listed.items():
        if all(c in metric["workloads"] for c in OLDER):
            assert (CELL in metric["workloads"]) == (name not in SILENT), name
    # Bytes of a resolving lane, a fast request's probe, and a counter that
    # a run without a checkpoint never has: nothing to read in this cell.
    for name in ("general_roofline", "commit_roofline", "probe_trips",
                 "checkpoints_in_window"):
        assert CELL not in listed[name]["workloads"], name
    assert not [n for n, m in listed.items()
                if n.startswith("shard_") and CELL in m["workloads"]]
    assert listed["hazard_roofline"]["unit"] == "%"
    assert listed["hazard_roofline"]["source"] == "device_trace"
    assert listed["hazard_roofline"]["layer"] == "kernels"
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert cell["entry"]["chips"] == cell["config"]["chips"] == 1
    assert cell["entry"]["traffic"] == "tpcc-payment-limits-s8"
    assert cell["listed"]["reduced"] == list(cell["config"]["reduced"]) == [
        "warehouses", "transfer_count"]
    assert "clause 2.5" in cell["listed"]["source"]
    assert "debits_must_not_exceed_credits" in cell["listed"]["source"]


def test_guarantees_and_shapes(cell, bench):
    control = _load("benchmarks", "configs", "tb-default-1r.json")
    mine, mix = cell["config"], cell["mix"]
    for key in ("consistency", "durability", "replicas", "acknowledgements"):
        assert mine["guarantees"][key] == control["guarantees"][key]
    assert mine["server_args"] == [
        "--no-engine", "--cache-accounts-log2", "21",
        "--cache-transfers-log2", "24"]
    assert mine["events_per_request"] == 8190 == mix["batch"]
    assert (mix["warehouses"], mix["districts_per_warehouse"],
            mix["customers_per_district"], mix["nurand_a"]) == (
        8, 10, 3000, 1023)
    assert mix["sessions"] == 8 and mix["payment_pct"] == 75
    assert mix["payment_amount"] == [100, 500_000]
    assert mix["topup_amount"] == [300, 1_500_000]
    # The net flow is zero: 75 x a payment's mean = 25 x a top-up's.
    assert 75 * sum(mix["payment_amount"]) == 25 * sum(mix["topup_amount"])
    assert mix["allowed_codes"] == [0, 54]
    assert mine["accounts"] == tpcc_payment.counts(mix)[2] == 240_081
    assert {"one_transfer_a_payment", "home_warehouse_only",
            "customer_by_number", "amounts", "top_ups_and_opening_balance",
            "sessions", "events_per_request", "table_sizes",
            "window"} <= set(mine["assumed"])
    assert f"{mix['opening_balance']:,}" in mine["assumed"][
        "top_ups_and_opening_balance"]
    sources = {c["source"] for c in bench["configs"]}
    assert len(sources) == len(bench["configs"])


# -- the mix's own arithmetic ------------------------------------------------------

def test_no_checkpoint_no_new_level_no_growth_and_the_clock_ends_the_window(
        cell, bench):
    mix, config = cell["mix"], cell["config"]
    sessions, batch = mix["sessions"], mix["batch"]
    plan_sizes = tpcc_payment.counts(mix)
    per_session = 30_000 + 10 + 1                  # session 0 has the bank
    account_requests = sessions * -(-per_session // batch)
    funding_requests = sessions * -(-30_000 // batch)
    preload = sessions * mix["preload_per_session"]
    window = sessions * mix["window_cap_per_session"]
    assert (account_requests, funding_requests, preload, window) == (
        32, 32, 512, 384)
    at_open = sessions + account_requests + funding_requests + preload
    at_close = at_open + window
    assert (at_open, at_close) == (584, 968)
    assert at_close < CHECKPOINT_OPS               # no checkpoint is due
    lookups = -(-plan_sizes[2] // LOOKUP_MAX) + 1
    assert lookups == 31                           # the read-back crosses it
    # The index: set-up's 544 create_transfers requests fill level 9 at the
    # 512th; 928 < 1,024 at the cap; the one carry inside is level 8's.
    before = funding_requests + preload
    assert before == 544 >= 1 << 9
    assert before + window == 928 < 1 << 10
    carries = [k for k in range(before + 1, before + window + 1)
               if k % 256 == 0]
    assert carries == [768] and 768 - before == 224
    # Rows against slots: nothing grows (tables double at load 0.5).
    tables = config["tables"]
    slots = 1 << tables["transfers_slots_log2"]
    at_open_rows = plan_sizes[1] + preload * batch
    rows = at_open_rows + window * batch
    assert at_open_rows == tables["transfer_rows_at_window_open"] == 4_433_280
    assert rows == tables["transfer_rows_at_cap"] == 7_578_240
    assert rows <= 928 * batch == 7_600_320
    assert rows < slots // 2 == tables["transfers_grow_at_rows"] == 8_388_608
    assert (funding_requests + 512) * batch > (1 << 23) // 2   # 2^23 grows
    assert tables["transfers_load_at_cap"] == round(rows / slots, 3)
    slots = 1 << tables["accounts_slots_log2"]
    assert tables["account_rows"] == plan_sizes[2] < slots // 2
    assert tables["accounts_load"] == round(plan_sizes[2] / slots, 3)
    # The clock ends the window while the cell runs under this rate.
    seconds = bench["run_seconds"]
    assert seconds == 40
    assert window * batch / seconds == 78_624
    for number in ("584", "968", "983", "544", "928", "1,024", "768", "224",
                   "78,624", "7,578,240", "8,388,608"):
        assert number in mix["why"] or number in config["assumed"][
            "window"] or number in config["assumed"]["table_sizes"], number


def test_the_bytes_reckoned_are_the_slots_times_the_row(cell):
    config = cell["config"]
    reckoned, row = config["memory_bytes_reckoned"], {
        "accounts": 129, "transfers": 133, "posted": 21}
    assert reckoned["slot_bytes"] == row
    accounts = (1 << 21) * row["accounts"]
    transfers = (1 << 24) * row["transfers"]
    posted = (1 << 16) * row["posted"]
    index = 8192 * ((1 << 10) - 1) * 5 * 8 * 2
    assert reckoned["accounts_table"] == accounts
    assert reckoned["transfers_table"] == transfers
    assert reckoned["posted_table"] == posted
    assert reckoned["index_levels_0_to_9_both_sides"] == index
    resident = accounts + transfers + posted + index
    assert reckoned["resident_before_temporaries"] == resident
    assert reckoned["share_of_one_chip"] == round(resident / 16e9, 3)
    assert 0.15 * 16e9 < resident < 0.3 * 16e9


# -- the rehearsal ---------------------------------------------------------------

# The window is 12 requests a session, not the issue's 3: the traced run's
# profiler opens at half the window and closes a cycle before its end, and 4
# requests of 64 lanes between the two are over before a CPU's profiler has
# started (the run then fails for a trace with no device operation).  And
# the cell's own index rule, three levels lower: 40 funding + 88 preloaded
# requests fill level 7 at the 128th, inside set-up; 224 < 256 at the cap.
SMALL = {"customers_per_district": 30, "nurand_a": 7, "batch": 64,
         "preload_per_session": 11, "window_cap_per_session": 12,
         "opening_balance": 300_000, "lookup_sample": 400}


def _small_mix():
    return dict(_load("benchmarks", "traffic",
                      "tpcc-payment-limits-s8.json"), **SMALL)


@pytest.fixture(scope="module")
def small_copy(tiny_copy, tmp_path_factory):
    """The cell's own mix at W = 8, 30 customers a district and 64 events a
    request (2,481 accounts; 2,400 + 184 x 64 = 14,176 rows at most, under
    half of 2^15 slots), added as files and entries to a copy of the
    rehearsals' copy."""
    tmp = str(tmp_path_factory.mktemp("bench_limits"))
    shutil.copytree(tiny_copy, tmp, symlinks=True, dirs_exist_ok=True)
    config = _load("benchmarks", "configs", "tb-limits-1r.json")
    config.update(name="small-limits", server_args=[
        "--no-engine", "--cache-accounts-log2", "13",
        "--cache-transfers-log2", "15"])
    with open(os.path.join(tmp, "benchmarks/configs/small-limits.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(tmp, "benchmarks/traffic/tpcc-small.json"),
              "w") as f:
        json.dump(_small_mix(), f)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "small-limits", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/small-limits.json"})
    bench["workloads"].append({
        "name": "small-limits-s8", "config": "small-limits",
        "traffic": "tpcc-small", "chips": 1, "why": "test"})
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"]:
            metric["workloads"].append("small-limits-s8")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def test_the_small_twin_ends_correct_with_refusals_and_cascades(small_copy):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    done = subprocess.run(
        [sys.executable,
         os.path.join(small_copy, "benchmarks/tests/cpu_cell.py"),
         small_copy, "small-limits-s8", "4400000041", "20", "1"],
        cwd=small_copy, env=env, capture_output=True, text=True,
        timeout=1500)
    assert done.returncode == 0, done.stderr[-4000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert all(value == 0 for value, limit in out["numbers"].values()
               if limit is not None)
    assert out["attempted"] == 8 * SMALL["window_cap_per_session"]
    numbers = out["numbers"]
    assert numbers["account_rows_compared"][0] == 2_481
    # 311 accounts and 300 opening balances a session are 5 requests of 64
    # each: 40 + 40, then 88 preloaded and the window's 96.
    assert numbers["requests_compared"][0] == 80 + 88 + out["attempted"]
    routes = out["observations"]["window_routes"]
    assert routes["general"] == out["attempted"]
    assert routes["fast"] == routes["grouped"] == routes["sequential"] == 0
    layer = out["per_layer"]
    assert 0 < layer["rejected_lane_pct"] < 50
    assert layer["general_passes"] > 1
    assert layer["seq_in_window"] == 0
    # ~5 payment legs a district slot here: some batches get a proved
    # bound; at 8190 events (~614 legs a slot) none can.
    assert 0 < layer["waves_unscheduled_pct"] <= 100.0
    assert layer["dispatches_per_batch"] == 1.0
    assert layer["compiles_in_window"] == 0
    assert "checkpoints_in_window" not in layer    # no capture, no counter
    assert "hazard_roofline" not in layer          # no peaks off a TPU
    # The client's count of accepted events is the server's.
    accepted = out["end_to_end"]["accepted_tx_s"] * out["observations"][
        "window_seconds"]
    lanes = out["attempted"] * SMALL["batch"]
    assert accepted == pytest.approx(
        lanes * (1 - layer["rejected_lane_pct"] / 100), rel=1e-6)
    bench = _load("BENCHMARK.json")
    # What lists the cell has to be in the line of a traced run; what the
    # deferred routes' spans would give is not there, and so is not listed.
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"] and metric["source"] != "device_trace":
            assert metric["name"] in layer, metric["name"]
    assert not set(SILENT) & set(layer)


class _NoLimits(reference.ReferenceLedger):
    """The reference with one guarantee broken: no account's limit is
    looked at."""

    def _apply(self, tid, dr, cr, *rest):
        kept = dr[reference._FLAGS]
        dr[reference._FLAGS] = 0
        try:
            return super()._apply(tid, dr, cr, *rest)
        finally:
            dr[reference._FLAGS] = kept


def test_the_reference_without_the_limit_check_is_not_correct():
    mix = _small_mix()
    plan = tpcc_payment.build(mix, 4400000041)
    counts = [len(q) for q in plan["window"]]
    sound, broken = reference.ReferenceLedger(), _NoLimits()
    answers = []
    for ledger in (sound, broken):
        setup = check.replay_setup(ledger, plan)
        window = check.replay_window(ledger, plan, counts)
        answers.append((setup, window,
                        ledger.lookup_accounts(plan["account_ids"])))
    (setup_a, window_a, rows_a), (setup_b, window_b, rows_b) = answers
    assert any(codes for queue in window_a for codes in queue)
    assert not any(codes for queue in window_b for codes in queue)
    differing = check._rows_differing(rows_b, rows_a)
    assert differing > 0
    assert rows_a["flags"].tolist() == rows_b["flags"].tolist()
    # Through the comparison that decides `correct`: codes and rows both.
    sent = []
    for s, queue in enumerate(window_b):
        for k, codes in enumerate(queue):
            sent.append(Sent(s, k, "create_transfers", mix["batch"], 0.0,
                             1.0, [tuple(c) for c in codes]))
    expected = {"setup": {}, "window": window_a, "accounts": rows_a,
                "transfers": rows_a[:0]}
    numbers = check.compare(expected, {}, sent, rows_b, rows_a[:0])
    assert numbers["account_rows_differing"] == (differing, 0)
    assert numbers["requests_with_wrong_codes"][0] > 0
    assert check.verdict(numbers) is False


# -- the four readers ------------------------------------------------------------

def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


@pytest.fixture
def run(cell):
    """A profiler window with four general executions, the first cut by its
    edge; a window of 10 requests of 8190 of which 4 % were refused."""
    ms = 1_000_000
    name = "jit_create_transfers_full_impl"
    executions = [[name, 0, 80 * ms, 0]]
    for k in range(3):
        executions.append([name, (100 + 100 * k) * ms, 80 * ms, 0])
        executions.append(["jit_build_runs", (185 + 100 * k) * ms, 2 * ms, 0])
    trace = {"device_span_ns": [0, 400 * ms], "executions": executions}
    before = {"counters": {"ops.general.lanes": 1_000,
                           "ops.general.rejected_lanes": 10,
                           "waves.batches_unscheduled": 3}, "gauges": {},
              "histograms": {}}
    after = {"counters": {"ops.general.lanes": 1_000 + 81_900,
                          "ops.general.rejected_lanes": 10 + 3_276,
                          "waves.batches_unscheduled": 3 + 9,
                          "waves.batches_scheduled": 1}, "gauges": {},
             "histograms": {}}
    refused = [(i, 54) for i in range(8190 * 4 // 100)]
    window = [Sent(k % 8, k // 8, "create_transfers", 8190, 0.0, 1.0, refused)
              for k in range(10)]
    return {"snapshots": {"open": before, "close": after}, "trace": trace,
            "window": window, "mix": cell["mix"], "config": cell["config"],
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_readers_on_known_arithmetic(run):
    assert _read("rejected_lane_pct", run) == pytest.approx(4.0)
    assert _read("seq_in_window", run) == 0
    run["snapshots"]["close"]["counters"]["ops.sequential_batches"] = 2
    assert _read("seq_in_window", run) == 2
    assert _read("waves_unscheduled_pct", run) == pytest.approx(90.0)
    # Three whole executions of 80 ms; 327 of 8190 lanes refused.
    share = 327 / 8190
    lane = share * 204 + (1 - share) * 400
    assert _read("hazard_roofline", run) == pytest.approx(
        100 * 3 * 8190 * lane / 819e9 / 0.240)
    assert _read("hazard_roofline", run) < 0.02    # far under a roofline
    assert _read("general_kernel_ms", run) == pytest.approx(80.0)


def test_the_bytes_of_a_lane_are_written_out():
    assert hazard_bytes_model.refused_lane_bytes() == 204
    assert hazard_bytes_model.accepted_lane_bytes() == 400 == (
        bytes_model.fast_lane_bytes())
    assert hazard_bytes_model.batch_bytes(8190, 0.0) == 8190 * 400
    assert hazard_bytes_model.batch_bytes(100, 0.5) == 50 * 204 + 50 * 400


@pytest.mark.parametrize("name", NEW_READERS)
def test_none_where_there_is_nothing_to_read(run, name):
    """A parent without the counters; a window with no general batch; no
    trace; a mix of resolving lanes; no peaks."""
    for snap in run["snapshots"].values():
        snap["counters"] = {}
    run["trace"]["executions"] = [
        e for e in run["trace"]["executions"] if "full" not in e[0]]
    if name == "seq_in_window":       # a counter that never moved reads 0
        assert _read(name, run) == 0
        return
    assert _read(name, run) is None
    run["trace"] = None
    assert _read(name, run) is None
    if name == "hazard_roofline":
        run["mix"] = dict(run["mix"], resolve={"post_pct": 80, "void_pct": 15})
        assert _read(name, run) is None
