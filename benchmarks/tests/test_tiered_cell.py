"""The cell `tiered-plain-s8`: where it is listed (by membership), the
arithmetic of its mix reckoned from its two files (the first eviction in
set-up, none and no capture inside ops 1,048-1,536, the index's levels, the
clock before the cap, bytes), a CPU rehearsal of the same mix at 64 events a
request and a hot window of 2^15 slots (`cpu_cell.py`) whose line holds every
listed non-device metric and none unlisted, a broken control (the reference
fed a tier that forgets its cold ids is not correct), and its six per-layer
readers on known arithmetic, None where there is nothing to read."""

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

from benchmarks.generators import ledger_mix, tiered_plain  # noqa: E402
from benchmarks.harness import check, evict_bytes_model  # noqa: E402
from benchmarks.harness.drive import Sent  # noqa: E402
from benchmarks.reference import ledger as reference  # noqa: E402

CELL, CONTROL = "tiered-plain-s8", "default-plain-s8"
NEW_READERS = {                  # name -> the end-to-end metric it moves
    "cold_redispatch_pct": "accepted_tx_s",
    "cold_false_positive_pct": "accepted_tx_s",
    "cold_resolve_ms": "accepted_tx_s",
    "evictions_in_window": "accepted_tx_s",
    "cold_evict_ms": "setup_s",
    "evict_roofline": "setup_s",
}
# The general route's own, and set-up's capture.
ALSO = ("general_kernel_ms", "general_commit_ms", "general_sync_ms",
        "general_passes", "blocking_commit_ms", "checkpoints_in_window",
        "checkpoint_capture_ms", "dispatches_per_batch", "compiles_in_window",
        "device_idle_pct")
# `harness/commit_programs.py` counts one `create_transfers_full` execution
# as one request; a request dispatched twice is two: both readers divide by
# that count and would under-read here by construction.  And the spans of
# the deferred routes, the limits' own four, the byte models of other lanes.
NOT_LISTED = ("kernel_ms_per_batch", "index_ms_per_batch",
              "readback_wait_ms", "lane_closure_ms", "lane_join_ms",
              "rejected_lane_pct", "seq_in_window", "waves_unscheduled_pct",
              "hazard_roofline", "general_roofline", "commit_roofline",
              "probe_trips")
CHECKPOINT_OPS = 983             # config.py vsr_checkpoint_interval


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


def _arg(config, name):
    args = config["server_args"]
    return int(args[args.index(name) + 1])


# -- where it is listed ------------------------------------------------------------

def test_the_cell_is_listed_where_its_traced_run_reads(bench, cell):
    """Membership only: a later cell or metric appended leaves this as it
    is."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, moves in NEW_READERS.items():
        assert listed[name]["workloads"] == [CELL] or (
            CELL in listed[name]["workloads"]), name
        assert listed[name]["moves"] == moves, name
    for name in ALSO:
        assert CELL in listed[name]["workloads"], name
    for name in NOT_LISTED:
        assert CELL not in listed[name]["workloads"], name
    assert not [n for n, m in listed.items()
                if n.startswith("shard_") and CELL in m["workloads"]]
    assert listed["evict_roofline"]["unit"] == "%"
    assert listed["evict_roofline"]["source"] == "program_span"
    assert listed["evict_roofline"]["layer"] == "kernels"
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    assert cell["entry"]["chips"] == cell["config"]["chips"] == 1
    assert cell["entry"]["traffic"] == "plain-tiered-s8"
    assert cell["listed"]["reduced"] == list(cell["config"]["reduced"]) == [
        "transfer_count", "hot_transfers_log2_max", "account_count"]
    assert "config 4" in cell["listed"]["source"]
    assert "--hot-transfers-log2-max" in cell["listed"]["source"]
    assert len({c["source"] for c in bench["configs"]}) == len(
        bench["configs"])
    for text in (cell["entry"]["why"], cell["listed"]["source"],
                 cell["listed"]["why"]):
        assert len(text) <= 200


def test_guarantees_and_shapes(cell):
    control = _load("benchmarks", "configs", "tb-default-1r.json")
    plain = _load("benchmarks", "traffic", "plain-s8.json")
    mine, mix = cell["config"], cell["mix"]
    for key in ("consistency", "read_back", "replicas", "acknowledgements"):
        assert mine["guarantees"][key] == control["guarantees"][key]
    assert mine["guarantees"]["durability"].startswith(
        control["guarantees"]["durability"])
    assert {"tier_read_back", "tier_ids"} <= set(mine["guarantees"])
    assert mine["server_args"] == [
        "--no-engine", "--cache-accounts-log2", "21",
        "--cache-transfers-log2", "24", "--hot-transfers-log2-max", "24",
        "--cold-bloom-log2", "29"]
    for key in ("accounts", "batch", "sessions", "amount_max",
                "lookup_sample"):
        assert mix[key] == plain[key], key
    assert mine["events_per_request"] == 8190 == mix["batch"]
    assert mine["accounts"] == mix["accounts"] == 10_000
    assert mix["generator"] == "tiered_plain"
    assert mix["allowed_codes"] == [0, tiered_plain.EXISTS]
    assert {"sessions", "eviction_fraction", "cold_bloom_bits", "retries",
            "retry_share_dispatched_twice_in_error"} <= set(mine["assumed"])


# -- the mix's own arithmetic ------------------------------------------------------

def test_one_eviction_in_setup_none_inside_and_the_clock_ends_the_window(
        cell, bench):
    mix, config = cell["mix"], cell["config"]
    sessions, batch = mix["sessions"], mix["batch"]
    tables = config["tables"]
    hot_slots = 1 << _arg(config, "--hot-transfers-log2-max")
    assert hot_slots == 1 << _arg(config, "--cache-transfers-log2") == (
        1 << tables["hot_transfers_slots_log2_max"])
    ceiling = hot_slots // 2                     # rows at load 0.5
    assert ceiling == tables["transfers_evict_at_rows"] == 8_388_608
    preload = sessions * mix["preload_per_session"]
    window = sessions * mix["window_cap_per_session"]
    assert (preload, window) == (1_032, 488)
    # The first eviction: in the growth check of the first request whose
    # rows would pass the ceiling (`machine._grow_if_needed`).
    first = next(k for k in range(1, preload + window + 1)
                 if k * batch > ceiling)
    assert first == 1_025 <= preload and (first - 1) * batch == 8_386_560
    live = (first - 1) * batch
    share = tables["eviction_fraction"]
    evicted = int(live * int(share * 1000) // 1000) + 1
    assert evicted == tables["cold_rows_after_the_first_eviction"] == 4_193_281
    hot = live - evicted
    assert hot == tables["hot_rows_after_the_first_eviction"] == 4_193_279
    # The retried sources are cold by then: the first 48 requests of every
    # session are among the first 512 requests' rows, which left.
    assert sessions * mix["retry_sources"] == 384 < evicted // batch == 512
    # The next one: `hot` rows, the requests since, and what the window's
    # retries rehydrate (128 rows each).
    retries = sessions * len(tiered_plain.retry_positions(mix))
    assert retries == 64
    rehydrated = retries * mix["retry_events"]
    assert rehydrated == 8_192
    second = next(k for k in range(first, 4_096)
                  if hot + rehydrated + (k - first + 1) * batch > ceiling)
    assert second == 1_536 and second - preload == 504 > window
    assert second - first == 511
    assert preload * batch == tables["transfer_rows_at_window_open"]
    assert (preload + window) * batch == tables["transfer_rows_at_cap"]
    # The index: level 10 is filled at the 1,024th request, in set-up; no
    # level 11; the highest carry inside the window is level 9's.
    assert preload >= 1 << 10 and preload + window == 1_520 < 1 << 11
    assert window < 1 << 10
    assert tables["index_levels_at_window_open"] == 11
    # Ops: 8 registers + 8 account requests + the preload at window open.
    accounts = sessions * -(-(-(-mix["accounts"] // sessions)) // batch)
    at_open = sessions + accounts + preload
    at_close = at_open + window
    assert (at_open, at_close) == (1_048, 1_536)
    assert CHECKPOINT_OPS < at_open            # set-up's capture is behind
    assert at_close < 2 * CHECKPOINT_OPS       # the next is not yet due
    # The clock ends the window while the cell runs under this rate.
    seconds = bench["run_seconds"]
    assert seconds == 40 and window * batch / seconds == 99_918
    retried_share = 100.0 * retries * mix["retry_events"] / (window * batch)
    assert 0.19 < retried_share < 0.21
    for number in ("1,032", "488", "1,025", "8,388,608", "1,520", "2,048",
                   "1,048", "1,536", "983", "1,966", "99,918", "511"):
        assert number in mix["why"] or number in config["assumed"][
            "window"], number


def test_the_bytes_reckoned_are_the_slots_times_the_row(cell):
    config = cell["config"]
    reckoned = config["memory_bytes_reckoned"]
    row = {"accounts": 129, "transfers": 133, "posted": 21}
    assert reckoned["slot_bytes"] == row
    accounts = (1 << 21) * row["accounts"]
    transfers = (1 << 24) * row["transfers"]
    posted = (1 << 16) * row["posted"]
    index = 8192 * ((1 << 11) - 1) * 5 * 8 * 2
    bloom = (1 << _arg(config, "--cold-bloom-log2")) // 8
    assert bloom == reckoned["cold_filter"] == 67_108_864
    assert reckoned["index_levels_0_to_10_both_sides"] == index
    resident = accounts + transfers + posted + index + bloom
    assert reckoned["resident_before_temporaries"] == resident
    assert reckoned["share_of_one_chip"] == round(resident / 16e9, 3)
    assert 3.5e9 < resident < 0.3 * 16e9
    # Bits an id, and the false positives a request of new ids then meets.
    ids = config["tables"]["cold_rows_after_the_first_eviction"]
    bits_an_id = (1 << 29) / ids
    assert 127 < bits_an_id < 129
    per_request = 8190 * (1 - 2.718281828 ** (-4 / bits_an_id)) ** 4
    assert per_request < 0.02                    # the acceptance's 2 %


# -- the generator ----------------------------------------------------------------

def test_a_retry_resends_its_sessions_old_events_byte_for_byte(cell):
    mix = dict(cell["mix"], batch=64, accounts=300, preload_per_session=50,
               window_cap_per_session=20, retry_events=16)
    plan = tiered_plain.build(mix, 4800000001)
    plain = ledger_mix.build(dict(mix, cycle=["plain"]), 4800000001)
    (preload,) = [p["queues"] for p in plan["setup"] if p["name"] == "preload"]
    positions = tiered_plain.retry_positions(mix)
    assert positions == [3, 11, 19]
    for s, queue in enumerate(plan["window"]):
        for at, (op, rows) in enumerate(queue):
            before = plain["window"][s][at][1]
            if at not in positions:
                assert rows.tobytes() == before.tobytes()
                continue
            source = preload[s][positions.index(at) % mix["retry_sources"]][1]
            assert rows[:16].tobytes() == source[:16].tobytes()
            assert rows[16:].tobytes() == before[16:].tobytes()
            assert op == "create_transfers" and len(rows) == 64
    # Through the reference: the retried events, and they alone, are 46.
    ledger = reference.ReferenceLedger()
    check.replay_setup(ledger, plan)
    codes = check.replay_window(ledger, plan, [20] * mix["sessions"])
    for queue in codes:
        for at, answered in enumerate(queue):
            want = [(i, 46) for i in range(16)] if at in positions else []
            assert answered == want


# -- the rehearsal ---------------------------------------------------------------

# 64 events a request and a hot window of 2^15 slots: it is past at 16,384
# rows = 256 requests.  33 preloaded a session are 264: the index's level 8
# is filled at the 256th, the first eviction falls at the 257th and takes
# 8,193 rows (the first 128 requests: 16 a session); the next is due 128
# requests later less what 24 retries rehydrate (384 rows = 6 requests),
# after the window's 96.
SMALL = {"accounts": 1000, "batch": 64, "preload_per_session": 33,
         "window_cap_per_session": 12, "retry_every": 4, "retry_first": 2,
         "retry_events": 16, "retry_sources": 12, "lookup_sample": 600}
SMALL_ARGS = ["--no-engine", "--cache-accounts-log2", "12",
              "--cache-transfers-log2", "15", "--hot-transfers-log2-max", "15",
              "--cold-bloom-log2", "20"]


def _small_mix():
    return dict(_load("benchmarks", "traffic", "plain-tiered-s8.json"),
                **SMALL)


@pytest.fixture(scope="module")
def small_copy(tiny_copy, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_tiered"))
    shutil.copytree(tiny_copy, tmp, symlinks=True, dirs_exist_ok=True)
    config = _load("benchmarks", "configs", "tb-tiered-1r.json")
    config.update(name="small-tiered", server_args=SMALL_ARGS)
    config["tables"] = dict(config["tables"], hot_transfers_slots_log2_max=15)
    with open(os.path.join(tmp, "benchmarks/configs/small-tiered.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(tmp, "benchmarks/traffic/tiered-small.json"),
              "w") as f:
        json.dump(_small_mix(), f)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "small-tiered", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/small-tiered.json"})
    bench["workloads"].append({
        "name": "small-tiered-s8", "config": "small-tiered",
        "traffic": "tiered-small", "chips": 1, "why": "test"})
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"]:
            metric["workloads"].append("small-tiered-s8")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def test_the_small_twin_ends_correct_and_its_line_holds_what_is_listed(
        small_copy):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    done = subprocess.run(
        [sys.executable,
         os.path.join(small_copy, "benchmarks/tests/cpu_cell.py"),
         small_copy, "small-tiered-s8", "4800000041", "30", "1"],
        cwd=small_copy, env=env, capture_output=True, text=True,
        timeout=1500)
    assert done.returncode == 0, done.stderr[-4000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert all(value == 0 for value, limit in out["numbers"].values()
               if limit is not None)
    assert out["attempted"] == 8 * SMALL["window_cap_per_session"] == 96
    numbers = out["numbers"]
    assert numbers["account_rows_compared"][0] == 1_000
    assert numbers["requests_compared"][0] == 16 + 264 + 96
    # Three quarters of the sample were created; of those about a third is
    # cold at read-back (8,193 of 23,040 rows) and read back equal.
    assert numbers["transfer_rows_compared"][0] >= 440
    routes = out["observations"]["window_routes"]
    assert routes["general"] == 96
    assert routes["fast"] == routes["grouped"] == routes["sequential"] == 0
    layer = out["per_layer"]
    assert layer["evictions_in_window"] == 0
    assert layer.get("checkpoints_in_window", 0) == 0   # no capture here:
    assert "checkpoint_capture_ms" not in layer         # 376 ops < 983
    assert layer["compiles_in_window"] == 0
    # 24 of 96 requests retry cold ids; a 2^20-bit filter over 8,193 ids
    # gives the others no false positive to speak of.
    assert layer["cold_redispatch_pct"] == pytest.approx(25.0, abs=1.1)
    assert layer["cold_false_positive_pct"] <= 2.0
    assert layer["dispatches_per_batch"] == pytest.approx(
        1 + layer["cold_redispatch_pct"] / 100)
    assert layer["cold_resolve_ms"] > 0 and layer["cold_evict_ms"] > 0
    assert "evict_roofline" not in layer          # no peaks off a TPU
    # The retried events were answered 46 and nothing else was refused.
    accepted = out["end_to_end"]["accepted_tx_s"] * out["observations"][
        "window_seconds"]
    assert accepted == pytest.approx(96 * 64 - 24 * 16, rel=1e-6)
    # What lists the cell is in the line of a traced run, and nothing that
    # does not list it (the copy's own metric aside).
    bench = _load("BENCHMARK.json")
    listing = {m["name"] for m in bench["per_layer"]
               if CELL in m["workloads"]}
    for metric in bench["per_layer"]:
        if metric["name"] in listing and metric["source"] != "device_trace" \
                and metric["name"] not in ("evict_roofline",
                                           "checkpoints_in_window",
                                           "checkpoint_capture_ms"):
            assert metric["name"] in layer, metric["name"]
    assert set(layer) <= listing


# -- the broken control ----------------------------------------------------------

class _ForgetsColdIds(reference.ReferenceLedger):
    """A tier with one guarantee broken: what it evicts it forgets, so an
    id that was acknowledged long ago is accepted again."""

    def evict(self, ids) -> None:
        for tid in ids:
            del self.transfers[tid]


def test_a_tier_that_forgets_its_cold_ids_is_not_correct():
    mix = dict(_small_mix(), preload_per_session=20,
               window_cap_per_session=8)
    plan = tiered_plain.build(mix, 4800000041)
    counts = [len(q) for q in plan["window"]]
    sound, broken = reference.ReferenceLedger(), _ForgetsColdIds()
    setup = check.replay_setup(sound, plan)
    check.replay_setup(broken, plan)
    # The older half of what set-up wrote leaves: every session's first ten
    # requests, the retries' sources among them.
    (preload,) = [p["queues"] for p in plan["setup"] if p["name"] == "preload"]
    broken.evict(int(i) for queue in preload for _op, rows in queue[:10]
                 for i in rows["id_lo"])
    want = check.replay_window(sound, plan, counts)
    got = check.replay_window(broken, plan, counts)
    retries = 8 * len(tiered_plain.retry_positions(mix))
    assert sum(bool(codes) for queue in want for codes in queue) == retries
    assert not any(codes for queue in got for codes in queue)
    sent = [Sent(s, k, "create_transfers", mix["batch"], 0.0, 1.0,
                 [tuple(c) for c in codes])
            for s, queue in enumerate(got) for k, codes in enumerate(queue)]
    rows = sound.lookup_accounts(plan["account_ids"])
    expected = {"setup": {}, "window": want, "accounts": rows,
                "transfers": rows[:0]}
    numbers = check.compare(expected, {}, sent, rows, rows[:0])
    assert numbers["requests_with_wrong_codes"][0] == retries == 16
    assert check.verdict(numbers) is False
    # And the accounts differ: a re-accepted transfer moved money twice.
    assert check._rows_differing(
        broken.lookup_accounts(plan["account_ids"]), rows) > 0
    assert setup  # the sound reference answered the set-up


# -- the six readers -------------------------------------------------------------

def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


@pytest.fixture
def run(cell):
    """Set-up with one eviction of 4,193,281 rows (threshold 0.2 s, extract
    0.5 s, rehash 1.3 s of a span of 6 s); a window of 80 general batches of
    which 10 were dispatched again for true cold ids and 1 in error."""
    def span(seconds, count=1):
        return {"sum": seconds * 1e6, "count": count}
    before = {
        "counters": {"ops.compactions": 1, "ops.rows_evicted": 4_193_281,
                     "ops.route.general": 1_032, "cold.redispatches": 0},
        "gauges": {"cold.bloom_bits_log2": 29, "cold.rows": 4_193_281},
        "histograms": {"txtrace.stage.cold_evict": span(6.0),
                       "txtrace.stage.cold_threshold": span(0.2),
                       "txtrace.stage.cold_extract": span(0.5),
                       "txtrace.stage.cold_rehash": span(1.3)}}
    after = {
        "counters": {"ops.compactions": 1, "ops.rows_evicted": 4_193_281,
                     "ops.route.general": 1_032 + 80,
                     "cold.redispatches": 11, "cold.false_redispatches": 1},
        "gauges": dict(before["gauges"]),
        "histograms": dict(before["histograms"], **{
            "txtrace.stage.cold_resolve": span(0.033, 11)})}
    return {"snapshots": {"open": before, "close": after}, "trace": None,
            "window": [], "mix": cell["mix"], "config": cell["config"],
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_readers_on_known_arithmetic(run):
    assert _read("cold_redispatch_pct", run) == pytest.approx(100 * 11 / 80)
    assert _read("cold_false_positive_pct", run) == pytest.approx(
        100 * 1 / 70)
    assert _read("cold_resolve_ms", run) == pytest.approx(3.0)
    assert _read("evictions_in_window", run) == 0
    run["snapshots"]["close"]["counters"]["ops.compactions"] = 2
    assert _read("evictions_in_window", run) == 1
    assert _read("cold_evict_ms", run) == pytest.approx(6_000.0)
    moved = (1 << 24) * 25 + 4_193_281 * 132 + 2 * 4_193_281 * 132
    assert evict_bytes_model.eviction_bytes(
        1 << 24, 4_193_281, 4_193_281) == moved
    assert _read("evict_roofline", run) == pytest.approx(
        100 * moved / 819e9 / 2.0)
    assert _read("evict_roofline", run) < 1.0    # far under a roofline


def test_the_bytes_of_an_eviction_are_written_out():
    assert evict_bytes_model.SLOT_SCAN_BYTES == 25
    assert evict_bytes_model.ROW_BYTES == 132
    assert evict_bytes_model.eviction_bytes(100, 10, 30) == (
        2_500 + 1_320 + 7_920)


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_none_where_there_is_nothing_to_read(run, name):
    """A parent (no tier's gauge, no spans, no counters), and no peaks."""
    if name == "evict_roofline":
        run["peaks"] = None
        assert _read(name, run) is None
        run["peaks"] = {"hbm_bytes_per_s": 819e9}
    for snap in run["snapshots"].values():
        snap.update(counters={}, gauges={}, histograms={})
    assert _read(name, run) is None
