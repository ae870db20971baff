"""The cell `bigstate-s8`: where it is listed, the arithmetic of its mix
reckoned from its two files (ops against the checkpoint interval, index
levels, rows against slots, bytes), a CPU rehearsal of the same mix at
40,000 accounts in small tables (`cpu_cell.py`), correct as it is and not
correct with one account row altered, and its four per-layer readers on
known arithmetic, None where there is nothing to read."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONTROL = "bigstate-s8", "default-plain-s8"
OLDER = (CONTROL, "twophase-resolve-s8", "default-plain-shard4",
         "twophase-resolve-shard4")
READERS = {                      # new with the cell: where each is listed
    "checkpoints_in_window": {CELL},
    "checkpoint_capture_ms": {CELL},
    "probe_trips": {CELL, CONTROL},
    "index_ms_per_batch": {CELL, CONTROL, "twophase-resolve-s8"},
}
CHECKPOINT_OPS = 983             # config.py vsr_checkpoint_interval
LOOKUP_MAX = 8190


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _cell(bench, name):
    (entry,) = [w for w in bench["workloads"] if w["name"] == name]
    (config,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    return {"entry": entry, "listed": config, "config": _load(config["file"]),
            "mix": _load("benchmarks", "traffic", entry["traffic"] + ".json")}


@pytest.fixture(scope="module")
def bench():
    return _load("BENCHMARK.json")


@pytest.fixture(scope="module")
def cell(bench):
    return _cell(bench, CELL)


def _slots_log2(config, table):
    args = config["server_args"]
    return int(args[args.index(f"--cache-{table}-log2") + 1])


# -- where it is listed ------------------------------------------------------------

def test_the_cell_is_listed_where_its_traced_run_reads(bench):
    """Membership only: a later cell or metric appended to a list, or to
    the file, leaves this test as it is."""
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, metric in listed.items():
        cells = metric["workloads"]
        if name in READERS:
            assert READERS[name] <= set(cells), name
        elif name == "commit_roofline":
            assert CELL in cells     # its bytes model counts lanes, not slots
        elif name.startswith(("general_", "shard_")) or name in (
                "blocking_commit_ms", "unshards_in_window"):
            assert CELL not in cells, name
        elif all(c in cells for c in OLDER):
            assert CELL in cells, name
    assert set(READERS) <= set(listed)
    assert listed["checkpoint_capture_ms"]["moves"] == "setup_s"
    for name in set(READERS) - {"checkpoint_capture_ms"}:
        assert listed[name]["moves"] == "accepted_tx_s"
    assert listed["probe_trips"]["layer"] == "kernels"
    assert listed["checkpoints_in_window"]["layer"] == "replica and WAL"
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1


def test_shapes_and_guarantees_are_the_controls(cell, bench):
    control = _cell(bench, CONTROL)
    mine = cell["config"]
    assert mine["guarantees"] == control["config"]["guarantees"]
    assert mine["events_per_request"] == 8190 == cell["mix"]["batch"]
    assert cell["entry"]["chips"] == mine["chips"] == 1
    assert mine["server_args"] == [
        "--no-engine", "--cache-accounts-log2", "24",
        "--cache-transfers-log2", "24"]     # the issue's cuts 1 and 2
    changed = {k for k in cell["mix"]
               if cell["mix"][k] != control["mix"].get(k)}
    assert changed == {"why", "accounts", "preload_per_session",
                       "window_cap_per_session"}
    assert mine["accounts"] == cell["mix"]["accounts"] == 5_000_000
    assert cell["listed"]["reduced"] == list(mine["reduced"]) == [
        "transfer_count", "account_count"]     # both cuts of scale, listed
    assert {"sessions", "table_sizes", "window"} <= set(mine["assumed"])
    sources = {c["source"] for c in bench["configs"]}
    assert len(sources) == len(bench["configs"])
    assert "--account-count=N" in cell["listed"]["source"]
    assert "5 M, reduced" in cell["listed"]["source"]  # claims no 10 M


# -- the mix's own arithmetic ------------------------------------------------------

def test_no_checkpoint_no_new_level_and_no_growth_inside_the_window(cell):
    mix, config = cell["mix"], cell["config"]
    sessions, batch = mix["sessions"], mix["batch"]
    per_session = -(-mix["accounts"] // sessions)
    account_requests = sessions * -(-per_session // batch)
    assert account_requests == 616
    preload = sessions * mix["preload_per_session"]
    window = sessions * mix["window_cap_per_session"]
    at_open = sessions + account_requests + preload
    at_close = at_open + window
    assert (preload, window, at_open, at_close) == (512, 480, 1_136, 1_616)
    # Checkpoints: the set-up crosses one, among the preloaded requests and
    # 153 ops before the window; the window closes before the next can be
    # due, even at its cap (a capture falls at the first group boundary
    # CHECKPOINT_OPS ops or more after the last one, and the first is at op
    # 983 or later: never before op 1,966); the read-back crosses that one.
    assert at_open // CHECKPOINT_OPS == at_close // CHECKPOINT_OPS == 1
    assert at_close < 2 * CHECKPOINT_OPS
    assert at_open - CHECKPOINT_OPS == 153 >= 100
    first = CHECKPOINT_OPS - sessions - account_requests
    assert 0 < first == 359 <= preload                   # a preloaded request's
    lookups = -(-mix["accounts"] // LOOKUP_MAX) + -(
        -mix["lookup_sample"] // LOOKUP_MAX)
    assert lookups == 612
    assert (at_close + lookups) // CHECKPOINT_OPS == 2    # the read-back's one
    # The index: set-up fills through level 9, nothing new inside.
    assert preload == 1 << 9
    assert preload + window < 1 << 10
    assert window < 1 << 9
    assert max(k for k in range(10) if window >> k) == 8  # its highest carry
    # Rows against slots: nothing grows (tables double at load 0.5).
    tables = config["tables"]
    for table in ("accounts", "transfers"):
        assert tables[f"{table}_slots_log2"] == _slots_log2(config, table)
    slots = 1 << _slots_log2(config, "transfers")
    rows = (preload + window) * batch
    assert rows == tables["transfer_rows_at_cap"] == 8_124_480
    assert preload * batch == tables["transfer_rows_at_window_open"]
    assert slots // 4 < rows < slots // 2 == tables["transfers_grow_at_rows"]
    assert tables["transfers_load_at_cap"] == round(rows / slots, 3)
    slots = 1 << _slots_log2(config, "accounts")
    assert slots // 4 < mix["accounts"] < slots // 2
    assert tables["accounts_load"] == round(mix["accounts"] / slots, 3)
    assert mix["accounts"] > slots // 4                   # 2^23 would grow
    assert tables["accounts_grow_at_rows"] == slots // 2
    assert 2 * batch * 100 < mix["accounts"]              # no hot account
    for number in ("616", "512", "1,136", "1,616", "983", "1,966", "992",
                   "8,124,480", "8,388,608", "359", "153"):
        assert number in mix["why"], number


def test_the_bytes_reckoned_are_the_slots_times_the_row(cell):
    config = cell["config"]
    reckoned, row = config["memory_bytes_reckoned"], {
        "accounts": 129, "transfers": 133, "posted": 21}
    assert reckoned["slot_bytes"] == row
    accounts = (1 << _slots_log2(config, "accounts")) * row["accounts"]
    transfers = (1 << _slots_log2(config, "transfers")) * row["transfers"]
    posted = (1 << config["tables"]["posted_slots_log2_at_start"]) * row[
        "posted"]
    assert reckoned["accounts_table"] == accounts
    assert reckoned["transfers_table"] == transfers
    assert reckoned["posted_table"] == posted
    levels = config["tables"]["index_levels_at_window_open"]
    index = 8192 * ((1 << levels) - 1) * 5 * 8 * 2
    assert reckoned["index_levels_0_to_9_both_sides"] == index
    resident = accounts + transfers + posted + index
    assert reckoned["resident_before_temporaries"] == resident
    assert 0.3 * 16e9 < resident < 0.7 * 16e9             # a third of a chip
    assert reckoned["share_of_one_chip"] == round(resident / 16e9, 2)
    assert reckoned["dense_host_copy_of_the_tables"] == (
        accounts + transfers + posted)
    measured = config["tables"]["memory_peak_bytes_measured"]
    assert resident < measured < 16e9


# -- the rehearsal ---------------------------------------------------------------

# 64 requests of set-up fill the index through level 6; 56 more stay under
# 128 in all and under 64: the cell's own rule, three levels lower (982,800
# rows in 2^21 slots), and a window long enough for the traced rehearsal's
# profiler to open inside it on a loaded host.
SMALL = {"accounts": 40_000, "preload_per_session": 8,
         "window_cap_per_session": 7}


@pytest.fixture(scope="module")
def small_copy(tiny_copy, tmp_path_factory):
    """The cell's own mix at 40,000 accounts (load 0.305 of 2^17 slots, as
    5 M of 2^24) with a short set-up and window, on small tables; added as
    files and entries to a copy of the rehearsals' copy."""
    tmp = str(tmp_path_factory.mktemp("bench_bigstate"))
    shutil.copytree(tiny_copy, tmp, symlinks=True, dirs_exist_ok=True)
    config = _load("benchmarks", "configs", "tb-bigstate-1r.json")
    config.update(name="small-bigstate", server_args=[
        "--no-engine", "--cache-accounts-log2", "17",
        "--cache-transfers-log2", "21"])
    with open(os.path.join(tmp, "benchmarks/configs/small-bigstate.json"),
              "w") as f:
        json.dump(config, f)
    mix = dict(_load("benchmarks", "traffic", "plain-10m-s8.json"), **SMALL)
    with open(os.path.join(tmp, "benchmarks/traffic/plain-40k-s8.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "small-bigstate", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/small-bigstate.json"})
    bench["workloads"].append({
        "name": "small-bigstate-s8", "config": "small-bigstate",
        "traffic": "plain-40k-s8", "chips": 1, "why": "test"})
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"]:
            metric["workloads"].append("small-bigstate-s8")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def _rehearse(copy, seed, trace, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    done = subprocess.run(
        [sys.executable, os.path.join(copy, "benchmarks/tests/cpu_cell.py"),
         copy, "small-bigstate-s8", str(seed), "20", str(trace), *extra],
        cwd=copy, env=env, capture_output=True, text=True, timeout=1500)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_the_small_twin_ends_correct_with_every_account_row_compared(
        small_copy):
    out = _rehearse(small_copy, 2147483659, 1)
    assert out["correct"] is True and out["failed"] == 0
    assert all(value == 0 for value, limit in out["numbers"].values()
               if limit is not None)
    assert out["attempted"] == 8 * SMALL["window_cap_per_session"]
    numbers = out["numbers"]
    assert numbers["account_rows_compared"][0] == 40_000
    assert numbers["requests_compared"][0] == 8 + 64 + out["attempted"]
    routes = out["observations"]["window_routes"]
    assert routes["general"] == routes["sequential"] == 0
    layer = out["per_layer"]
    bench = _load("BENCHMARK.json")
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"] and metric["source"] not in (
                "device_trace",) and metric["name"] not in (
                "checkpoints_in_window", "checkpoint_capture_ms"):
            assert metric["name"] in layer, metric["name"]
    assert layer["compiles_in_window"] == 0
    # 136 ops: no checkpoint, so nothing to read for either checkpoint metric.
    assert "checkpoints_in_window" not in layer
    assert "checkpoint_capture_ms" not in layer


def test_one_altered_account_row_is_not_correct(small_copy):
    out = _rehearse(
        small_copy, 3000000019, 0, "--server-main", os.path.join(
            small_copy, "benchmarks/tests/bigstate_broken_server_main.py"))
    assert out["correct"] is False
    assert out["numbers"]["account_rows_compared"] == [40_000, None]
    assert out["numbers"]["account_rows_differing"] == [1, 0]
    assert out["numbers"]["transfer_rows_differing"] == [0, 0]
    assert out["numbers"]["requests_with_wrong_codes"] == [0, 0]


# -- the four readers ------------------------------------------------------------

def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


@pytest.fixture
def run(cell):
    """A profiler window: a lone request (a probe loop of 21 trips), its
    sort, a loop of 7 trips with 7 sorts and 3 merges behind it, twice, and
    a lone request the window closed on; two captures in set-up."""
    ms = 1_000_000
    lone = "jit_create_transfers_fast_probed_impl"
    loop = "jit__group_fast_dispatch_impl"
    executions = [[loop, 0, 90 * ms, 3]]                  # cut by the edge
    at = 100
    for trips in (21, 17):
        executions.append([lone, at * ms, 40 * ms, trips])
        executions.append(["jit_build_runs", (at + 40) * ms, 2 * ms, 0])
        executions.append([loop, (at + 50) * ms, 300 * ms, 7])
        for k in range(7):
            executions.append(
                ["jit_build_runs", (at + 350 + 3 * k) * ms, 2 * ms, 0])
        for k in range(3):
            executions.append(
                ["jit__merge", (at + 380 + 10 * k) * ms, 8 * ms, 0])
        executions.append(
            ["jit_broadcast_in_dim", (at + 420) * ms, 1 * ms, 0])
        at += 500
    executions.append([lone, at * ms, 30 * ms, 0])        # the closing edge
    trace = {"device_span_ns": [0, (at + 30) * ms], "executions": executions}
    histogram = {"count": 2, "sum": 2 * 9_000_000}        # us
    before = {"counters": {"replica.checkpoint.captures": 2}, "gauges": {},
              "histograms": {"txtrace.stage.checkpoint_capture": histogram}}
    after = {"counters": {"replica.checkpoint.captures": 2}, "gauges": {},
             "histograms": {"txtrace.stage.checkpoint_capture": {
                 "count": 3, "sum": 3 * 9_000_000 + 5_000_000}}}
    return {"snapshots": {"open": before, "close": after}, "trace": trace,
            "window": [], "mix": cell["mix"], "config": cell["config"],
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_readers_on_known_arithmetic(run):
    assert _read("checkpoints_in_window", run) == 0
    assert _read("checkpoint_capture_ms", run) == pytest.approx(9_000.0)
    assert _read("probe_trips", run) == pytest.approx(19.0)
    # Whole requests: two lone and two loops of 7 between the first lone
    # request and the closing one: 16, with 16 sorts and 6 merges.
    index = 16 * 2 + 6 * 8
    assert _read("index_ms_per_batch", run) == pytest.approx(index / 16)
    assert _read("kernel_ms_per_batch", run) == pytest.approx(
        (2 * 40 + 2 * 300 + index + 2 * 1) / 16)
    run["snapshots"]["close"]["counters"]["replica.checkpoint.captures"] = 3
    assert _read("checkpoints_in_window", run) == 1


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_where_there_is_nothing_to_read(run, name):
    """A parent without the counter and the span; a lazy index and a
    sharded program's name; a trace without the operations' line; no trace."""
    for snap in run["snapshots"].values():
        snap["counters"], snap["histograms"] = {}, {}
    for execution in run["trace"]["executions"]:
        execution[3] = 0
        if execution[0].startswith(("jit__merge", "jit_build_runs")):
            execution[0] = "jit_step"
    if name == "index_ms_per_batch":
        for execution in run["trace"]["executions"]:
            if "group_fast" in execution[0]:
                execution[3] = 7      # the loops show, the index does not
    assert _read(name, run) is None
    run["trace"] = None
    assert _read(name, run) is None
