"""The cell `twophase-resolve-shard4`: its invariants, reckoned from its two
files beside `tb-twophase-1r`'s and `tb-default-4shard`'s; a CPU rehearsal of
a tiny twin on a 4-device host platform (`shard4_cpu_cell.py`); and its two
per-layer readers on known arithmetic, None where there is nothing to read
(an unsharded server, a plain mix, a parent whose trace has no such
program)."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "twophase-resolve-shard4"
ONE_CHIP, LAYOUT = "twophase-resolve-s8", "default-plain-shard4"
READERS = ("shard_general_roofline", "shard_general_collective_pct")
NOT_READ_HERE = {"commit_roofline", "general_roofline",
                 "shard_commit_roofline"}
OLDER = ("default-plain-s8", ONE_CHIP, LAYOUT)
READ_BESIDE_A_CONTROL = {                       # the two-phase cells' ...
    "general_kernel_ms", "general_commit_ms", "general_sync_ms",
    "general_passes",
    "shard_kernel_ms", "shard_collective_pct",  # ... and the four-chip cells'
    "unshards_in_window"}
CHECKPOINT_OPS = 983          # config.py vsr_checkpoint_interval


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


# -- the two files ---------------------------------------------------------------

def test_schema_mix_and_guarantees_are_the_one_chip_deployments(cell, bench):
    """tb-twophase-1r's guarantees word for word, its mix but for the cap,
    on tb-default-4shard's layout."""
    one_chip, layout = _cell(bench, ONE_CHIP), _cell(bench, LAYOUT)
    mine = cell["config"]
    for key in ("guarantees", "events_per_request", "accounts"):
        assert mine[key] == one_chip["config"][key] == layout["config"][key]
    for key in ("resolve_shares", "timeout", "sessions"):
        assert mine["assumed"][key] == one_chip["config"]["assumed"][key]
    assert mine["assumed"]["shards"].startswith("4:")
    assert {"table_sizes", "window"} <= set(mine["assumed"])
    assert {"lazy_index", "reads", "state_fits_one_chip"} <= set(
        mine["notes"])
    changed = {k for k in cell["mix"]
               if cell["mix"][k] != one_chip["mix"].get(k)}
    assert changed == {"why", "window_cap_per_session"}
    assert cell["mix"]["window_cap_per_session"] == 72
    assert cell["entry"]["chips"] == mine["chips"] == 4
    assert mine["server_args"] == [
        "--no-engine", "--cache-accounts-log2", "21",
        "--cache-transfers-log2", "24", "--cache-posted-log2", "24",
        "--shards", "4"]
    assert cell["listed"]["reduced"] == list(mine["reduced"]) == [
        "transfer_count"]
    sources = {c["source"] for c in bench["configs"]}
    assert len(sources) == len(bench["configs"])
    assert mine.get("architecture") is None


def test_nothing_grows_and_no_checkpoint_falls_inside_a_run(cell):
    """Transfers under half the slots, globally and in every shard with room
    for the owners' spread; posted under a QUARTER of the slots (the sharded
    2x rule: `machine._grow_if_needed`); every operation of a run under the
    checkpoint interval."""
    mix, tables = cell["mix"], cell["config"]["tables"]
    per_chip = tables["per_chip"]
    lanes = (mix["batch"] * mix["resolve"]["post_pct"] // 100
             + mix["batch"] * mix["resolve"]["void_pct"] // 100)
    assert lanes == 7_780
    steps = mix["preload_per_session"] + mix["window_cap_per_session"]
    each = mix["sessions"] * steps // 2           # pending = resolving
    assert each == 416
    rows, posted = each * (mix["batch"] + lanes), each * lanes
    assert (rows, posted) == (6_643_520, 3_236_480)
    assert tables["transfer_rows_at_cap"] == rows
    assert tables["posted_rows_at_cap"] == posted
    assert tables["transfer_rows_at_window_open"] == (
        mix["sessions"] * mix["preload_per_session"] // 2
        * (mix["batch"] + lanes)) == 2_044_160
    slots = 1 << tables["transfers_slots_log2"]
    assert slots // 4 < rows < slots // 2 == tables["transfers_grow_at_rows"]
    slots = 1 << tables["posted_slots_log2_at_start"]
    assert tables["posted_grow_at_load_under_shards"] == 0.25
    assert slots // 8 < posted < slots // 4 == tables[
        "posted_grows_at_rows_under_shards"]
    # A shard's rows are binomial(rows, 1/4).
    assert tables["shards"] == 4
    for name in ("accounts", "transfers"):
        assert per_chip[f"{name}_slots_log2"] == (
            tables[f"{name}_slots_log2"] - 2)
    assert per_chip["posted_slots_log2_at_start"] == (
        tables["posted_slots_log2_at_start"] - 2)
    sd = (rows * 0.25 * 0.75) ** 0.5
    assert per_chip["transfer_rows_at_cap_expected"] == rows // 4
    assert per_chip["transfer_rows_at_cap_sd"] == round(sd)
    assert per_chip["grows_at_rows"] == 1 << (
        per_chip["transfers_slots_log2"] - 1)
    assert rows // 4 + 6 * sd < per_chip["grows_at_rows"]
    # Registers, account requests, preload, window, read-back (two account
    # lookups of at most 8190 ids, one of the transfer sample).
    accounts = -(-mix["accounts"] // mix["sessions"])
    account_requests = mix["sessions"] * -(-accounts // mix["batch"])
    lookups = -(-mix["accounts"] // 8190) + -(-mix["lookup_sample"] // 8190)
    ops = (mix["sessions"] + account_requests
           + mix["sessions"] * steps + lookups)
    assert ops == 851 and ops + 60 <= CHECKPOINT_OPS
    for number in ("6,643,520", "6,472,960", "851", "983"):
        assert number in mix["why"]


def test_the_bytes_reckoned_are_the_slots_times_the_row(cell):
    reckoned = cell["config"]["memory_bytes_reckoned"]
    tables, row = cell["config"]["tables"], reckoned["slot_bytes"]
    whole = sum((1 << tables[key]) * row[name] for name, key in (
        ("accounts", "accounts_slots_log2"),
        ("transfers", "transfers_slots_log2"),
        ("posted", "posted_slots_log2_at_start")))
    assert reckoned["canonical_copy_on_device_0"] == whole
    assert reckoned["tables_a_chip"] == whole // 4
    assert whole < 16e9 / 4                       # one chip would hold it


def test_the_cell_is_listed_where_its_traced_run_reads(bench):
    """Membership only: a later cell or metric appended to a list, or to
    the file, leaves this test as it is."""
    for metric in bench["per_layer"]:
        name, cells = metric["name"], metric["workloads"]
        if name in READERS:
            assert cells == [CELL]
            assert metric["moves"] == "accepted_tx_s"
            assert metric["unit"] == "%" and metric["layer"] == "kernels"
        elif name in NOT_READ_HERE:
            assert CELL not in cells, name
        elif name in READ_BESIDE_A_CONTROL or all(c in cells for c in OLDER):
            assert CELL in cells, name
    assert set(READERS) <= {m["name"] for m in bench["per_layer"]}
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= len(bench["workloads"]) // 2


# -- the rehearsal ---------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tiny_copy, tmp_path_factory):
    """`tiny-twophase` (the new mix's cycle and shares at a small cap) on a
    tiny twin of the configuration, traced, against a CPU child with four
    devices; configuration and cell are files and entries added to a copy
    of the rehearsals' copy."""
    tmp = str(tmp_path_factory.mktemp("bench_twophase_shard4"))
    shutil.copytree(tiny_copy, tmp, symlinks=True, dirs_exist_ok=True)
    config = _load("benchmarks", "configs", "tb-twophase-4shard.json")
    config.update(name="tiny-2p-4shard", server_args=[
        "--no-engine", "--cache-accounts-log2", "10",
        "--cache-transfers-log2", "16", "--cache-posted-log2", "15",
        "--shards", "4"])
    with open(os.path.join(tmp, "benchmarks/configs/tiny-2p-4shard.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-2p-4shard", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/tiny-2p-4shard.json"})
    bench["workloads"].append({
        "name": "tiny-twophase-shard4", "config": "tiny-2p-4shard",
        "traffic": "tiny-twophase", "chips": 4, "why": "test"})
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"]:
            metric["workloads"].append("tiny-twophase-shard4")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    done = subprocess.run(
        [sys.executable,
         os.path.join(tmp, "benchmarks/tests/shard4_cpu_cell.py"), tmp,
         "tiny-twophase-shard4", "3000000019", "6", "1"],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=1200)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_the_tiny_twin_ends_correct_on_four_devices(rehearsal):
    out = rehearsal
    assert out["correct"] is True and out["failed"] == 0
    assert all(value == 0 for value, limit in out["numbers"].values()
               if limit is not None)
    assert out["device"]["count"] == 4
    assert out["device"]["executor"] == "device"
    assert out["attempted"] == 4 * 6              # the window's cap
    assert out["numbers"]["account_rows_compared"][0] == 300
    routes = out["observations"]["window_routes"]
    assert routes["general"] == out["attempted"] // 2
    assert routes["fast"] + routes["grouped"] == out["attempted"] // 2
    assert routes["sequential"] == 0


def test_the_rehearsals_traced_run_reads_the_general_route(rehearsal):
    """Every listed metric whose source is the program or the host's clock
    reads a number under shards, the general route's spans among them; the
    device-trace ones need the chip's program names."""
    layer = rehearsal["per_layer"]
    bench = _load("BENCHMARK.json")
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"] and metric["source"] != "device_trace":
            assert metric["name"] in layer, metric["name"]
    assert layer["general_commit_ms"] >= layer["general_sync_ms"] > 0
    assert layer["general_passes"] == 1.0
    assert layer["unshards_in_window"] == 0
    assert layer["compiles_in_window"] == 0
    for name in READERS:                          # no such program's name
        assert name not in layer


# -- the two readers -------------------------------------------------------------

def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


@pytest.fixture
def run(cell):
    """A profiler window with 4 whole executions of the sharded general
    program on device 0 (60 ms each) between sharded fast ones, one general
    execution under way when it opened and one it closed on.  Of the general
    program's self time 25 % lies in all-reduces; the fast program's
    all-reduces are not its own."""
    ms = 1_000_000
    general = "jit_sharded_create_transfers_full_waves"
    fast = "jit_sharded_create_transfers_fast_probed"
    executions = [[general, 0, 30 * ms, 0]]
    for k in range(4):
        executions.append([general, (50 + 100 * k) * ms, 60 * ms, 0])
        executions.append([fast, (115 + 100 * k) * ms, 20 * ms, 0])
    executions.append([general, 470 * ms, 30 * ms, 0])
    trace = {
        "device_span_ns": [0, 500 * ms], "executions": executions,
        "ops": {
            f"{general}:%all-reduce.143": [0.050, 6],
            f"{general}:%all-reduce-start.7": [0.010, 6],
            f"{general}:%all-reduce-done.7": [0.015, 6],
            f"{general}:%fusion.1797": [0.150, 6],
            f"{general}:%gather.4": [0.075, 600],
            f"{fast}:%all-reduce.3": [0.5, 4],
            f"{fast}:%fusion.408": [0.01, 4],
        }}
    snap = {"counters": {}, "gauges": {"sharding.shards": 4},
            "histograms": {}}
    return {"snapshots": {"open": snap, "close": snap}, "trace": trace,
            "window": [], "mix": cell["mix"], "config": cell["config"],
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_readers_on_known_arithmetic(run):
    from benchmarks.harness import bytes_model, shard_general_bytes_model

    assert _read("shard_general_collective_pct", run) == pytest.approx(25.0)
    assert _read("general_kernel_ms", run) == pytest.approx(60.0)
    # A quarter of a lane's table traffic, three quarters of its context:
    # new id found; pending found + its value columns; two account sides
    # (found, slot, meta, balances); posted found + value.
    context = 4 + (4 + 116) + 2 * (4 + 8 + 20 + 32) + (4 + 4)
    assert shard_general_bytes_model.context_lane_bytes() == context == 260
    per_chip = shard_general_bytes_model.resolve_lane_bytes_per_chip(4)
    assert bytes_model.resolve_lane_bytes() == 600
    assert per_chip == 600 / 4 + 260 * 3 / 4 == 345
    least_s = 4 * 7780 * per_chip / 819e9
    share = _read("shard_general_roofline", run)
    assert share == pytest.approx(100.0 * least_s / 0.240)
    assert 0 < share < 1
    # One chip's share of the work against one chip's peak: between a
    # quarter of `general_roofline` and the whole of it.
    whole = _read("general_roofline", run)
    assert whole == pytest.approx(100.0 * 4 * 7780 * 600 / 819e9 / 0.240)
    assert whole / 4 < share < whole


@pytest.mark.parametrize("name", READERS)
def test_none_where_there_is_nothing_to_read(run, name, bench):
    """A plain mix on the same layout (no general execution, no resolve
    shares); a trace without the program's name; no trace at all."""
    plain = _cell(bench, LAYOUT)
    fast_only = dict(run, mix=plain["mix"], config=plain["config"],
                     trace=dict(run["trace"], executions=[
                         e for e in run["trace"]["executions"]
                         if "full" not in e[0]], ops={
                         k: v for k, v in run["trace"]["ops"].items()
                         if "full" not in k}))
    assert _read(name, fast_only) is None
    for execution in run["trace"]["executions"]:
        execution[0] = "jit_step"
    run["trace"]["ops"] = {"jit_step:" + key.partition(":")[2]: value
                           for key, value in run["trace"]["ops"].items()}
    assert _read(name, run) is None
    run["trace"] = None
    run["peaks"] = None
    assert _read(name, run) is None


def test_no_roofline_without_shards(run, bench):
    """The one-chip deployment's general program has a roofline of its own
    (`general_roofline`); this one reads a sharded server only."""
    run["config"] = _cell(bench, ONE_CHIP)["config"]
    assert _read("shard_general_roofline", run) is None
