"""The cell `default-plain-shard4`: its invariants, reckoned from its two
files beside the control's; a CPU rehearsal of a tiny twin on a 4-device host
platform (a driver of its own: `cpu_cell.py` pins one device); and its four
per-layer readers on known arithmetic, None where there is nothing to read
(as on the parent commit, whose sharded programs are all `jit_step`)."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL, CONTROL = "default-plain-shard4", "default-plain-s8"
READERS = ("shard_kernel_ms", "shard_collective_pct",
           "shard_commit_roofline", "unshards_in_window")
NOT_READ_HERE = {"commit_roofline", "general_kernel_ms", "general_roofline",
                 "general_commit_ms", "general_sync_ms", "general_passes"}


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


@pytest.fixture(scope="module")
def control(bench):
    return _cell(bench, CONTROL)


# -- the two files ---------------------------------------------------------------

def test_the_pair_differs_in_the_layout_alone(cell, control):
    """The control's mix, guarantees, events, accounts and GLOBAL tables:
    what differs is `--shards 4`, the chips and what follows from them."""
    assert cell["entry"]["traffic"] == control["entry"]["traffic"]
    assert cell["entry"]["chips"] == cell["config"]["chips"] == 4
    mine, theirs = cell["config"], control["config"]
    for key in ("guarantees", "events_per_request", "accounts"):
        assert mine[key] == theirs[key], key
    for key, value in theirs["tables"].items():
        assert mine["tables"][key] == value, key
    assert mine["server_args"] == theirs["server_args"] + ["--shards", "4"]
    assert mine["reduced"] == theirs["reduced"]
    assert cell["listed"]["reduced"] == ["transfer_count"]
    assert cell["listed"]["source"] != control["listed"]["source"]
    assert "shards" in mine["assumed"]
    assert {"lazy_index", "reads"} <= set(mine["notes"])


def test_per_shard_sizing(cell):
    """No shard's table grows inside the window: the rows a shard expects at
    the cap stay under half its slots, with room for the owners' spread."""
    mix, tables = cell["mix"], cell["config"]["tables"]
    shards, per_chip = tables["shards"], tables["per_chip"]
    assert shards == 4
    for name in ("accounts", "transfers"):
        assert per_chip[f"{name}_slots_log2"] == (
            tables[f"{name}_slots_log2"] - 2)
    rows = mix["sessions"] * mix["batch"] * (
        mix["preload_per_session"] + mix["window_cap_per_session"])
    assert rows == 4_127_760
    expected = rows // shards
    grows_at = 1 << (per_chip["transfers_slots_log2"] - 1)
    assert per_chip["transfer_rows_at_cap_expected"] == expected
    assert per_chip["grows_at_rows"] == grows_at
    # Owners are uniform: a shard's rows are binomial(rows, 1/4).
    sd = (rows * 0.25 * 0.75) ** 0.5
    assert expected + 6 * sd < grows_at
    assert mix["accounts"] / shards < (1 << (
        per_chip["accounts_slots_log2"] - 1))


def test_the_cell_is_listed_where_its_traced_run_reads(bench):
    for metric in bench["per_layer"]:
        listed = CELL in metric["workloads"]
        if metric["name"] in READERS:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "accepted_tx_s"
        else:
            assert listed == (metric["name"] not in NOT_READ_HERE), (
                metric["name"])
            assert metric["workloads"][-1] == CELL or not listed


# -- the rehearsal ---------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal(tiny_copy, tmp_path_factory):
    """`tiny-plain` on a tiny twin of the configuration, traced, against a
    CPU child with four devices; configuration and cell are files and
    entries added to a copy of the rehearsals' copy."""
    tmp = str(tmp_path_factory.mktemp("bench_shard4"))
    shutil.copytree(tiny_copy, tmp, symlinks=True, dirs_exist_ok=True)
    config = _load("benchmarks", "configs", "tb-default-4shard.json")
    config.update(name="tiny-4shard", server_args=[
        "--no-engine", "--cache-accounts-log2", "10",
        "--cache-transfers-log2", "16", "--shards", "4"])
    with open(os.path.join(tmp, "benchmarks/configs/tiny-4shard.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-4shard", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/tiny-4shard.json"})
    bench["workloads"].append({
        "name": "tiny-plain-shard4", "config": "tiny-4shard",
        "traffic": "tiny-plain", "chips": 4, "why": "test"})
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"]:
            metric["workloads"].append("tiny-plain-shard4")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    done = subprocess.run(
        [sys.executable,
         os.path.join(tmp, "benchmarks/tests/shard4_cpu_cell.py"), tmp,
         "tiny-plain-shard4", "3000000019", "4", "1"],
        cwd=tmp, env=env, capture_output=True, text=True, timeout=900)
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
    assert routes["grouped"] > 0 and routes["general"] == 0
    assert routes["sequential"] == 0
    assert routes["fast"] + routes["grouped"] == out["attempted"]
    assert out["observations"]["server_sigterms"] == 1


def test_the_rehearsals_traced_run_reads_the_host_side_metrics(rehearsal):
    """Every listed metric whose source is the program or the host's clock
    reads a number under shards; the device-trace ones need the chip's
    program names (the stand-in plane has the CPU client's threads)."""
    layer = rehearsal["per_layer"]
    bench = _load("BENCHMARK.json")
    for metric in bench["per_layer"]:
        if CELL in metric["workloads"] and metric["source"] != "device_trace":
            assert metric["name"] in layer, metric["name"]
    assert layer["unshards_in_window"] == 0
    assert layer["dispatches_per_batch"] > 0
    assert "device_idle_pct" in layer


# -- the four readers ------------------------------------------------------------

def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


@pytest.fixture
def run(cell):
    """A profiler window with 5 whole executions of the sharded fast program
    on device 0 (40 ms each), one that was under way when it opened and one
    it closed on; from the first whole one's start to the start of the one
    it closed on, 5 requests' device time: 5 x 40 ms and one lookup of 10
    ms.  Of the program's self time 30 % lies in all-reduces.  No rebuild
    inside the window."""
    ms = 1_000_000
    fast = "jit_sharded_create_transfers_fast_probed"
    executions = [[fast, 0, 25 * ms, 0]]
    executions += [[fast, (50 + 60 * k) * ms, 40 * ms, 0] for k in range(5)]
    executions.insert(3, ["jit_lookup_accounts", 215 * ms, 10 * ms, 0])
    executions.append([fast, 380 * ms, 20 * ms, 0])
    trace = {
        "device_span_ns": [0, 400 * ms], "executions": executions,
        "ops": {
            f"{fast}:%all-reduce.3": [0.04, 70],
            f"{fast}:%all-reduce-start.1": [0.01, 7],
            f"{fast}:%all-reduce-done.1": [0.025, 7],
            f"{fast}:%fusion.12": [0.100, 7],
            f"{fast}:%gather.4": [0.075, 700],
            "jit_lookup_accounts:%all-reduce.9": [0.5, 1],
            "jit_lookup_accounts:%fusion.2": [0.01, 1],
        }}
    counters = {"sharding.unshards": 3, "sharding.batches": 264}
    snap = {"counters": counters, "gauges": {"sharding.shards": 4},
            "histograms": {}}
    closed = dict(snap, counters=dict(counters, **{"sharding.batches": 512}))
    return {"snapshots": {"open": snap, "close": closed}, "trace": trace,
            "window": [], "mix": cell["mix"], "config": cell["config"],
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_readers_on_known_arithmetic(run):
    from benchmarks.harness import bytes_model, shard_bytes_model

    assert _read("shard_kernel_ms", run) == pytest.approx(40.0)
    assert _read("shard_collective_pct", run) == pytest.approx(30.0)
    assert _read("unshards_in_window", run) == 0
    # A quarter of a request's table traffic, three quarters of its context.
    assert shard_bytes_model.context_lane_bytes() == 2 * (4 + 8 + 20) + 4
    per_chip = shard_bytes_model.fast_lane_bytes_per_chip(4)
    assert per_chip == bytes_model.fast_lane_bytes() / 4 + 68 * 3 / 4
    least_s = 5 * 8190 * per_chip / 819e9
    share = _read("shard_commit_roofline", run)
    assert share == pytest.approx(100.0 * least_s / 0.210)
    assert 0 < share < 100
    # One chip's share of the work against one chip's peak: with the
    # exchange free and the same device time, a quarter of `commit_roofline`
    # plus the context.
    whole = 100.0 * 5 * 8190 * bytes_model.fast_lane_bytes() / 819e9 / 0.210
    assert _read("commit_roofline", run) == pytest.approx(whole)
    assert whole / 4 < share < whole


def test_a_rebuild_inside_the_window_is_counted(run):
    run["snapshots"]["close"]["counters"]["sharding.unshards"] = 5
    assert _read("unshards_in_window", run) == 2


@pytest.mark.parametrize("name", READERS)
def test_none_where_there_is_nothing_to_read(run, name):
    """The parent commit (every sharded program is `jit_step`, so no commit
    program is found), a server that is not sharded, a run with no trace."""
    for execution in run["trace"]["executions"]:
        execution[0] = execution[0].replace(
            "jit_sharded_create_transfers_fast_probed", "jit_step")
    run["trace"]["ops"] = {
        key.replace("jit_sharded_create_transfers_fast_probed", "jit_step"):
        value for key, value in run["trace"]["ops"].items()}
    for snap in run["snapshots"].values():
        snap["gauges"] = {}
    assert _read(name, run) is None
    run["trace"] = None
    run["peaks"] = None
    assert _read(name, run) is None


def test_no_roofline_without_shards(run, control):
    run["config"] = control["config"]
    assert _read("shard_commit_roofline", run) is None
