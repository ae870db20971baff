"""The cell `twophase-resolve-s8`: its own sizing invariants, reckoned from
its two files, and its five per-layer readers, on a fixture with known
arithmetic and on a recorded chip run's snapshots and reduced trace: a number
where the program has the span, the counter or the kernel, None where it has
not (as the parent commit has not)."""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "twophase-resolve-s8"
READERS = ("general_kernel_ms", "general_roofline", "general_commit_ms",
           "general_sync_ms", "general_passes")


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cell():
    bench = _load("BENCHMARK.json")
    (entry,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (config,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    return {"bench": bench, "entry": entry,
            "config": _load(config["file"]),
            "mix": _load("benchmarks", "traffic", entry["traffic"] + ".json")}


def test_the_cells_own_sizing_invariants(cell):
    """Nothing grows and no index level is first filled inside the window:
    requests < 512, transfer rows < half the transfers table, posted rows <
    half the posted table `start` is told to make."""
    mix, config = cell["mix"], cell["config"]
    assert mix["cycle"] == ["pending", "resolve"]
    sessions = mix["sessions"]
    steps = mix["preload_per_session"] + mix["window_cap_per_session"]
    preload = sessions * mix["preload_per_session"]
    assert preload >= 256 and preload & (preload - 1) == 0
    assert sessions * steps < 512
    pending = sessions * ((steps + 1) // 2)       # a session starts pending
    resolving = sessions * (steps // 2)
    share = mix["resolve"]
    lanes = (mix["batch"] * share["post_pct"] // 100
             + mix["batch"] * share["void_pct"] // 100)
    assert lanes == 7780
    tables = config["tables"]
    assert pending * mix["batch"] + resolving * lanes < (
        1 << (tables["transfers_slots_log2"] - 1))
    assert resolving * lanes < (
        1 << (tables["posted_slots_log2_at_start"] - 1))
    # The table sizes the file states are the ones `start` is given.
    args = config["server_args"]
    for option, key in (("--cache-accounts-log2", "accounts_slots_log2"),
                        ("--cache-transfers-log2", "transfers_slots_log2"),
                        ("--cache-posted-log2",
                         "posted_slots_log2_at_start")):
        assert int(args[args.index(option) + 1]) == tables[key]


def test_guarantees_are_the_controls_word_for_word(cell):
    control = _load("benchmarks", "configs", "tb-default-1r.json")
    for key in ("guarantees", "deployment", "chips", "events_per_request",
                "accounts"):
        assert cell["config"][key] == control[key], key


def test_the_cell_is_listed_where_its_traced_run_reads(cell):
    listed = {m["name"] for m in cell["bench"]["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(READERS) <= listed
    for metric in cell["bench"]["per_layer"]:
        if metric["name"] in READERS:
            assert metric["workloads"] == [CELL]


def _histogram(count, total):
    return {"count": count, "sum": total}


@pytest.fixture
def run(cell):
    """A window of 10 general commits of 120 ms each with a device wait of
    100 ms, 2 passes each; a profiler window with 4 whole executions of the
    general program, 0.5 s of device time in all, one more that was under way
    when the profiler opened (60 ms of it seen) and one it closed on (40)."""
    opened = {"counters": {}, "gauges": {}, "histograms": {
        "txtrace.stage.general_commit": _histogram(5, 5 * 90e3),
        "txtrace.stage.full_sync": _histogram(5, 5 * 70e3),
        "waves.jacobi_passes": _histogram(5, 5)}}
    closed = {"counters": {}, "gauges": {}, "histograms": {
        "txtrace.stage.general_commit": _histogram(15, 5 * 90e3 + 10 * 120e3),
        "txtrace.stage.full_sync": _histogram(15, 5 * 70e3 + 10 * 100e3),
        "waves.jacobi_passes": _histogram(15, 5 + 20)}}
    ms = 1_000_000
    general = "jit_create_transfers_full_impl"
    trace = {"program_s": 1.6, "device_span_ns": [0, 2000 * ms], "executions": [
        [general, 0, 60 * ms, 0],
        ["jit__group_fast_dispatch_impl", 100 * ms, 500 * ms, 7],
        [general, 700 * ms, 120 * ms, 0], [general, 900 * ms, 130 * ms, 0],
        ["jit__group_fast_dispatch_impl", 1100 * ms, 500 * ms, 7],
        [general, 1650 * ms, 110 * ms, 0], [general, 1800 * ms, 140 * ms, 0],
        [general, 1960 * ms, 40 * ms, 0]]}
    return {"snapshots": {"open": opened, "trace_start": opened,
                          "trace_stop": closed, "close": closed},
            "trace": trace, "window": [], "mix": cell["mix"],
            "config": cell["config"],
            "peaks": {"hbm_bytes_per_s": 819e9}}


def _read(name, run):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run)


def test_readers_on_known_arithmetic(run):
    from benchmarks.harness import bytes_model

    assert _read("general_commit_ms", run) == pytest.approx(120.0)
    assert _read("general_sync_ms", run) == pytest.approx(100.0)
    assert _read("general_passes", run) == pytest.approx(2.0)
    assert _read("general_kernel_ms", run) == pytest.approx(125.0)
    least_s = 4 * 7780 * bytes_model.resolve_lane_bytes() / 819e9
    assert _read("general_roofline", run) == pytest.approx(
        100.0 * least_s / 0.5)
    assert 0 < _read("general_roofline", run) < 100


def test_readers_on_a_recorded_chip_run(cell):
    """What the run's own result line printed (four decimals), read again
    from its four snapshots and its reduced trace."""
    recorded = _load("benchmarks", "tests", "fixtures",
                     "twophase_chip_run.json")
    run = {"snapshots": recorded["snapshots"], "trace": recorded["trace"],
           "window": [], "mix": cell["mix"], "config": cell["config"],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name, printed in recorded["printed"].items():
        assert round(_read(name, run), 4) == printed, name


@pytest.mark.parametrize("name", READERS)
def test_none_where_there_is_nothing_to_read(run, name):
    """A program without the spans, the counter or the kernel in the window
    (the parent commit; a plain mix): nothing is read, nothing raises."""
    for snap in run["snapshots"].values():
        snap["histograms"] = {}
    run["trace"]["executions"] = [e for e in run["trace"]["executions"]
                                  if "create_transfers_full" not in e[0]]
    assert _read(name, run) is None
    run["trace"] = None
    run["peaks"] = None
    assert _read(name, run) is None


def test_no_roofline_for_a_mix_that_resolves_nothing(run):
    run["mix"] = dict(run["mix"], cycle=["plain"])
    del run["mix"]["resolve"]
    assert _read("general_roofline", run) is None
