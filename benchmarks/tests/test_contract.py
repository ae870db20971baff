"""What the benchmark promises without a chip: every name in
`BENCHMARK.json` has its file, the peaks table refuses a device it does not
know, and `run.py` prints no result where the program is missing."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import bytes_model, peaks  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_file(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
            config = json.load(f)
        assert config["chips"] == cell["chips"]
        for key in ("source", "guarantees", "reduced", "assumed",
                    "server_args", "tables"):
            assert key in config, key
        assert set(configs[cell["config"]]["reduced"]) == set(
            config["reduced"])
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "generators", mix["generator"] + ".py"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    layers = set()
    for metric in bench["per_layer"]:
        assert NAME.match(metric["name"]) and metric["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", metric["name"] + ".py"))
        layers.add(metric["layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, layer


def test_sizing_rule_of_every_mix(bench):
    """Set-up plus window stays under the next power of two of requests (no
    index level is filled for the first time inside the window) and under the
    transfers table's growth at load 0.5."""
    for cell in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmarks", "traffic",
                               cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        pre = mix["sessions"] * mix["preload_per_session"]
        cap = mix["sessions"] * mix["window_cap_per_session"]
        assert pre & (pre - 1) == 0 or pre > 256, pre
        assert pre + cap < 512 and cap < 256
        assert (pre + cap) * mix["batch"] < (1 << 23) // 2


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in benchmarks/harness/peaks.py"):
        peaks.peaks_of("TPU v9 imaginary")


def test_byte_count_is_the_copied_one():
    """The copy agrees with `utils/roofline.py`'s fast count at d0bcfcd
    (400 B a lane: `fast_kernel_model().bytes_per_batch` = 3,276,000 for 8190
    lanes, read there once when the copy was made)."""
    assert bytes_model.fast_lane_bytes() == 32 + 16 + 116 + 64 + 40 + 128 + 4
    assert int(bytes_model.fast_lane_bytes() * 8190) == 3_276_000
    assert bytes_model.resolve_lane_bytes() > bytes_model.fast_lane_bytes()


def test_no_result_where_only_the_benchmark_is_present(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`, run.py exits non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "default-plain-s8", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0
    assert done.stdout.strip() == ""


@pytest.mark.parametrize("trace", [False, True])
def test_the_result_line_has_the_contracts_keys_and_compared_last(
        bench, trace):
    """`result_line` on a made-up run of each cell: the driver's keys, a
    metric only where its reader read something, and every number compared
    beside its limit under the line's last key."""
    from benchmarks import run

    for cell in bench["workloads"]:
        loaded = {"bench": bench, "cell": cell}
        listed = [m["name"] for m in run.metrics_for(
            bench, "per_layer" if trace else "end_to_end", cell["name"])]
        assert "group_scan_fill" not in listed
        values = {name: 1.5 for name in listed}
        silent = listed[-1] if trace else None
        values[silent] = None
        out = {"correct": True, "attempted": 248, "failed": 0,
               "per_layer": values, "end_to_end": values,
               "memory_peak_bytes": 1 << 31,
               "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                          "count": 1},
               "trace": {"busy_s": 4.5, "window_s": 5.0, "device_ops": [],
                         "idle_gaps": []},
               "numbers": {"requests_compared": (512, None),
                           "account_rows_differing": (0, 0)}}
        line = json.loads(json.dumps(run.result_line(loaded, out, trace)))
        assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                                  "device"]
        assert list(line)[-1] == "compared"
        assert line["compared"] == {
            "requests_compared": {"value": 512, "limit": None},
            "account_rows_differing": {"value": 0, "limit": 0}}
        assert set(line["metrics"]) == set(listed) - {silent}
        assert ("busy_s" in line["device"]) == trace == ("breakdown" in line)
