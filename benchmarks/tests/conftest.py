"""A temporary copy of the benchmark with a tiny configuration, two tiny
mixes, their cells and one more per-layer metric ADDED AS NEW FILES AND
ENTRIES — no file that is there is edited — shared by the CPU rehearsals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_MIX = {
    "generator": "ledger_mix", "accounts": 300, "batch": 256, "sessions": 4,
    "preload_per_session": 4, "window_cap_per_session": 6,
    "amount_max": 1000, "allowed_codes": [0], "lookup_sample": 400,
}
NEW_METRIC = '''"""Requests the window's clients had answered."""


def read(run):
    return len([r for r in run["window"] if not r.error])
'''


@pytest.fixture(scope="session")
def tiny_copy(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "tigerbeetle_tpu"),
               os.path.join(tmp, "tigerbeetle_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/configs/tb-default-1r.json")) as f:
        config = json.load(f)
    config.update(name="tiny", server_args=[
        "--no-engine", "--cache-accounts-log2", "10",
        "--cache-transfers-log2", "14"])
    with open(os.path.join(tmp, "benchmarks/configs/tiny.json"), "w") as f:
        json.dump(config, f)
    bench["configs"].append({
        "name": "tiny", "source": "test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tiny.json"})
    for name, extra in (
            ("tiny-plain", {"cycle": ["plain"]}),
            ("tiny-twophase", {"cycle": ["pending", "resolve"],
                               "resolve": {"post_pct": 80, "void_pct": 15}})):
        with open(os.path.join(tmp, f"benchmarks/traffic/{name}.json"),
                  "w") as f:
            json.dump(dict(TINY_MIX, **extra), f)
        bench["workloads"].append({
            "name": name, "config": "tiny", "traffic": name, "chips": 1,
            "why": "test"})
        for metric in bench["per_layer"]:
            metric["workloads"].append(name)
    with open(os.path.join(tmp, "benchmarks/layer_metrics/answered.py"),
              "w") as f:
        f.write(NEW_METRIC)
    bench["per_layer"].append({
        "name": "answered", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "client and bus",
        "moves": "accepted_tx_s", "workloads": ["tiny-twophase"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture(scope="session")
def cpu_cell(tiny_copy):
    """Run one tiny cell against a CPU child; returns (rc, result, stderr)."""
    def run(workload, seed, seconds, trace, *extra):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # The repo's cache, not the copy's: the child then compiles nothing
        # that an earlier test run already has.
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache"))
        done = subprocess.run(
            [sys.executable,
             os.path.join(tiny_copy, "benchmarks/tests/cpu_cell.py"),
             tiny_copy, workload, str(seed), str(seconds), str(trace),
             *extra],
            cwd=tiny_copy, env=env, capture_output=True, text=True,
            timeout=900)
        lines = done.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if done.returncode == 0 else None
        return done.returncode, result, done.stderr
    return run
