"""Test-only driver of the four-chip cell's CPU rehearsal: one cell through
`run.run_cell` against a CPU child that sees FOUR host-platform devices
(`cpu_cell.py` pins one).  The traced rehearsal presents the CPU client's
threads as one device plane, as `cpu_cell.py` does.  Prints one JSON object.

    python shard4_cpu_cell.py <root> <workload> <seed> <seconds> <trace>
"""

import json
import os
import sys
import tempfile

DEVICES = 4


def main(argv) -> int:
    root, workload, seed, seconds, trace = argv
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import cpu_cell
    from benchmarks import run
    from benchmarks.harness import trace_reduce
    from tigerbeetle_tpu import jaxenv

    trace_reduce.read_events = cpu_cell._cpu_threads_as_device
    env = jaxenv.child_env(cpu=True, n_devices=DEVICES)
    env["TB_GROUP_COMMIT"] = "1"   # the default only on a TPU
    loaded = run.load_cell(root, workload)
    with tempfile.TemporaryDirectory(prefix="tb_bench_test_") as workdir:
        out = run.run_cell(loaded, int(seed), float(seconds), trace == "1",
                           "cpu", env, workdir, root=root)
    keep = ("correct", "numbers", "attempted", "failed", "end_to_end",
            "per_layer", "observations", "device", "memory_peak_bytes")
    print(json.dumps({k: out[k] for k in keep if k in out}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
