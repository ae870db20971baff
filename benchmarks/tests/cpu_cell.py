"""Test-only driver: one cell through `run.run_cell` against a CPU child.

`run.py` itself accepts no CPU; the expected platform, the child's
environment and (for the traced rehearsal) a stand-in for the device plane
are passed from here, the way `tests/test_chip_smoke.py` drives
`chip_smoke.py`.  TB_GROUP_COMMIT=1 steers the child onto the grouped
dispatch, which is the default only on a TPU.  Prints one JSON object; its
`run_queues_progress` says, for each `drive.run_queues` call the run made,
whether it was handed a `Progress`.

    python cpu_cell.py <root> <workload> <seed> <seconds> <trace> \
        [--server-main FILE] [--expect-platform NAME] [--no-device-plane]
"""

import argparse
import json
import sys
import tempfile


def _cpu_threads_as_device(path: str) -> dict:
    """The CPU client's execution threads, presented as one device plane's
    operation line, so that the reduction and the readers run end to end."""
    from jax.profiler import ProfileData

    first = last = None
    ops = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                first = start if first is None else min(first, start)
                last = end if last is None else max(last, end)
                if line.name.startswith("tf_XLAPjRtCpuClient") and (
                        e.duration_ns > 0):
                    ops.append([e.name, start, e.duration_ns])
    return {"span_ns": [first, last],
            "devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": ops}}}


def main(argv) -> int:
    p = argparse.ArgumentParser()
    for name in ("root", "workload", "seed", "seconds", "trace"):
        p.add_argument(name)
    p.add_argument("--server-main", default=None)
    p.add_argument("--expect-platform", default="cpu")
    p.add_argument("--no-device-plane", action="store_true",
                   help="read the CPU trace as it is: it has no device plane")
    args = p.parse_args(argv)
    root = args.root
    sys.path.insert(0, root)
    from benchmarks import run
    from benchmarks.harness import drive, trace_reduce
    from tigerbeetle_tpu import jaxenv

    if not args.no_device_plane:
        trace_reduce.read_events = _cpu_threads_as_device
    run_queues, with_progress = drive.run_queues, []

    def recording(*a, progress=None, **kw):
        with_progress.append(progress is not None)
        return run_queues(*a, progress=progress, **kw)

    drive.run_queues = recording
    env = jaxenv.child_env(cpu=True, n_devices=1)
    env["TB_GROUP_COMMIT"] = "1"
    loaded = run.load_cell(root, args.workload)
    with tempfile.TemporaryDirectory(prefix="tb_bench_test_") as workdir:
        out = run.run_cell(
            loaded, int(args.seed), float(args.seconds), args.trace == "1",
            args.expect_platform, env, workdir, root=root,
            server_main=args.server_main)
    keep = ("correct", "numbers", "attempted", "failed", "end_to_end",
            "per_layer", "observations", "device", "memory_peak_bytes")
    print(json.dumps(dict({k: out[k] for k in keep if k in out},
                          run_queues_progress=with_progress)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
