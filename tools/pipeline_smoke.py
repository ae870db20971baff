"""CI pipeline smoke: prove the pipelined commit engine end to end, cheaply.

Runs ``bench.py`` (subprocess, CPU-pinned) with a tiny flagship workload
and ``--pipeline-depth 1,2`` + ``--metrics-json``, then asserts the
ARTIFACTS, not just the exit code:

1. depth-identity — the sweep's depth-1 and depth-2 entries must report
   byte-identical reply digests (``replies_sha``) AND ledger digests: the
   three overlaps (staged H2D, deferred D2H on the dispatch lane,
   fsync/compute overlap) are performance-only by construction, and this
   is the cheap cross-process check that stays true.
2. occupancy/stall counters — METRICS.json must carry the pipeline series
   (``pipeline.dispatches`` / ``pipeline.resolves`` / ``pipeline.groups``
   and the ``pipeline.inflight`` histogram), so BENCH_r06+ can read the
   overlap forensics the same way docs/commit_pipeline.md describes.
3. the primary JSON line carries the sweep (``reps.pipeline_sweep``) and
   the ``pipeline`` block with the depth sweep.

Artifacts land at the repo root: METRICS.json (shared with the obs tier's
snapshot path — this run overwrites it with fresh series) and
PIPELINE_SMOKE.json (the summary; the pipeline tier in tools/ci.py records
pass/fail in CI_LAST.json).

Usage: python tools/pipeline_smoke.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

EXPECTED_COUNTERS = (
    "pipeline.dispatches", "pipeline.resolves", "pipeline.groups",
)


def main() -> int:
    summary: dict = {}
    metrics_path = os.path.join(REPO, "METRICS.json")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "bench.py"),
            "--force-cpu", "--skip-e2e", "--skip-kernel-profile",
            "--skip-parity",
            "--transfers", "30000", "--accounts", "256", "--count", "1024",
            "--pipeline-depth", "1,2",
            "--metrics-json", metrics_path,
        ],
        cwd=REPO, capture_output=True, text=True, timeout=1500,
    )
    sys.stderr.write(proc.stderr[-2000:])
    assert proc.returncode == 0, f"bench rc={proc.returncode}"
    payload = json.loads(proc.stdout.strip().splitlines()[-1])

    # 1. depth-identity: pipelined == sequential, bit for bit.
    sweep = (payload.get("reps") or {}).get("pipeline_sweep") or {}
    d1, d2 = sweep.get("1"), sweep.get("2")
    assert d1 and d2, f"sweep entries missing: {sorted(sweep)}"
    assert d1["replies_sha"] == d2["replies_sha"], (
        "reply bodies diverge between depth 1 and depth 2"
    )
    assert d1["digest"] == d2["digest"], (
        "ledger digests diverge between depth 1 and depth 2"
    )
    summary["identity"] = {
        "replies_sha": d1["replies_sha"], "digest": d1["digest"],
        "depth1_tx_s": d1["tx_s"], "depth2_tx_s": d2["tx_s"],
    }

    # 2. the pipeline block rides the primary line.
    pipe = payload.get("pipeline") or {}
    assert "depth" in pipe and "sweep" in pipe, pipe
    summary["speedup_vs_depth1"] = pipe.get("speedup_vs_depth1")

    # 3. occupancy/stall counters in METRICS.json.
    with open(metrics_path) as f:
        metrics = json.load(f)
    counters = metrics.get("counters", {})
    for name in EXPECTED_COUNTERS:
        assert counters.get(name, 0) > 0, (
            f"{name} missing from METRICS.json: "
            f"{sorted(k for k in counters if k.startswith('pipeline'))}"
        )
    assert counters["pipeline.resolves"] == counters["pipeline.dispatches"]
    hists = metrics.get("histograms", {})
    assert "pipeline.inflight" in hists, sorted(hists)
    stalls = {
        k: v for k, v in counters.items() if k.startswith("pipeline.stall.")
    }
    summary["counters"] = {
        **{name: counters[name] for name in EXPECTED_COUNTERS},
        "stalls": stalls,
    }

    out = os.path.join(REPO, "PIPELINE_SMOKE.json")
    with open(out, "w") as f:
        json.dump({"green": True, **summary}, f, indent=1)
    print(json.dumps({"green": True, **summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
