"""CI obs smoke: prove the observability stack end to end, cheaply.

Two probes, each asserting the ARTIFACT (not just the exit code):

1. VOPR visualization — a tiny seed with the status grid enabled must
   produce a legend + per-tick lines (obs/vopr_viz).
2. In-process serving — a temp replica served over TCP with the metrics
   registry + tracer enabled must record the commit-pipeline series
   (replica.commit_us / net.group_size / net.requests) and the typed spans
   (state_machine_commit, journal_write).

Artifacts land at the repo root: METRICS.json (the serving snapshot) and
OBS_SMOKE.json (the summary; the obs tier in
tools/ci.py records pass/fail in CI_LAST.json).

Usage: python tools/obs_smoke.py
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

EXPECTED_SERVING_SERIES = (
    "replica.commit_us", "replica.prefetch_us", "replica.batch_events",
    "net.group_size", "net.request_us",
)
EXPECTED_SPANS = {"state_machine_commit", "journal_write"}


def probe_vopr_viz(summary: dict) -> None:
    from tigerbeetle_tpu.sim.vopr import run_seed

    result = run_seed(7, ticks=250, viz=True)
    assert result.viz, "vopr viz requested but not recorded"
    lines = result.viz.splitlines()
    assert lines[0].startswith("legend:"), lines[0]
    assert len(lines) > 4, f"suspiciously short viz: {len(lines)} lines"
    summary["vopr"] = {
        "seed": result.seed, "exit": result.exit_code,
        "viz_lines": len(lines),
    }


def probe_serving(summary: dict) -> None:
    """Temp replica over TCP with registry + tracer on: the serving series
    and typed spans must appear."""
    import numpy as np

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.config import LEDGER_TEST, TEST_MIN
    from tigerbeetle_tpu.net.bus import run_server
    from tigerbeetle_tpu.obs.metrics import registry
    from tigerbeetle_tpu.utils.tracer import tracer
    from tigerbeetle_tpu.vsr.replica import Replica

    registry.reset()
    registry.enable()
    tracer.enable("json")
    with tempfile.TemporaryDirectory(prefix="tb_obs_smoke_") as tmp:
        path = os.path.join(tmp, "obs.tb")
        Replica.format(path, cluster=0x0B5, cluster_config=TEST_MIN)
        replica = Replica(path, cluster_config=TEST_MIN,
                          ledger_config=LEDGER_TEST, batch_lanes=64)
        replica.open()
        box: dict = {}
        ready = threading.Event()
        thread = threading.Thread(
            target=run_server, args=(replica, "127.0.0.1", 0),
            kwargs=dict(
                ready_callback=lambda p: (box.update(port=p), ready.set())
            ),
            daemon=True,
        )
        thread.start()
        assert ready.wait(60), "obs smoke server failed to start"

        client = Client([("127.0.0.1", box["port"])], cluster=0x0B5,
                        config=TEST_MIN, timeout_s=30)
        accounts = np.zeros(8, dtype=types.ACCOUNT_DTYPE)
        accounts["id_lo"] = np.arange(1, 9, dtype=np.uint64)
        accounts["ledger"] = 1
        accounts["code"] = 10
        assert client.create_accounts(accounts) == []
        for b in range(4):
            transfers = np.zeros(16, dtype=types.TRANSFER_DTYPE)
            transfers["id_lo"] = 100 + 16 * b + np.arange(
                16, dtype=np.uint64
            )
            transfers["debit_account_id_lo"] = 1 + (
                np.arange(16, dtype=np.uint64) % 8
            )
            transfers["credit_account_id_lo"] = 1 + (
                np.arange(1, 17, dtype=np.uint64) % 8
            )
            transfers["amount_lo"] = 5
            transfers["ledger"] = 1
            transfers["code"] = 10
            assert client.create_transfers(transfers) == []
        client.close()

    snap = registry.snapshot()
    missing = [
        name for name in EXPECTED_SERVING_SERIES
        if not snap["histograms"].get(name, {}).get("count")
    ]
    assert not missing, f"serving series missing from snapshot: {missing}"
    assert snap["counters"].get("net.requests", 0) >= 5
    assert snap["counters"].get("replica.commits", 0) >= 5
    commit = snap["histograms"]["replica.commit_us"]
    assert commit.get("p50") is not None and commit.get("p99") is not None

    names = {e["name"] for e in tracer.drain()}
    tracer.backend = "none"
    missing_spans = EXPECTED_SPANS - names
    assert not missing_spans, f"spans missing from tracer: {missing_spans}"

    metrics_path = os.path.join(REPO, "METRICS.json")
    with open(metrics_path, "w") as f:
        json.dump(snap, f, indent=1)
    registry.disable()
    registry.reset()
    summary["serving"] = {
        "series": sorted(snap["histograms"]),
        "commit_us_p50": commit.get("p50"),
        "commit_us_p99": commit.get("p99"),
        "metrics_json": "METRICS.json",
        "spans": sorted(names),
    }


def main() -> int:
    from tigerbeetle_tpu import jaxenv

    jaxenv.force_cpu()
    summary: dict = {"iso": time.strftime("%Y-%m-%dT%H:%M:%S")}
    t0 = time.time()
    for probe in (probe_vopr_viz, probe_serving):
        name = probe.__name__
        try:
            probe(summary)
            print(f"# {name}: ok", file=sys.stderr)
        except Exception as err:  # noqa: BLE001 — summarized + rethrown
            summary["failed"] = f"{name}: {type(err).__name__}: {err}"
            summary["seconds"] = round(time.time() - t0, 1)
            with open(os.path.join(REPO, "OBS_SMOKE.json"), "w") as f:
                json.dump(summary, f, indent=1)
            print(json.dumps(summary))
            raise
    summary["seconds"] = round(time.time() - t0, 1)
    with open(os.path.join(REPO, "OBS_SMOKE.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
