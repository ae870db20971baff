"""CI reconfiguration smoke: the live-reshaping fault domain's proof set
(docs/reconfiguration.md), cheaply and deterministically.

Six proofs with asserted artifacts:

1. PROMOTION E2E — a committed ``reconfigure`` op promotes the standby
   into the voter set on every seat, the primary is then killed, and the
   survivors elect a new primary and keep committing: the promotion is
   load-bearing (a 2-voter cluster would wedge), and the per-op digest
   auditor stays green throughout.
2. SPLIT IDENTITY — a live 2 -> 4 shard split pumped one Merkle-verified
   chunk at a time, with commits landing between every chunk (serving
   never wedges), finishes byte-identical to a machine cold-booted at
   4 shards and fed the same op stream.
3. VOPR RECONFIG, POSITIVE — the pinned seed through the real
   ``tb vopr --reconfig`` CLI: online 2 -> 4 shard split mid-flood with
   one migration source crashed mid-transfer (resume-by-rollback,
   restarts >= 1) and one chunk corrupted in flight (leaf check rejects
   and re-ships, chunk_retries >= 1); the run exits 0 with every live
   seat at 4 shards and the final digest byte-identical to the
   no-reshard oracle.
4. VOPR RECONFIG, NEGATIVE — the SAME seed with ``--no-verify`` (the
   scrub-off discipline): the corrupt chunk installs unaudited and the
   run must fail the convergence/audit oracles (exit 129), proving chunk
   verification is load-bearing, not decorative.
5. TBMC RECONFIG SCOPE — the reconfiguration fault domain in the
   model checker: the unmutated 3+1 -> 4+0 promotion scope is
   exhaustively CLEAN under crash + timeout interleavings, while the
   ``reconfig_stale_quorum`` mutation (view-change quorum sized from
   boot-time membership) falls to a guided machine-checked agreement
   counterexample that does NOT reproduce with the defense restored.
6. ``reconfig.*`` METRICS — membership_ops / promotions /
   reshard_started / reshard_completed / bytes_migrated land in
   METRICS.json.

Artifact: RECONFIG_SMOKE.json at the repo root; the ``reconfig`` tier in
tools/ci.py records pass/fail in CI_LAST.json.

Usage: python tools/reconfig_smoke.py [--skip-vopr]
  (--skip-vopr: skip proofs 3 and 4 — the two CLI vopr runs are
  ~45 s of single-core simulation each)
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEED = 830001   # the pinned reconfiguration seed (tests/test_reconfig.py)
CID = 1009      # tbmc's single scripted client id (McCluster's derivation)


def main(argv=None) -> int:
    skip_vopr = "--skip-vopr" in (argv or sys.argv[1:])
    from tigerbeetle_tpu import jaxenv

    jaxenv.force_cpu(8)  # the 2 -> 4 split needs >= 4 virtual devices
    from tigerbeetle_tpu.obs.metrics import registry

    registry.enable()
    summary = {}

    # -- 1. promotion e2e: the flipped membership is load-bearing ------------
    import tempfile

    from tigerbeetle_tpu.sim.cluster import SimCluster

    with tempfile.TemporaryDirectory() as wd:
        cl = SimCluster(wd, n_replicas=2, n_clients=2, seed=11,
                        requests_per_client=5, n_standbys=1)
        cl.add_reconfigure_client(at_tick=60, new_rc=3, new_sc=0, seed=11)
        for _ in range(400):
            cl.step()
        live = [i for i in range(cl.total) if cl.alive[i]]
        assert all(
            cl.replicas[i].replica_count == 3
            and cl.replicas[i].standby_count == 0 for i in live
        ), "membership flip did not land on every seat"
        assert not cl.replicas[2].is_standby, "standby was not promoted"
        prim = next(i for i in live if cl.replicas[i].is_primary)
        cl.crash(prim)
        cl.add_flood_clients(2, seed=77, n_requests=3, start_tick=cl.t + 5)
        for _ in range(1_500):
            cl.step()
        alive = [i for i in range(3) if cl.alive[i]]
        new_primary = [i for i in alive if cl.replicas[i].is_primary]
        assert new_primary, (
            "no primary elected after the kill — the promotion was not "
            "load-bearing"
        )
        done = sum(1 for c in cl.clients.values() if c.done)
        assert done == len(cl.clients), (
            f"commits wedged after the post-promotion kill: "
            f"{done}/{len(cl.clients)} clients done"
        )
        summary["promotion_e2e"] = {
            "killed_primary": prim,
            "new_primary": new_primary[0],
            "clients_done": done,
            "audited_ops": cl.auditor.audited,
        }

    # -- 2. split identity: a LIVE 2 -> 4 split, pumped one chunk at a
    # time while the machine keeps serving commits, lands byte-identical
    # to a machine cold-booted at 4 shards and fed the same op stream
    # (the layout-invariance half of the cutover rule; the vopr proof
    # below covers the no-reshard-oracle half).
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import LedgerConfig
    from tigerbeetle_tpu.machine import TpuStateMachine

    cfg = LedgerConfig(accounts_capacity_log2=10,
                       transfers_capacity_log2=12, posted_capacity_log2=10)
    live = TpuStateMachine(cfg, batch_lanes=128, shards=2)
    cold = TpuStateMachine(cfg, batch_lanes=128, shards=4)
    accounts = types.accounts_array([
        types.account(id=i, ledger=1, code=10) for i in range(1, 65)
    ])

    def batch(base):
        return types.transfers_array([
            types.transfer(id=base + i, debit_account_id=1 + (base + i) % 64,
                           credit_account_id=1 + (base + i * 7 + 3) % 64,
                           amount=1 + i, ledger=1, code=10)
            for i in range(16)
        ])

    for m in (live, cold):
        m.create_accounts(accounts)
    for b in range(4):
        w = live.create_transfers(batch(100 + 16 * b))
        assert w == cold.create_transfers(batch(100 + 16 * b))
    assert live.reshard_begin(4, verify=True, chunk_rows=16)
    # Serving NEVER wedges during the split: commits keep landing on
    # both machines between chunk shipments (each dirties migrated rows,
    # so the split needs catch-up rounds)...
    served_mid_split = 0
    for b in range(8):
        if not live.reshard_active:
            break
        live.reshard_step(1)
        w = live.create_transfers(batch(200 + 16 * b))
        assert w == cold.create_transfers(batch(200 + 16 * b))
        served_mid_split += 1
    # ...then the flood drains and the split pumps to cutover (the same
    # settle discipline as the vopr schedule — a 100% write duty cycle
    # never quiesces by design).
    pumps = 0
    while live.reshard_active:
        live.reshard_step(1)
        pumps += 1
        assert pumps < 10_000, "split did not finish after the drain"
    assert live.shards == 4 and live.reshard_stats["splits_completed"] == 1
    assert int(live.digest()) == int(cold.digest()), (
        f"live-split digest {int(live.digest()):032x} != cold-boot-at-4 "
        f"digest {int(cold.digest()):032x}"
    )
    summary["split_identity"] = {
        "digest": f"{int(live.digest()):032x}",
        "commits_mid_split": served_mid_split,
        "reshard_stats": dict(live.reshard_stats),
    }

    # -- 3 + 4. the pinned VOPR seed through the real CLI --------------------
    def vopr(extra, timeout=900):
        proc = subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu", "vopr",
             "--reconfig", "--seed", str(SEED)] + extra,
            capture_output=True, text=True, timeout=timeout, cwd=REPO,
        )
        return proc.returncode, proc.stdout + proc.stderr

    if skip_vopr:
        summary["vopr_positive"] = {"skipped": True}
        summary["vopr_negative"] = {"skipped": True}
    else:
        rc, out = vopr([])
        assert rc == 0, f"positive reconfig seed {SEED} failed rc={rc}:\n{out}"
        line = next(ln for ln in out.splitlines()
                    if ln.startswith(f"seed={SEED} "))
        assert "promoted=True" in line, line
        stats = {
            k: int(v) for k, v in
            re.findall(r"'(\w+)': (\d+)", line.split("stats=", 1)[1])
        }
        assert "crash_source=-1" not in line, (
            f"no migration source was crashed mid-transfer: {line}"
        )
        assert stats.get("chunk_retries", 0) >= 1, (
            f"corrupt chunk was not rejected + re-shipped: {line}"
        )
        assert stats.get("splits_completed", 0) >= 1, line
        assert "shards=[4, 4, 4, 4]" in line, (
            f"not every live seat finished at 4 shards: {line}"
        )
        summary["vopr_positive"] = {
            "seed": SEED, "exit": 0, "stats": stats, "line": line,
        }

        rc, out = vopr(["--no-verify"])
        assert rc == 129, (
            f"NEGATIVE CONTROL PASSED (rc={rc}): with verification off "
            f"the corrupt chunk must be digest-visible — chunk "
            f"verification is decorative.\n{out}"
        )
        summary["vopr_negative"] = {"seed": SEED, "exit": 129}

    # -- 5. tbmc: the reconfiguration fault domain ---------------------------
    from tigerbeetle_tpu.sim.mc import McScope, check, replay_schedule

    clean = check(McScope(
        n_replicas=3, n_standbys=1, reconfig=True, ops_per_client=1,
        crash_budget=1, timeout_budget=2, max_view=1, depth_max=8,
        max_states=400_000,
    ))
    assert clean.violation is None, (
        f"UNMUTATED promotion scope violation: {clean.violation} via "
        f"{clean.schedule}"
    )
    assert clean.exhaustive, (
        f"promotion scope not exhausted: cap hit at {clean.states}"
    )
    summary["tbmc_clean"] = {
        "states_explored": clean.states,
        "exhaustive": True,
        "elapsed_s": clean.elapsed_s,
    }

    # Guided hunt: op 2 committed by the post-flip 4-voter ring with the
    # 1 -> 2 hop dropped (seats 2 and 3 starved), then seat 2's
    # suspect -> escalate view change — under the stale boot-membership
    # quorum it completes ONE VOTE SHORT of intersection and re-commits
    # a different op at the same number.
    prefix = (
        ("client", CID, 0), ("deliver", "client", CID, "replica", 0),
        ("deliver", "replica", 0, "replica", 1),
        ("deliver", "replica", 1, "replica", 2),
        ("deliver", "replica", 1, "replica", 0),
        ("deliver", "replica", 2, "replica", 3),
        ("deliver", "replica", 2, "replica", 0),
        ("deliver", "replica", 0, "client", CID),
        ("timeout", 0, "commit_hb"),
        ("deliver", "replica", 0, "replica", 1),
        ("deliver", "replica", 0, "replica", 2),
        ("deliver", "replica", 0, "replica", 3),
        ("client", CID, 0), ("deliver", "client", CID, "replica", 0),
        ("deliver", "replica", 0, "replica", 1),
        ("drop", "replica", 1, "replica", 2),
        ("deliver", "replica", 1, "replica", 0),
        ("deliver", "replica", 0, "client", CID),
        ("timeout", 2, "suspect"), ("timeout", 2, "vc_escalate"),
        ("deliver", "replica", 2, "replica", 3),
        ("deliver", "replica", 2, "replica", 3),
        ("deliver", "replica", 3, "replica", 2),
        ("deliver", "replica", 3, "replica", 2),
        ("deliver", "replica", 3, "replica", 2),
        ("deliver", "replica", 2, "replica", 3),
        ("client", CID, 2), ("deliver", "client", CID, "replica", 2),
    )
    scope = McScope(
        n_replicas=3, n_standbys=1, reconfig=True, ops_per_client=2,
        crash_budget=0, drop_budget=1, timeout_budget=3,
        timeout_quiescent_only=False, max_view=2, depth_max=6,
        max_states=50_000,
    )
    report = check(scope, ("reconfig_stale_quorum",), prefix=prefix)
    assert report.violation is not None, (
        "reconfig_stale_quorum yielded NO counterexample at its scope"
    )
    assert report.violation["kind"] == "agreement", report.violation
    ce = report.counterexample()
    defended = replay_schedule(dict(ce, mutations=[]))
    assert defended["reproduced"] is False, (
        "stale-quorum counterexample reproduced WITHOUT the mutation — "
        "that is a real protocol bug, not a mutation proof"
    )
    summary["tbmc_stale_quorum"] = {
        "violation": report.violation,
        "schedule_len": len(report.schedule),
        "states_to_find": report.states,
        "defense_replay": {
            "reproduced": False,
            "diverged": defended["error"] is not None,
        },
    }

    # -- 6. reconfig.* series in METRICS.json --------------------------------
    metrics_path = os.path.join(REPO, "METRICS.json")
    snap = registry.dump(metrics_path)
    counters = sorted(k for k in snap.get("counters", {})
                      if k.startswith("reconfig."))
    needed = [
        # membership path (the promotion e2e) + reshard path (the
        # in-process split-identity machine).
        "reconfig.membership_ops", "reconfig.promotions",
        "reconfig.reshard_started", "reconfig.reshard_completed",
        "reconfig.bytes_migrated",
    ]
    for k in needed:
        assert k in counters, (
            f"{k} missing from METRICS.json counters: {counters}"
        )
    summary["metrics"] = {"counters": counters}

    out_path = os.path.join(REPO, "RECONFIG_SMOKE.json")
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    print(f"# reconfig smoke OK -> {out_path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
