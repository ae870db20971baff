"""CI sanitize smoke: prove the TB_SANITIZE runtime sanitizer end to end.

Four proofs, each asserting the artifact (not just the exit code; the
staging pool's donation poisoning and the cached template's guard went with
the pool and the template in PR 46):

1. STEADY SERVING IS COMPILE-FREE — a real TpuStateMachine under
   TB_SANITIZE=1: warmup + one warm group absorb every first-use jit,
   then a strict-armed serving region of grouped commits must observe
   ZERO XLA compiles (the PR 10 recompile class, asserted at the source).
2. INJECTED VIOLATIONS ARE CAUGHT — one deliberate violation of each
   sanitizer check must raise SanitizeError: a leaked registry enable (the
   leak guard), and a forced recompile inside a strict tripwire region.
3. VOPR UNDER SANITIZE — a pinned seed runs green with TB_SANITIZE=1
   (the sanitizer must never shift a schedule: it only reads and counts).
4. COUNTERS IN METRICS.json — the sanitize.* series land in the registry
   snapshot dumped to METRICS.json, like every other smoke tier.

Artifact: SANITIZE_SMOKE.json at the repo root; the ``sanitize`` tier in
tools/ci.py records pass/fail in CI_LAST.json.

Usage: python tools/sanitize_smoke.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["TB_SANITIZE"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    summary: dict = {"green": False, "checks": {}}

    from tigerbeetle_tpu import jaxenv

    jaxenv.enable_compile_cache()
    jaxenv.force_cpu()

    import numpy as np

    from tigerbeetle_tpu import sanitize as san
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import LedgerConfig
    from tigerbeetle_tpu.machine import TpuStateMachine
    from tigerbeetle_tpu.obs.metrics import registry

    assert san.enabled(), "TB_SANITIZE must be armed for this smoke"
    assert jaxenv.instrument_compiles(), "compile listener unavailable"

    registry.reset()
    registry.enable()
    try:
        lanes, n_accounts = 64, 16
        m = TpuStateMachine(
            LedgerConfig(accounts_capacity_log2=10,
                         transfers_capacity_log2=12,
                         posted_capacity_log2=10),
            batch_lanes=lanes,
        )
        accs = types.accounts_array([
            types.account(id=i + 1, ledger=1, code=10)
            for i in range(n_accounts)
        ])
        assert m.create_accounts(accs, wall_clock_ns=1000) == []
        m.warmup()

        def group(first_id: int, k: int = 2, n: int = 8):
            batches = [
                types.transfers_array([
                    types.transfer(
                        id=first_id + 100 * j + i,
                        debit_account_id=1 + i % (n_accounts - 1),
                        credit_account_id=2 + i % (n_accounts - 2),
                        amount=1 + i, ledger=1, code=1,
                    )
                    for i in range(n)
                ])
                for j in range(k)
            ]
            tss = [m.prepare("create_transfers", n, 0) for _ in batches]
            res = m.commit_group_fast(batches, tss)
            assert res is not None and all(r == [] for r in res), res

        # -- 1. steady serving: zero compiles, strict-armed --------------
        # Warm groups absorb every first-use jit INCLUDING the Bentley-
        # Saxe index levels the timed region will touch: 8 groups = 16
        # appends builds levels 0-4 (a new level first merges at append
        # 2^k); the 8 timed appends then stay under the 32-append
        # boundary, so the steady region compiles NOTHING — raw.
        for g in range(8):
            group(10_000 + 1_000 * g)
        m._sanitize_arm_tripwire()
        os.environ["TB_SANITIZE_STRICT"] = "1"
        compiles0 = jaxenv.compile_count()
        for g in range(4):
            group(30_000 + 1_000 * g)  # strict: a recompile would raise
        os.environ.pop("TB_SANITIZE_STRICT", None)
        serving_compiles = jaxenv.compile_count() - compiles0
        assert serving_compiles == 0, (
            f"{serving_compiles} compile(s) in the steady serving region"
        )
        summary["checks"]["serving"] = {
            "timed_groups": 4, "serving_compiles": serving_compiles,
        }

        # -- 2. injected violations all caught ---------------------------
        caught = {}

        import jax.numpy as jnp

        try:
            san.assert_registry_disabled("smoke scope")  # registry IS on
        except san.SanitizeError:
            caught["registry_leak"] = True
        registry.enable()  # the guard disarmed it; re-arm for the dump

        try:
            with san.compile_tripwire("smoke region", raise_on_trip=True):
                import jax

                jax.jit(lambda x: x * 7 + 3)(
                    jnp.ones((29,), jnp.uint32)
                ).block_until_ready()
        except san.SanitizeError:
            caught["forced_recompile"] = True

        assert caught == {
            "registry_leak": True, "forced_recompile": True,
        }, f"injected violations not all caught: {caught}"
        summary["checks"]["injected_violations"] = caught

        # -- 3. VOPR under sanitize --------------------------------------
        from tigerbeetle_tpu.sim.vopr import run_seed

        result = run_seed(7, ticks=250)
        assert result.exit_code == 0, (
            f"VOPR seed 7 failed under TB_SANITIZE: {result.exit_code}"
        )
        summary["checks"]["vopr"] = {
            "seed": result.seed, "exit": result.exit_code,
        }

        # -- 4. sanitize.* counters in METRICS.json ----------------------
        snap = registry.snapshot()
        metrics_path = os.path.join(REPO, "METRICS.json")
        registry.dump(metrics_path)
    finally:
        registry.disable()
        registry.reset()

    sanitize_series = {
        k: v for k, v in snap["counters"].items()
        if k.startswith("sanitize.")
    }
    for needed in ("sanitize.recompiles", "sanitize.registry_leaks"):
        assert sanitize_series.get(needed, 0) > 0, (
            f"{needed} missing/zero in the registry snapshot: "
            f"{sorted(sanitize_series)}"
        )
    with open(metrics_path) as f:
        dumped = json.load(f)
    assert "sanitize.recompiles" in dumped.get("counters", {}), (
        "sanitize counters missing from METRICS.json"
    )
    summary["checks"]["counters"] = sanitize_series

    summary["green"] = True
    out_path = os.path.join(REPO, "SANITIZE_SMOKE.json")
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
