"""CI waves smoke: prove the conflict-index wave scheduler end to end.

In-process (CPU-pinned), three proofs with asserted artifacts, mirroring
the acceptance bar in docs/waves.md:

1. IDENTITY — a seeded Zipfian-hot mix (plain + pending + table post/void)
   committed twice through TpuStateMachine, waves off vs on: per-batch
   results, final ledger digest, and balance snapshots must be identical.
2. FEWER PASSES — the kernel-level wave certification on a conflict-free
   batch: wave_bound == 1 and the Jacobi loop runs ONE pass (vs 2 for the
   stability exit), with every lane in wave 0; a limit-account hazard
   chain must either bound tightly or fall back unscheduled.
3. COUNTERS — the same workload with the metrics registry enabled and
   TB_WAVES on must land waves.* series (batches_scheduled, jacobi_passes,
   wave0_pct) in the METRICS.json snapshot.

Artifact: WAVES_SMOKE.json at the repo root; the ``waves`` tier in
tools/ci.py records pass/fail in CI_LAST.json.

Usage: python tools/waves_smoke.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import jax.numpy as jnp

    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.config import LedgerConfig
    from tigerbeetle_tpu.machine import TpuStateMachine
    from tigerbeetle_tpu.obs.metrics import registry
    from tigerbeetle_tpu.ops import staging
    from tigerbeetle_tpu.ops import state_machine as sm
    from tigerbeetle_tpu.ops import transfer_full as tf

    cfg = LedgerConfig(
        accounts_capacity_log2=10, transfers_capacity_log2=12,
        posted_capacity_log2=10,
    )
    n_accounts = 16

    def mix_batches(seed):
        rng = np.random.default_rng(seed)
        batches = []
        pendings = []
        next_id = 1000
        for _ in range(4):
            specs = []
            # Posts draw only from EARLIER batches' (table) pendings: an
            # in-batch pending reference makes the whole batch
            # unschedulable by design, and the smoke wants scheduled ones.
            avail = list(pendings)
            for _ in range(64):
                dr = 1 + int(n_accounts * rng.random() ** 3) % n_accounts
                cr = 1 + (dr + 1 + int(3 * rng.random())) % n_accounts
                kind = rng.random()
                if kind < 0.6:
                    specs.append(types.transfer(
                        id=next_id, debit_account_id=dr,
                        credit_account_id=cr,
                        amount=1 + int(rng.random() * 50), ledger=1, code=1,
                    ))
                elif kind < 0.8 or not avail:
                    specs.append(types.transfer(
                        id=next_id, debit_account_id=dr,
                        credit_account_id=cr, amount=20, ledger=1, code=1,
                        flags=types.TransferFlags.PENDING,
                    ))
                    pendings.append(next_id)
                else:
                    pid = avail[int(rng.random() * len(avail))]
                    specs.append(types.transfer(
                        id=next_id, pending_id=pid, ledger=1, code=1,
                        flags=types.TransferFlags.POST_PENDING_TRANSFER,
                    ))
                next_id += 1
            batches.append(types.transfers_array(specs))
        return batches

    def run(waves: bool):
        dev = TpuStateMachine(cfg, batch_lanes=128)
        dev.waves_enabled = waves
        dev.create_accounts(types.accounts_array([
            types.account(id=i + 1, ledger=1, code=10)
            for i in range(n_accounts)
        ]), wall_clock_ns=1)
        results = [dev.create_transfers(b) for b in mix_batches(5)]
        return results, f"{dev.digest():#x}", dev.balances_snapshot()

    # 1. IDENTITY ---------------------------------------------------------
    res_off, dig_off, bal_off = run(False)
    res_on, dig_on, bal_on = run(True)
    assert res_off == res_on, "waves on/off result divergence"
    assert dig_off == dig_on, "waves on/off digest divergence"
    assert bal_off == bal_on, "waves on/off balance divergence"

    # 2. FEWER PASSES (kernel-level certification) ------------------------
    led = sm.make_ledger(1 << 8, 1 << 10, 1 << 8)
    acc = np.zeros(64, dtype=types.ACCOUNT_DTYPE)
    acc["id_lo"][:16] = 1 + np.arange(16, dtype=np.uint64)
    acc["ledger"][:16] = 1
    acc["code"][:16] = 10
    led, _ = sm.create_accounts(led, *staging.stage_batch(acc[:16], 64, 16))
    b = np.zeros(64, dtype=types.TRANSFER_DTYPE)
    b["id_lo"][:8] = 100 + np.arange(8, dtype=np.uint64)
    b["debit_account_id_lo"][:8] = 1 + np.arange(8) % 8
    b["credit_account_id_lo"][:8] = 9 + np.arange(8) % 8
    b["amount_lo"][:8] = 5
    b["ledger"][:8] = 1
    b["code"][:8] = 10
    soa = {k: jnp.asarray(v) for k, v in types.to_soa(b).items()}
    lane = jnp.arange(64, dtype=jnp.int32)
    valid = lane < 8
    ctx = tf.build_gather_ctx(led, soa, valid, jnp.zeros((64,), jnp.bool_))
    plan_on = tf._kernel_core(
        ctx, soa, jnp.uint64(8), jnp.uint64(24), use_waves=True
    )
    plan_off = tf._kernel_core(ctx, soa, jnp.uint64(8), jnp.uint64(24))
    passes_on, passes_off = int(plan_on.passes), int(plan_off.passes)
    bound = int(plan_on.wave_bound)
    hist = np.asarray(plan_on.wave_hist).tolist()
    assert bound == 1, f"conflict-free batch not certified: bound={bound}"
    assert passes_on == 1 and passes_off == 2, (passes_on, passes_off)
    assert hist[0] == 8 and sum(hist[1:]) == 0, hist
    assert np.asarray(plan_on.codes[:8]).tolist() == (
        np.asarray(plan_off.codes[:8]).tolist()
    )

    # 3. COUNTERS ---------------------------------------------------------
    registry.enable()
    try:
        dev = TpuStateMachine(cfg, batch_lanes=128)
        dev.waves_enabled = True
        dev.create_accounts(types.accounts_array([
            types.account(id=i + 1, ledger=1, code=10)
            for i in range(n_accounts)
        ]), wall_clock_ns=1)
        for batch in mix_batches(9):
            dev.create_transfers(batch)
        snap = registry.snapshot()
        metrics_path = os.path.join(REPO, "METRICS.json")
        registry.dump(metrics_path)
    finally:
        registry.disable()
    counters = snap["counters"]
    hists = snap["histograms"]
    scheduled = counters.get("waves.batches_scheduled", 0)
    assert scheduled > 0, "no batch was wave-scheduled"
    assert "waves.jacobi_passes" in hists, sorted(hists)
    assert "waves.wave0_pct" in hists, sorted(hists)
    with open(metrics_path) as f:
        dumped = json.load(f)
    assert "waves.batches_scheduled" in dumped.get("counters", {}), (
        "waves counters missing from METRICS.json"
    )

    out = {
        "identity": {"digest": dig_on, "batches": len(res_on)},
        "certification": {
            "passes_off": passes_off, "passes_on": passes_on,
            "bound": bound, "wave_hist": hist,
        },
        "counters": {
            "batches_scheduled": scheduled,
            "batches_unscheduled": counters.get(
                "waves.batches_unscheduled", 0
            ),
            "jacobi_passes_p50": hists["waves.jacobi_passes"].get("p50"),
            "wave0_pct_p50": hists["waves.wave0_pct"].get("p50"),
        },
        "green": True,
    }
    with open(os.path.join(REPO, "WAVES_SMOKE.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
