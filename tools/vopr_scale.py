"""Run the pmapped VOPR clean model at scale and record throughput.

Writes VOPR_TPU_SCALE.json: schedules run, violations (must be 0),
schedules/minute on the measuring backend.  The round-3 verdict asked for
the clean model to stay clean at >= 100k schedules with the rate recorded
(BASELINE config 5's search-throughput claim needs a number, not an
adjective).

Usage: python tools/vopr_scale.py [--schedules 100000] [--steps 200]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--schedules", type=int, default=100_000)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=8192)
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    from tigerbeetle_tpu import jaxenv

    jaxenv.enable_compile_cache()
    if args.force_cpu:
        jaxenv.force_cpu()
    import jax

    from tigerbeetle_tpu.sim import vopr_tpu

    platform = jax.devices()[0].platform
    harsh = dict(vopr_tpu.HARSH_FAULTS)

    total = 0
    violations = 0
    # Warmup batch compiles; excluded from the timed region.
    vopr_tpu.run(seed=0, n_clusters=args.batch, n_steps=args.steps, **harsh)
    t0 = time.time()
    seed = 1
    while total < args.schedules:
        v = vopr_tpu.run(seed=seed, n_clusters=args.batch,
                         n_steps=args.steps, **harsh)
        total += len(v)
        violations += int(v.sum())
        seed += 1
        elapsed = time.time() - t0
        print(f"# {total} schedules, {violations} violations, "
              f"{total / max(elapsed, 1e-9) * 60:.0f}/min", file=sys.stderr)
    elapsed = time.time() - t0
    out = {
        "schedules": total,
        "steps_per_schedule": args.steps,
        "violations": violations,
        "elapsed_s": round(elapsed, 1),
        "schedules_per_minute": round(total / elapsed * 60),
        "platform": platform,
        "faults": harsh,
        "captured_iso": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(os.path.join(REPO, "VOPR_TPU_SCALE.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    assert violations == 0, f"{violations} clean-model violations"


if __name__ == "__main__":
    main()
