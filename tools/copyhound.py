"""copyhound: hunt unnecessary large copies in the COMPILED serving kernels.

The reference's copyhound (/root/reference/src/copyhound.zig) walks LLVM IR
hunting memcpys of aggregates — copies the source language made too easy to
write by accident.  The TPU-native analogue: walk the XLA-compiled HLO of
every serving kernel hunting table-sized ``copy`` instructions.  On this
architecture an accidental copy is not a few cache lines, it is a whole
HBM-resident hash-table column — the round-4/5 perf forensics repeatedly
traced mystery milliseconds to exactly such copies (donation not
propagating, aliasing broken by a reshape, a while-loop carry
double-buffered).

For each kernel variant this tool compiles the same program the dispatcher
runs (donated ledger, batch derived in-jit), walks the optimized HLO, and
reports every copy instruction at or above --min-mb, grouped by shape.
A healthy donated kernel shows ZERO table-sized copies; anything else is a
lead with the exact HLO instruction name to chase.

Usage: python tools/copyhound.py [--min-mb 1.0] [--out COPYHOUND.json]
       (runs on whatever backend jaxenv resolves; CPU lowering is a good
       donation-regression canary even though TPU is the target)
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# u4 is 4 bits; pred is 1 byte in practice.
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COPY_RE = re.compile(
    r"%?([\w.-]+)\s*=\s*(\w+)\[([\d,]*)\][^=]*\bcopy\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 8)


def scan_hlo(hlo_text: str, min_bytes: int):
    """Every copy instruction >= min_bytes as (name, dtype[dims], bytes)."""
    out = []
    for m in _COPY_RE.finditer(hlo_text):
        name, dtype, dims = m.groups()
        size = _shape_bytes(dtype, dims)
        if size >= min_bytes:
            out.append({
                "instruction": name,
                "shape": f"{dtype}[{dims}]",
                "mb": round(size / 1e6, 2),
            })
    return sorted(out, key=lambda r: -r["mb"])


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--min-mb", type=float, default=1.0)
    p.add_argument("--table-log2", type=int, default=18,
                   help="transfers-table capacity (log2 slots)")
    p.add_argument("--out", default=os.path.join(REPO, "COPYHOUND.json"))
    args = p.parse_args()

    from tigerbeetle_tpu import jaxenv

    jaxenv.enable_compile_cache()
    platform = jaxenv.backend_info()[0]
    print(f"# platform={platform}", file=sys.stderr)

    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu import u128
    from tigerbeetle_tpu.ops import state_machine as sm
    from tigerbeetle_tpu.ops import transfer_full as tf

    N, COUNT, NA = 8192, 8190, 1024
    TABLE = 1 << args.table_log2
    ledger = sm.make_ledger(1 << 12, TABLE, 1 << 14)
    min_bytes = int(args.min_mb * 1e6)

    from tigerbeetle_tpu.utils.benchgen import gen_plain as _gp, gen_twop as _gt

    def gen_plain(b):
        return _gp(b, lanes=N, count=COUNT, n_accounts=NA)

    def gen_twop(b):
        return _gt(b, lanes=N, count=COUNT, n_accounts=NA)

    def fast_multi(led, fails, b0):
        def body(i, c):
            led2, f = c
            led2, codes = sm.create_transfers_impl(
                led2, gen_plain(b0 + i.astype(jnp.uint64)),
                jnp.uint64(COUNT), jnp.uint64(1 << 20) + b0,
            )
            return led2, f + jnp.sum(codes.astype(jnp.uint64))

        return jax.lax.fori_loop(0, 8, body, (led, fails))

    def general_multi(gen, has_postvoid):
        def multi(led, fails, b0):
            def body(i, c):
                led2, f = c
                led2, codes, *_ = tf.create_transfers_full_impl(
                    led2, gen(b0 + i.astype(jnp.uint64)),
                    jnp.uint64(COUNT), jnp.uint64(1 << 20) + b0,
                    has_postvoid=has_postvoid, has_history=False,
                )
                return led2, f + jnp.sum(codes.astype(jnp.uint64))

            return jax.lax.fori_loop(0, 8, body, (led, fails))

        return multi

    kernels = {
        "fast_multi_donated": fast_multi,
        "general_plain_multi_donated": general_multi(gen_plain, False),
        "general_twop_multi_donated": general_multi(gen_twop, True),
    }
    report = {"platform": platform, "min_mb": args.min_mb,
              "table_slots": TABLE, "kernels": {}}
    worst = 0.0
    for name, fn in kernels.items():
        jfn = jax.jit(fn, donate_argnames=("led", "fails"))
        lowered = jfn.lower(ledger, jnp.uint64(0), jnp.uint64(0))
        hlo = lowered.compile().as_text()
        found = scan_hlo(hlo, min_bytes)
        report["kernels"][name] = {
            "hlo_bytes": len(hlo),
            "large_copies": found[:40],
            "large_copy_count": len(found),
            "largest_mb": found[0]["mb"] if found else 0.0,
        }
        worst = max(worst, found[0]["mb"] if found else 0.0)
        print(f"# {name}: {len(found)} copies >= {args.min_mb} MB"
              + (f", largest {found[0]['mb']} MB ({found[0]['shape']})"
                 if found else ""), file=sys.stderr)
    report["largest_copy_mb"] = worst
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: report["kernels"][k]["large_copy_count"]
                      for k in report["kernels"]} | {
                          "largest_copy_mb": worst}))


if __name__ == "__main__":
    main()
