"""What ordering one level of the secondary index costs on the chip, form by
form: device milliseconds and compile seconds.

    chiprun -- python3 tools/index_sort_probe.py [<out dir> [<rows>[:<form>,...] ...]]

(default `chiprun_out/index_sort_probe`, 16,384 and 2,097,152 rows, every
form; `2097152:gathers,columns` times those two alone there: a form's
compile is charged to the chip).  A level is five `uint64` columns ordered
by (acct_hi, acct_lo, ts), as `jnp.lexsort` would:
`ops/index.py:_sort_level`.  The forms:

- `gathers`: the form the tree held until PR 45: three stable single-key
  `argsort`s that move nothing but a permutation, the next pass's key and
  the permutation itself gathered between them, all five columns at the end
  (18 one-column `u32` gathers).
- `columns`: `ops/index.py:_sort_level` itself, what ships since PR 45: each
  of the three passes sorts its key column beside ONE other column at a
  time, twelve stable (u64, u64) sorts of one signature; no gather.
- `halves`: the same with one `u32` HALF of a column at a time: twenty-four
  stable (u64, u32) sorts, the signature of an `argsort`'s own sort.
- `batched`: each pass is ONE sort of two (4, n) operands along the rows:
  the key broadcast four times, the other four columns stacked.
- `passes`: three stable single-key `lax.sort`s, each with the other four
  columns as payload (ten `u32` halves travel; no gather).
- `threekey`: one `lax.sort` with `num_keys=3` over all five columns.
- `mixed`: the three key columns and an `s32` iota travel through the
  passes, `tid_lo` / `tid_hi` are gathered once at the end (4 gathers).

Every form's output is held to `gathers`' on the device, bit for bit, before
it is timed.  Device ms: the median of RUNS executions in one profiler
window, read from the trace's own program line (`tools/trace_ops.py`'s
walk); compile seconds: `lower().compile()` on the host clock, the
persistent compile cache off.  One JSON object a (rows, form) on stdout and
in `<out dir>/probe.jsonl`.  This is a probe, not a path: the program calls
`ops/index.py:_sort_level` and nothing here (`gathers` must be among a
size's forms: the others are held to it).
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tigerbeetle_tpu.ops.index import (  # noqa: E402
    COLS, _PASSES as KEYS, _sort_level)
from tools import trace_ops  # noqa: E402
from benchmarks.harness.trace_reduce import MODULES_LINE  # noqa: E402

RUNS = 10


def gathers(lvl):
    order = jnp.argsort(lvl["ts"], stable=True)
    order = order[jnp.argsort(lvl["acct_lo"][order], stable=True)]
    order = order[jnp.argsort(lvl["acct_hi"][order], stable=True)]
    return {name: lvl[name][order] for name in COLS}


def columns(lvl):
    return _sort_level(lvl)


def halves(lvl):
    def beside(key, half):
        return jax.lax.sort((key, half), num_keys=1, is_stable=True)

    for key in KEYS:
        moved = {}
        for name in COLS:
            if name != key:
                moved[key], hi = beside(
                    lvl[key], (lvl[name] >> 32).astype(jnp.uint32))
                _, lo = beside(lvl[key], lvl[name].astype(jnp.uint32))
                moved[name] = (hi.astype(jnp.uint64) << 32) | lo.astype(
                    jnp.uint64)
        lvl = moved
    return {name: lvl[name] for name in COLS}


def batched(lvl):
    for key in KEYS:
        rest = [name for name in COLS if name != key]
        keys, moved = jax.lax.sort(
            (jnp.broadcast_to(lvl[key], (len(rest),) + lvl[key].shape),
             jnp.stack([lvl[name] for name in rest])),
            dimension=1, num_keys=1, is_stable=True)
        lvl = {key: keys[0], **dict(zip(rest, moved))}
    return {name: lvl[name] for name in COLS}


def _pass(cols: dict, key: str) -> dict:
    names = (key,) + tuple(name for name in cols if name != key)
    moved = jax.lax.sort(
        tuple(cols[name] for name in names), num_keys=1, is_stable=True)
    return dict(zip(names, moved))


def passes(lvl):
    for key in KEYS:
        lvl = _pass(lvl, key)
    return {name: lvl[name] for name in COLS}


def threekey(lvl):
    names = ("acct_hi", "acct_lo", "ts", "tid_lo", "tid_hi")
    moved = jax.lax.sort(
        tuple(lvl[name] for name in names), num_keys=3, is_stable=True)
    lvl = dict(zip(names, moved))
    return {name: lvl[name] for name in COLS}


def mixed(lvl):
    n = lvl["ts"].shape[0]
    cols = {key: lvl[key] for key in KEYS}
    cols["row"] = jnp.arange(n, dtype=jnp.int32)
    for key in KEYS:
        cols = _pass(cols, key)
    row = cols.pop("row")
    return {**cols, "tid_lo": lvl["tid_lo"][row], "tid_hi": lvl["tid_hi"][row]}


FORMS = (gathers, columns, halves, batched, passes, threekey, mixed)


def level(rows: int, seed: int) -> dict:
    """Duplicate accounts, `acct_hi` != 0, timestamps that differ in the
    high half only, and a tail of sentinels, as a partial run has."""
    rng = np.random.default_rng(seed)
    live = rows - rows // 16
    u64 = np.uint64
    cols = {
        "acct_lo": rng.integers(1, max(2, rows // 8), rows).astype(u64)
        | (rng.integers(0, 2, rows).astype(u64) << u64(40)),
        "acct_hi": rng.integers(0, 3, rows).astype(u64),
        "ts": rng.integers(1, max(2, rows // 4), rows).astype(u64) << u64(30),
        "tid_lo": rng.integers(1, 1 << 62, rows).astype(u64),
        "tid_hi": rng.integers(0, 1 << 62, rows).astype(u64),
    }
    for col in cols.values():
        col[live:] = u64((1 << 64) - 1)
    return {name: jnp.asarray(cols[name]) for name in COLS}


def device_ms(trace_dir: str) -> dict:
    """{program: median device ms} of the one profile under `trace_dir`."""
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    by_program: dict = {}
    try:
        programs = trace_ops.device_rows(path)[MODULES_LINE]
    except ValueError:      # no device plane: a CPU rehearsal has no device ms
        return {}
    for name, _stats, _start, dur in programs:
        by_program.setdefault(trace_ops.hs._short(name), []).append(dur / 1e6)
    return {name: statistics.median(durs) for name, durs in by_program.items()}


def main(argv) -> int:
    out_dir = argv[1] if len(argv) > 1 else "chiprun_out/index_sort_probe"
    sizes = [a.partition(":") for a in argv[2:]] or [
        ("16384", "", ""), ("2097152", "", "")]
    os.makedirs(out_dir, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", False)
    device = jax.devices()[0]
    lines = []
    for rows, _, chosen in sizes:
        rows = int(rows)
        lvl = level(rows, seed=rows)
        want = None
        compiled = {}
        for form in FORMS:
            if chosen and form.__name__ not in chosen.split(","):
                continue
            def named(cols, form=form):
                return form(cols)
            named.__name__ = f"probe_{form.__name__}_{rows}"
            t0 = time.monotonic()
            exe = jax.jit(named).lower(lvl).compile()
            compile_s = time.monotonic() - t0
            got = jax.block_until_ready(exe(lvl))
            if want is None:
                want = got
            same = all(bool((got[name] == want[name]).all()) for name in COLS)
            compiled[named.__name__] = (form.__name__, exe, compile_s, same)
        trace_dir = os.path.join(out_dir, f"trace_{rows}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        host_ms = {}
        with jax.profiler.trace(trace_dir):
            for name, (_form, exe, _s, _same) in compiled.items():
                t0 = time.monotonic()
                for _ in range(RUNS):
                    out = exe(lvl)
                jax.block_until_ready(out)
                host_ms[name] = (time.monotonic() - t0) * 1e3 / RUNS
        on_device = device_ms(trace_dir)
        for name, (form, _exe, compile_s, same) in compiled.items():
            ms = next((v for k, v in on_device.items() if name in k), None)
            lines.append({
                "rows": rows, "form": form, "device_ms": ms,
                "host_ms_each_of_%d_enqueued_together" % RUNS: host_ms[name],
                "compile_s": compile_s, "same_as_gathers": same,
                "platform": device.platform, "kind": device.device_kind,
            })
            print(json.dumps(lines[-1]), flush=True)
    with open(os.path.join(out_dir, "probe.jsonl"), "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0 if all(line["same_as_gathers"] for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
