"""What staging one request, or one grouped run, costs the host on the chip,
form by form: milliseconds until the last `device_put` returns, and until a
trivial program that reads every operand has run.

    chiprun -- python3 tools/stage_probe.py [<out dir> [<k> ...]]

(default `chiprun_out/stage_probe`, a run of k = 7; lanes 8192, batches of
8190 transfers).  The forms:

- `columns`: what every one-chip route did until PR 46 (`machine._pad_soa`):
  the batch zero-padded to the lanes, `types.to_soa`, 19 `jnp.asarray` of one
  column each and two eager scalars (`jnp.uint64(count)`, `(timestamp)`).
- `packed`: `ops/staging.stage_batch`, what ships since PR 46 (and on the
  mesh since PR 38): `uint64[14, lanes]`, `uint32[5, lanes]`, `uint64[2]` in
  ONE `device_put`; `packed_pooled` fills one kept set of host buffers
  instead of fresh `np.zeros`.
- `group_columns`: the grouped route until PR 46 (`machine._stage_group`): a
  kept set of 19 `(32, lanes)` host buffers, k rows filled, 19 `device_put`s
  of the WHOLE buffers and two eager `jnp.asarray(..., uint64)`.
- `group_packed_32` / `group_packed_8`: `ops/staging.stage_group` at 32 and
  at 8 rows: `uint64[rows, 14, lanes]`, `uint32[rows, 5, lanes]`,
  `uint64[2, rows]` in one put; `*_pooled` as above.
- `group_chunks_32`: the 32-row stack as FOUR chunks of 8 rows (eight host
  arrays and `uint64[2, 32]`) in ONE `device_put` of a tuple: the bytes of
  `group_packed_32`, transferred array by array (a lead, not a path:
  PERF.md section 7).
- `group_rows`: k per-row `(uint64[14, lanes], uint32[5, lanes])` pairs and
  one `uint64[2, 32]` in ONE `device_put` of a tuple, read by a program of
  32 pairs whose other 32 - k are one kept zero pair on the device: a
  single program that uploads k rows' bytes.

Each form is run 3 + 24 times on one device; the line gives the medians of
the 24 and the host bytes handed to `device_put`.  This is a probe, not a
path: the program stages through `ops/staging.py` and nothing here.  It
rehearses on the CPU (where the numbers are the CPU's and say nothing).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from tigerbeetle_tpu import types  # noqa: E402
from tigerbeetle_tpu.ops import staging  # noqa: E402

LANES = 8192
COUNT = 8190
GROUP_K = 32
WARM, RUNS = 3, 24
DTYPE = types.TRANSFER_DTYPE


def batches(k: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        b = np.zeros(COUNT, DTYPE)
        for name in DTYPE.names:
            dt = DTYPE.fields[name][0]
            b[name] = rng.integers(1, np.iinfo(dt).max, COUNT, dtype=dt)
        out.append(b)
    return out


def _nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def _touch(tree):
    """One element of every operand: the program cannot start before each
    is on the device, and does next to nothing itself."""
    return sum(
        leaf.reshape(-1)[0].astype(jnp.uint64)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


read = jax.jit(_touch)


# -- one request ------------------------------------------------------------

def columns(bs, _pool):
    (b,) = bs[:1]
    padded = np.zeros(LANES, dtype=b.dtype)
    padded[:len(b)] = b
    host = types.to_soa(padded)
    soa = {k: jnp.asarray(v) for k, v in host.items()}
    ops = (soa, jnp.uint64(len(b)), jnp.uint64(12345))
    return ops, _nbytes(host) + 16, 21


def packed(bs, _pool):
    ops = staging.stage_batch(bs[0], LANES, 12345)
    return ops, _nbytes(ops), 1


def _fill(cols64, cols32, b):
    wide, narrow = staging.staged_names(b.dtype)
    n = len(b)
    for i, name in enumerate(wide):
        cols64[i, :n] = b[name]
        cols64[i, n:] = 0
    for i, name in enumerate(narrow):
        cols32[i, :n] = b[name]
        cols32[i, n:] = 0


def packed_pooled(bs, pool):
    bufs = pool.setdefault("one", (
        np.zeros((14, LANES), np.uint64), np.zeros((5, LANES), np.uint32)))
    _fill(*bufs, bs[0])
    host = (*bufs, np.array([len(bs[0]), 12345], np.uint64))
    return jax.device_put(host), _nbytes(host), 1


# -- a grouped run ----------------------------------------------------------

def group_columns(bs, pool):
    bufs = pool.get("cols")
    if bufs is None:
        bufs = pool["cols"] = {}
        for name in DTYPE.names:
            dt = DTYPE.fields[name][0]
            if dt == np.uint16:
                dt = np.dtype(np.uint32)
            bufs[name] = np.zeros((GROUP_K, LANES), dt)
    k = len(bs)
    for name, buf in bufs.items():
        for j, b in enumerate(bs):
            buf[j, len(b):] = 0
            buf[j, :len(b)] = b[name]
    stacked = {name: jax.device_put(buf) for name, buf in bufs.items()}
    cnt = jnp.asarray([len(b) for b in bs] + [0] * (GROUP_K - k),
                      dtype=jnp.uint64)
    tss = jnp.asarray([12345] * GROUP_K, dtype=jnp.uint64)
    return (stacked, cnt, tss), _nbytes(bufs) + 2 * 8 * GROUP_K, 21


def _group_packed(rows):
    def form(bs, _pool):
        ops = staging.stage_group(bs, LANES, [12345] * len(bs), rows)
        return ops, _nbytes(ops), 1
    form.__name__ = f"group_packed_{rows}"
    return form


def _group_packed_pooled(rows):
    def form(bs, pool):
        bufs = pool.setdefault(rows, (
            np.zeros((rows, 14, LANES), np.uint64),
            np.zeros((rows, 5, LANES), np.uint32)))
        for j, b in enumerate(bs):
            _fill(bufs[0][j], bufs[1][j], b)
        meta = np.zeros((2, rows), np.uint64)
        meta[0, :len(bs)] = [len(b) for b in bs]
        meta[1] = 12345
        host = (*bufs, meta)
        return jax.device_put(host), _nbytes(host), 1
    form.__name__ = f"group_packed_{rows}_pooled"
    return form


def group_chunks_32(bs, _pool):
    chunks = [(np.zeros((8, 14, LANES), np.uint64),
               np.zeros((8, 5, LANES), np.uint32)) for _ in range(GROUP_K // 8)]
    for j, b in enumerate(bs):
        c64, c32 = chunks[j // 8]
        _fill(c64[j % 8], c32[j % 8], b)
    meta = np.zeros((2, GROUP_K), np.uint64)
    meta[0, :len(bs)] = [len(b) for b in bs]
    meta[1] = 12345
    host = (tuple(chunks), meta)
    return jax.device_put(host), _nbytes(host), 1


def group_rows(bs, pool):
    zero = pool.get("zero")
    if zero is None:
        zero = pool["zero"] = jax.device_put((
            np.zeros((14, LANES), np.uint64), np.zeros((5, LANES), np.uint32)))
    host = []
    for b in bs:
        pair = (np.zeros((14, LANES), np.uint64),
                np.zeros((5, LANES), np.uint32))
        _fill(*pair, b)
        host.append(pair)
    meta = np.zeros((2, GROUP_K), np.uint64)
    meta[0, :len(bs)] = [len(b) for b in bs]
    meta[1] = 12345
    rows, meta_dev = jax.device_put((tuple(host), meta))
    ops = (rows + (zero,) * (GROUP_K - len(bs)), meta_dev)
    return ops, _nbytes(host) + meta.nbytes, 1


ONE = (columns, packed, packed_pooled)
GROUP = (group_columns, _group_packed(GROUP_K), _group_packed_pooled(GROUP_K),
         _group_packed(8), _group_packed_pooled(8), group_chunks_32,
         group_rows)


def time_form(form, bs) -> dict:
    pool: dict = {}
    put_ms, ready_ms = [], []
    nbytes = puts = 0
    for i in range(WARM + RUNS):
        t0 = time.perf_counter()
        ops, nbytes, puts = form(bs, pool)
        t1 = time.perf_counter()
        jax.block_until_ready(read(ops))
        t2 = time.perf_counter()
        if i >= WARM:
            put_ms.append((t1 - t0) * 1e3)
            ready_ms.append((t2 - t0) * 1e3)
        del ops
    return {
        "form": form.__name__, "k": len(bs), "puts": puts, "bytes": nbytes,
        "put_ms": round(statistics.median(put_ms), 3),
        "ready_ms": round(statistics.median(ready_ms), 3),
        "put_ms_max": round(max(put_ms), 3),
    }


def main(argv) -> int:
    out_dir = argv[1] if len(argv) > 1 else "chiprun_out/stage_probe"
    ks = [int(a) for a in argv[2:]] or [7]
    os.makedirs(out_dir, exist_ok=True)
    device = jax.devices()[0]
    lines = []
    for form in ONE:
        lines.append(time_form(form, batches(1, seed=1)))
    for k in ks:
        for form in GROUP:
            if "_8" in form.__name__ and k > 8:
                continue
            lines.append(time_form(form, batches(k, seed=k)))
    with open(os.path.join(out_dir, "probe.jsonl"), "w") as f:
        for line in lines:
            line["device"] = f"{device.platform}:{device.device_kind}"
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
