"""Device hash table: probe/insert/remove vs a Python dict model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tigerbeetle_tpu  # noqa: F401  (enables x64)
from tigerbeetle_tpu.ops import hash_table as ht

MAX_PROBE = 1 << 9


def make(capacity=256):
    return ht.make_table(capacity, {"val": jnp.uint64})


def keys_of(ints):
    lo = jnp.array([v & ((1 << 64) - 1) for v in ints], jnp.uint64)
    hi = jnp.array([v >> 64 for v in ints], jnp.uint64)
    return lo, hi


def test_insert_then_lookup():
    t = make()
    ids = [1, 2, 3, 1 << 64, (1 << 64) + 1, 0xDEAD << 90]
    lo, hi = keys_of(ids)
    mask = jnp.ones(len(ids), jnp.bool_)
    t, slots = ht.insert(t, lo, hi, mask, {"val": jnp.arange(len(ids), dtype=jnp.uint64)}, MAX_PROBE)
    assert int(t.count) == len(ids)
    assert not bool(t.probe_overflow)

    res = ht.lookup(t, lo, hi, MAX_PROBE)
    assert bool(res.found.all())
    vals = ht.gather_cols(t, res.slot, res.found)["val"]
    np.testing.assert_array_equal(np.asarray(vals), np.arange(len(ids)))

    # Absent keys not found; key 0 resolves to not-found immediately.
    lo2, hi2 = keys_of([99, 0, 1 << 100])
    res2 = ht.lookup(t, lo2, hi2, MAX_PROBE)
    np.testing.assert_array_equal(np.asarray(res2.found), [False, False, False])


def test_collision_heavy_insert():
    # Force lots of collisions: tiny table, many keys (load factor ~0.75).
    t = make(64)
    ids = list(range(1, 49))
    lo, hi = keys_of(ids)
    mask = jnp.ones(len(ids), jnp.bool_)
    t, _ = ht.insert(t, lo, hi, mask, {"val": jnp.array(ids, jnp.uint64)}, MAX_PROBE)
    assert int(t.count) == len(ids)
    res = ht.lookup(t, lo, hi, MAX_PROBE)
    assert bool(res.found.all())
    vals = ht.gather_cols(t, res.slot, res.found)["val"]
    np.testing.assert_array_equal(np.asarray(vals), ids)


def test_incremental_batches_random():
    # Fixed 512-lane batches (pad with key 0) so jit compiles once — mirrors
    # the production fixed-shape 8190-event batches.
    BATCH = 512
    rng = np.random.default_rng(7)
    t = make(1 << 13)
    model = {}
    for batch in range(8):
        ids = rng.integers(1, 1 << 62, size=BATCH).tolist()
        seen = set()
        for j, i in enumerate(ids):  # dedupe within batch by zeroing repeats
            if i in seen:
                ids[j] = 0
            seen.add(i)
        new = [i for i in ids if i and i not in model]
        lo, hi = keys_of(ids)
        res = ht.lookup(t, lo, hi, MAX_PROBE)
        np.testing.assert_array_equal(
            np.asarray(res.found),
            [i != 0 and i in model for i in ids],
            err_msg=f"batch {batch}",
        )
        insert_mask = jnp.array([bool(i) and i in new for i in ids])
        vals = jnp.array([i % 1000 for i in ids], jnp.uint64)
        t, _ = ht.insert(t, lo, hi, insert_mask, {"val": vals}, MAX_PROBE)
        for i in new:
            model[i] = i % 1000
    assert int(t.count) == len(model)
    assert not bool(t.probe_overflow)
    lo, hi = keys_of(list(model)[:BATCH])
    res = ht.lookup(t, lo, hi, MAX_PROBE)
    assert bool(res.found.all())
    vals = ht.gather_cols(t, res.slot, res.found)["val"]
    np.testing.assert_array_equal(np.asarray(vals), list(model.values())[:BATCH])


def test_remove_tombstone_probe_continues():
    # Keys that collide: insert a, b (b probes past a), remove a, lookup b.
    t = make(16)
    # Find two keys with the same home slot.
    import tigerbeetle_tpu.u128 as u128

    ks = jnp.arange(1, 2000, dtype=jnp.uint64)
    homes = np.asarray(u128.mix64(ks, jnp.zeros_like(ks)) & jnp.uint64(15))
    by_home = {}
    for k, h in enumerate(homes, start=1):
        by_home.setdefault(int(h), []).append(k)
        if len(by_home[int(h)]) == 2:
            a, b = by_home[int(h)]
            break
    lo, hi = keys_of([a, b])
    t, slots = ht.insert(t, lo, hi, jnp.ones(2, jnp.bool_), {"val": jnp.array([10, 20], jnp.uint64)}, MAX_PROBE)
    # Remove a -> tombstone; b must still be found (probe passes tombstone).
    la, ha = keys_of([a])
    ra = ht.lookup(t, la, ha, MAX_PROBE)
    t = ht.remove_to_tombstone(t, ra.slot, ra.found)
    assert int(t.count) == 1
    rb = ht.lookup(t, *keys_of([b]), MAX_PROBE)
    assert bool(rb.found.all())
    assert int(ht.gather_cols(t, rb.slot, rb.found)["val"][0]) == 20
    ra2 = ht.lookup(t, la, ha, MAX_PROBE)
    assert not bool(ra2.found.any())


def test_scatter_cols_update():
    t = make()
    ids = [5, 6, 7]
    lo, hi = keys_of(ids)
    t, _ = ht.insert(t, lo, hi, jnp.ones(3, jnp.bool_), {"val": jnp.array([1, 2, 3], jnp.uint64)}, MAX_PROBE)
    res = ht.lookup(t, lo, hi, MAX_PROBE)
    t = ht.scatter_cols(t, res.slot, res.found, {"val": jnp.array([10, 20, 30], jnp.uint64)})
    res2 = ht.lookup(t, lo, hi, MAX_PROBE)
    np.testing.assert_array_equal(
        np.asarray(ht.gather_cols(t, res2.slot, res2.found)["val"]), [10, 20, 30]
    )


def test_insert_under_jit():
    @jax.jit
    def step(t, lo, hi):
        res = ht.lookup(t, lo, hi, MAX_PROBE)
        t2, _ = ht.insert(t, lo, hi, ~res.found, {"val": lo}, MAX_PROBE)
        return t2

    t = make()
    lo, hi = keys_of([11, 12, 13])
    t = step(t, lo, hi)
    t = step(t, lo, hi)  # idempotent: already present
    assert int(t.count) == 3


def _claim_slots_sorted_reference(table, key_lo, key_hi, insert_mask,
                                  max_probe):
    """The pre-PR7 sort-based claim protocol, kept as the parity oracle:
    per iteration every unplaced lane probes home+i, and among unplaced
    lanes sharing a slot the lowest batch index wins (argsort + first-of-
    run).  claim_slots' group-rank rewrite must pick IDENTICAL slots."""
    from tigerbeetle_tpu.u128 import mix64

    capacity = table.capacity
    n = key_lo.shape[0]
    mask = jnp.uint64(capacity - 1)
    home = mix64(key_lo, key_hi) & mask
    sentinel = jnp.uint64(capacity)
    occ = np.asarray(
        (table.key_lo != 0) | (table.key_hi != 0) | table.tombstone
    ).copy()
    home_np = np.asarray(home)
    unplaced = np.asarray(insert_mask).copy()
    claimed = np.full(n, capacity, np.uint64)
    offset = np.zeros(n, np.uint64)
    while unplaced.any():
        cur = (home_np + offset) & np.uint64(capacity - 1)
        cand = np.where(unplaced, cur, np.uint64(capacity))
        order = np.argsort(cand, kind="stable")
        first = np.ones(n, bool)
        first[1:] = cand[order][1:] != cand[order][:-1]
        winner = np.zeros(n, bool)
        winner[order] = first
        win = unplaced & ~occ[cur] & winner
        claimed[win] = cur[win]
        occ[cur[win]] = True
        unplaced = unplaced & ~win
        offset[unplaced] += 1
        if (offset >= max_probe).any():
            break
    return claimed


def test_claim_parity_with_sorted_protocol():
    """The group-rank claim rewrite is bit-identical to the documented
    sort-based protocol, including intra-batch home collisions, masked
    lanes interleaved with live ones, and a well-filled table."""
    rng = np.random.default_rng(0xC1A1)
    t = ht.make_table(1 << 12, {"val": jnp.uint64})
    # Pre-fill to ~45% so probe chains are realistic.
    pre = rng.choice(np.arange(1, 1 << 20), size=1800, replace=False)
    lo, hi = keys_of([int(v) for v in pre])
    t, _ = ht.insert(t, lo, hi, jnp.ones(len(pre), jnp.bool_),
                     {"val": lo}, MAX_PROBE)
    for trial in range(3):
        n = 512
        ids = rng.choice(np.arange(1 << 20, 1 << 21), size=n, replace=False)
        mask_np = rng.random(n) < 0.8  # interleaved masked-out lanes
        lo, hi = keys_of([int(v) for v in ids])
        mask = jnp.asarray(mask_np)
        got, ovf = ht.claim_slots(t, lo, hi, mask, MAX_PROBE)
        want = _claim_slots_sorted_reference(t, lo, hi, mask, MAX_PROBE)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert not bool(ovf)
        # Commit this trial's claims so the next trial sees a fuller table.
        t = ht.write_rows(t, lo, hi, got, mask, {"val": lo})


def test_claim_parity_forced_home_collisions():
    """Many lanes sharing one home slot place in strict batch-lane order
    past the cluster (the lowest-lane-wins rule)."""
    t = ht.make_table(1 << 8, {"val": jnp.uint64})
    # Find 6 keys with the SAME home slot by brute force.
    from tigerbeetle_tpu.u128 import mix64

    cands = np.arange(1, 4000, dtype=np.uint64)
    homes = np.asarray(
        mix64(jnp.asarray(cands), jnp.zeros(len(cands), jnp.uint64))
    ) & np.uint64((1 << 8) - 1)
    target = np.bincount(homes.astype(np.int64)).argmax()
    same = cands[homes == target][:6]
    assert len(same) >= 4
    lo, hi = keys_of([int(v) for v in same])
    mask = jnp.ones(len(same), jnp.bool_)
    got, ovf = ht.claim_slots(t, lo, hi, mask, MAX_PROBE)
    want = _claim_slots_sorted_reference(t, lo, hi, mask, MAX_PROBE)
    np.testing.assert_array_equal(np.asarray(got), want)
    # Lane order == placement order within the shared cluster.
    slots = np.asarray(got)
    assert (np.diff(slots.astype(np.int64)) > 0).all()


# ---------------------------------------------------------------------------
# uint64 columns are written by halves (two one-operand uint32 scatters):
# the tables must not know.
# ---------------------------------------------------------------------------

HIGH_HALF = [1 << 32, 1 << 63, (1 << 64) - 1, (1 << 32) - 1, 0]
WRITE_COLS = {"a": jnp.uint64, "b": jnp.uint64, "c": jnp.uint32,
              "h": jnp.uint16}


def _random_table(rng, capacity):
    """A table whose every array holds noise (high halves included)."""
    def u64(n):
        return rng.integers(0, 1 << 64, size=n, dtype=np.uint64)

    cols = {
        name: (u64(capacity) if dt == jnp.uint64 else
               rng.integers(0, 1 << 16, size=capacity).astype(dt))
        for name, dt in WRITE_COLS.items()
    }
    return ht.Table(
        key_lo=jnp.asarray(u64(capacity)), key_hi=jnp.asarray(u64(capacity)),
        tombstone=jnp.asarray(rng.random(capacity) < 0.1),
        cols={k: jnp.asarray(v) for k, v in cols.items()},
        count=jnp.uint64(12345), probe_overflow=jnp.bool_(False),
    )


def _as_numpy(table):
    return {
        "key_lo": np.asarray(table.key_lo).copy(),
        "key_hi": np.asarray(table.key_hi).copy(),
        "tombstone": np.asarray(table.tombstone).copy(),
        "count": int(table.count),
        **{"col." + k: np.asarray(v).copy() for k, v in table.cols.items()},
    }


def _lane_values(rng, n, dtype=np.uint64):
    vals = rng.integers(0, 1 << 64, size=n, dtype=np.uint64)
    vals[: len(HIGH_HALF)] = np.array(HIGH_HALF, np.uint64)
    return vals.astype(dtype)


@pytest.mark.parametrize("lanes", [64, 8190])
@pytest.mark.parametrize(
    "op", ["write_rows", "scatter_cols", "remove_to_tombstone"])
def test_writes_by_halves_match_a_numpy_oracle(op, lanes):
    """write_rows / scatter_cols / remove_to_tombstone against plain numpy
    fancy assignment: values that use the high half, masked lanes, sentinel
    (dropped) lanes, a full 8190-lane batch."""
    rng = np.random.default_rng(lanes * 7 + len(op))
    capacity = 1 << 15
    table = _random_table(rng, capacity)
    want = _as_numpy(table)

    slot = rng.choice(capacity, size=lanes, replace=False).astype(np.uint64)
    mask = rng.random(lanes) < 0.8
    mask[: len(HIGH_HALF)] = True
    dropped = rng.random(lanes) < 0.1            # sentinel lanes
    dropped[: len(HIGH_HALF)] = False
    slot_in = np.where(dropped, np.uint64(capacity), slot)
    live = mask & ~dropped
    at = slot[live].astype(np.int64)
    key_lo, key_hi = _lane_values(rng, lanes), _lane_values(rng, lanes)[::-1]
    rows = {
        "a": _lane_values(rng, lanes), "b": _lane_values(rng, lanes)[::-1],
        "c": _lane_values(rng, lanes, np.uint32),
        "h": _lane_values(rng, lanes, np.uint16),
    }

    if op == "write_rows":
        def run(t):
            return ht.write_rows(
                t, jnp.asarray(key_lo), jnp.asarray(key_hi),
                jnp.asarray(slot_in), jnp.asarray(mask),
                {k: jnp.asarray(v) for k, v in rows.items()},
            )
        want["key_lo"][at], want["key_hi"][at] = key_lo[live], key_hi[live]
        want["tombstone"][at] = False
        for name, vals in rows.items():
            want["col." + name][at] = vals[live]
        want["count"] += int(live.sum())
    elif op == "scatter_cols":
        updates = {k: rows[k] for k in ("a", "c")}     # "b", "h" untouched
        def run(t):
            return ht.scatter_cols(
                t, jnp.asarray(slot_in), jnp.asarray(mask),
                {k: jnp.asarray(v) for k, v in updates.items()},
            )
        for name, vals in updates.items():
            want["col." + name][at] = vals[live]
    else:
        def run(t):
            return ht.remove_to_tombstone(
                t, jnp.asarray(slot_in), jnp.asarray(live))
        want["key_lo"][at] = want["key_hi"][at] = 0
        want["tombstone"][at] = True
        want["count"] -= int(live.sum())

    got = _as_numpy(jax.jit(run)(table))
    assert got.keys() == want.keys()
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
        assert np.asarray(got[name]).dtype == np.asarray(want[name]).dtype


def _scatter_operands(stablehlo_text):
    """The result types of every stablehlo.scatter in a lowered program's
    text: one per operand (inputs and results correspond)."""
    import re

    out = []
    for found in re.finditer(r'"stablehlo\.scatter"', stablehlo_text):
        end = stablehlo_text.index("}) : (", found.end())
        signature = stablehlo_text[end:stablehlo_text.index("\n", end)]
        out.extend(re.findall(r"tensor<([^>]*)>", signature.split("->")[1]))
    return out


@pytest.mark.parametrize("program", ["fast", "grouped", "general"])
def test_no_commit_program_scatters_a_64_bit_operand(program):
    """The guard: a TPU compiles a 64-bit scatter to ONE scatter with two
    32-bit operands, 10-18x as slow an index as two one-operand scatters
    (hash_table docstring).  None may come back into the commit programs
    (the general kernel as served: no history rows)."""
    from tigerbeetle_tpu import machine, types
    from tigerbeetle_tpu.ops import state_machine as sm
    from tigerbeetle_tpu.ops import transfer_full as tf

    lanes = 256
    led = jax.eval_shape(lambda: sm.make_ledger(1 << 10, 1 << 12, 1 << 8))
    u64 = jax.ShapeDtypeStruct((), jnp.uint64)
    cols = types.to_soa(np.zeros(1, dtype=types.TRANSFER_DTYPE))

    def batch(lead=()):
        return {k: jax.ShapeDtypeStruct(lead + (lanes,), v.dtype)
                for k, v in cols.items()}

    if program == "fast":
        lowered = jax.jit(sm.create_transfers_impl).lower(
            led, batch(), u64, u64)
    elif program == "grouped":
        k = machine.TpuStateMachine.GROUP_K
        kvec = jax.ShapeDtypeStruct((k,), jnp.uint64)
        lowered = machine._group_fast_dispatch.lower(
            led, batch((k,)), kvec, kvec)
    else:
        lowered = tf.create_transfers_full.lower(
            led, batch(), u64, u64, None, None, max_passes=8,
            has_postvoid=True, has_history=False, use_waves=True)
    operands = _scatter_operands(lowered.as_text())
    assert len(operands) > 20, "the parser lost the program's scatters"
    wide = [t for t in operands if t.endswith(("i64", "f64"))]
    assert not wide, f"64-bit scatter operands: {wide}"
