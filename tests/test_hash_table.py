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


@pytest.mark.parametrize("case", ["every_lane", "masked_lanes",
                                  "half_full", "one_home"])
def test_a_claim_into_an_empty_table_is_claim_slots(case):
    """claim_slots_empty (a rehash's claim: lanes by the million, no sort)
    picks the slots claim_slots picks on an empty table."""
    rng = np.random.default_rng(0xE3F7)
    capacity, n = 1 << 10, 400
    ids = rng.choice(np.arange(1, 1 << 20), size=n, replace=False)
    mask_np = np.ones(n, bool)
    if case == "masked_lanes":
        mask_np = rng.random(n) < 0.6
    elif case == "half_full":
        n = capacity // 2
        ids = rng.choice(np.arange(1, 1 << 20), size=n, replace=False)
        mask_np = np.ones(n, bool)
    elif case == "one_home":
        from tigerbeetle_tpu.u128 import mix64

        cands = np.arange(1, 40_000, dtype=np.uint64)
        homes = np.asarray(mix64(
            jnp.asarray(cands), jnp.zeros(len(cands), jnp.uint64))
        ) & np.uint64(capacity - 1)
        ids = np.concatenate([cands[homes == 7][:20], ids[:200]])
        mask_np = np.ones(len(ids), bool)
    lo, hi = keys_of([int(v) for v in ids])
    mask = jnp.asarray(mask_np)
    want, overflow = ht.claim_slots(
        ht.make_table(capacity, {"val": jnp.uint64}), lo, hi, mask, capacity)
    got = jax.jit(ht.claim_slots_empty, static_argnums=0)(
        capacity, lo, hi, mask)
    assert not bool(overflow)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (np.asarray(got)[~mask_np] == capacity).all()


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
# lookup probes in two phases (all lanes, then the open tail at window
# width): its result must be the single loop's, bit for bit.
# ---------------------------------------------------------------------------


def _lookup_single_loop_reference(table, key_lo, key_hi, max_probe,
                                  hash_shift=0):
    """The single-loop probe protocol lookup ran before it narrowed, kept as
    the parity oracle: every open lane reads home+i on trip i, until no lane
    is open or i == max_probe.  Returns (found, slot, overflow, open lanes
    before each trip)."""
    from tigerbeetle_tpu.u128 import mix64

    t_lo, t_hi = np.asarray(table.key_lo), np.asarray(table.key_hi)
    tomb = np.asarray(table.tombstone)
    mask = np.uint64(table.capacity - 1)
    home = np.asarray(mix64(key_lo, key_hi) >> jnp.uint64(hash_shift)) & mask
    lo, hi = np.asarray(key_lo), np.asarray(key_hi)
    done = (lo == 0) & (hi == 0)
    found = np.zeros(len(lo), bool)
    slot = np.zeros(len(lo), np.uint64)
    open_by_trip = []
    i = 0
    while (~done).any() and i < max_probe:
        open_by_trip.append(int((~done).sum()))
        cur = (home + np.uint64(i)) & mask
        match = ~done & (t_lo[cur] == lo) & (t_hi[cur] == hi) & ~tomb[cur]
        empty = ~done & (t_lo[cur] == 0) & (t_hi[cur] == 0) & ~tomb[cur]
        found |= match
        slot = np.where(match, cur, slot)
        done |= match | empty
        i += 1
    return found, slot, bool((~done).any()), open_by_trip


def _homes(keys, capacity, hash_shift=0):
    """Home slots of the test's keys (key_hi = key_lo ^ 1, so that both
    halves are compared)."""
    from tigerbeetle_tpu.u128 import mix64

    k = jnp.asarray(keys, jnp.uint64)
    return (np.asarray(mix64(k, k ^ jnp.uint64(1)) >> jnp.uint64(hash_shift))
            & np.uint64(capacity - 1)).astype(np.int64)


def _key_table(t_lo, tomb):
    """The table of a key_lo column (key_hi = key_lo ^ 1 where occupied)."""
    return ht.Table(
        key_lo=jnp.asarray(t_lo),
        key_hi=jnp.asarray(np.where(t_lo != 0, t_lo ^ np.uint64(1), t_lo)),
        tombstone=jnp.asarray(tomb), cols={},
        count=jnp.uint64(np.count_nonzero(t_lo)),
        probe_overflow=jnp.bool_(False),
    )


def _probe_table(capacity, keys, hash_shift=0, tombstoned=()):
    """A table holding ``keys`` by plain linear-probing insertion, then
    ``tombstoned`` of them cleared to tombstones."""
    t_lo = np.zeros(capacity, np.uint64)
    at = {}
    for key, slot in zip(keys.tolist(), _homes(keys, capacity, hash_shift)):
        while t_lo[slot]:
            slot = (slot + 1) & (capacity - 1)
        t_lo[slot] = key
        at[key] = slot
    tomb = np.zeros(capacity, bool)
    for key in np.asarray(tombstoned).tolist():
        t_lo[at[key]] = 0
        tomb[at[key]] = True
    return _key_table(t_lo, tomb)


def _loaded(rng, n, load, capacity=1 << 16, hash_shift=0, nulls=0.0,
            tombstones=0.0):
    """A table at ``load`` and ``n`` lanes: half present keys, half absent,
    ``nulls`` of them zeroed, the last five lanes padding."""
    universe = rng.permutation(np.unique(rng.integers(
        1, 1 << 40, size=capacity + n, dtype=np.uint64)))[
            :int(capacity * load) + n]
    present, absent = universe[:-n], universe[-n:]
    removed = present[rng.random(len(present)) < tombstones]
    table = _probe_table(capacity, present, hash_shift, removed)
    lanes = np.where(rng.random(n) < 0.5, rng.choice(present, size=n), absent)
    if nulls:
        lanes[rng.random(n) < nulls] = 0
        lanes[-5:] = 0
    return table, lanes[None, :]


def _cluster(rng, n, depth, probing, capacity=1 << 12):
    """One run of ``depth`` occupied slots and ``probing`` lanes whose home
    is its first slots: a third of them present at the run's far end, the
    rest absent (they resolve at the empty slot behind it); the other lanes
    have their homes elsewhere, some of them present there."""
    cands = np.arange(1, 1 << 19, dtype=np.uint64)
    homes = _homes(cands, capacity)
    start = 100
    at_head = cands[(homes >= start) & (homes < start + 16)][:probing]
    assert len(at_head) == probing
    elsewhere = cands[homes > start + 2 * depth][: n - probing]
    deep = at_head[: probing // 3]
    t_lo = np.zeros(capacity, np.uint64)
    _, first = np.unique(_homes(elsewhere, capacity), return_index=True)
    t_lo[_homes(elsewhere[first[::2]], capacity)] = elsewhere[first[::2]]
    t_lo[start:start + depth] = np.uint64(1 << 40) + np.arange(
        depth, dtype=np.uint64)
    t_lo[start + depth - len(deep):start + depth] = deep
    lanes = rng.permutation(np.concatenate([at_head, elsewhere]))
    return _key_table(t_lo, np.zeros(capacity, bool)), lanes[None, :]


def _lookup_case(name):
    """(table, keys[K, n], max_probe, hash_shift, expectations) of a case."""
    rng = np.random.default_rng(sum(name.encode()))
    expect = {}
    max_probe, hash_shift = MAX_PROBE, 0
    if name.startswith("load_"):
        table, keys = _loaded(rng, 8192, float(name[5:]))
        expect = {"narrow_trips": 1}
    elif name == "nulls_and_padding":
        table, keys = _loaded(rng, 8190, 0.3, nulls=0.3)
    elif name == "tombstones":
        table, keys = _loaded(rng, 8192, 0.4, tombstones=0.3)
    elif name == "hash_shift_2":
        hash_shift = 2
        table, keys = _loaded(rng, 8192, 0.3, hash_shift=2)
    elif name.startswith("lanes_"):
        table, keys = _loaded(rng, int(name[6:]), 0.3, capacity=1 << 14)
    elif name == "in_a_while_loop":
        table, keys = _loaded(rng, 8190, 0.3, nulls=0.1)
        keys = np.stack([keys[0], rng.permutation(keys[0]), keys[0][::-1]])
    else:
        # window = 1024 at 8192 lanes.  The run's absent lanes resolve on
        # trips depth-14 .. depth+1 (the empty slot behind it).
        depth, probing, max_probe, expect = {
            "wide_phase_runs_long": (
                1100, 1500, 1 << 11, {"wide_trips": 1024, "overflow": False}),
            "max_probe_in_wide_phase": (
                1100, 1500, 64, {"wide_trips": 64, "overflow": True}),
            "max_probe_in_narrow_phase": (
                300, 500, 128, {"narrow_trips": 100, "overflow": True}),
            "last_trip_is_max_probe": (300, 500, 301, {"overflow": False}),
            "one_trip_short_of_max_probe": (
                300, 500, 300, {"overflow": True}),
        }[name]
        table, keys = _cluster(rng, 8192, depth, probing)
    return table, keys, max_probe, hash_shift, expect


@jax.jit
def _lookups_in_a_while_loop(table, lo, hi):
    """The grouped dispatch's shape: one lookup a trip of a lax.while_loop."""
    k, n = lo.shape

    def body(state):
        i, found, slot, overflow = state
        res = ht.lookup(table, lo[i], hi[i], MAX_PROBE)
        return (i + 1, found.at[i].set(res.found), slot.at[i].set(res.slot),
                overflow.at[i].set(res.overflow))

    return jax.lax.while_loop(
        lambda state: state[0] < k, body,
        (jnp.int32(0), jnp.zeros((k, n), jnp.bool_),
         jnp.zeros((k, n), jnp.uint64), jnp.zeros((k,), jnp.bool_)),
    )[1:]


@pytest.mark.parametrize("case", [
    "load_0.05", "load_0.3", "load_0.49", "nulls_and_padding", "tombstones",
    "wide_phase_runs_long", "max_probe_in_wide_phase",
    "max_probe_in_narrow_phase", "last_trip_is_max_probe",
    "one_trip_short_of_max_probe", "lanes_1", "lanes_63", "lanes_64",
    "lanes_8192", "hash_shift_2", "in_a_while_loop",
])
def test_two_phase_lookup_matches_the_single_loop(case):
    """found, slot and overflow of the two-phase lookup against the single
    loop it replaced, and that each case drives the phase its name says."""
    table, keys, max_probe, hash_shift, expect = _lookup_case(case)
    lo = jnp.asarray(keys, jnp.uint64)
    hi = jnp.where(lo != 0, lo ^ jnp.uint64(1), lo)
    if case == "in_a_while_loop":
        got = _lookups_in_a_while_loop(table, lo, hi)
    else:
        res = ht.lookup(table, lo[0], hi[0], max_probe, hash_shift)
        got = (res.found[None], res.slot[None], res.overflow[None])
    window = ht._window(keys.shape[1])
    for k in range(keys.shape[0]):
        found, slot, overflow, open_by_trip = _lookup_single_loop_reference(
            table, lo[k], hi[k], max_probe, hash_shift)
        np.testing.assert_array_equal(np.asarray(got[0][k]), found)
        np.testing.assert_array_equal(np.asarray(got[1][k]), slot)
        assert np.asarray(got[1]).dtype == np.uint64
        assert bool(got[2][k]) == overflow
        assert len(found) == 1 or (found.any() and not found.all())
        wide = sum(c > window for c in open_by_trip)
        assert wide >= expect.get("wide_trips", 0)
        assert len(open_by_trip) - wide >= expect.get("narrow_trips", 0)
        assert overflow == expect.get("overflow", overflow)


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

    def staged(lead=()):  # staging.stage_batch's / stage_group's operands
        return (jax.ShapeDtypeStruct(lead + (14, lanes), jnp.uint64),
                jax.ShapeDtypeStruct(lead + (5, lanes), jnp.uint32),
                jax.ShapeDtypeStruct((2,) + lead, jnp.uint64))

    if program == "fast":
        lowered = sm.create_transfers_fast.jitted.lower(led, *staged())
    elif program == "grouped":
        k = machine.TpuStateMachine.GROUP_K
        lowered = machine._group_fast_dispatch.lower(led, *staged((k,)))
    else:
        lowered = tf.create_transfers_full.lower(
            led, *staged(), None, None, max_passes=8,
            has_postvoid=True, has_history=False, use_waves=True)
    operands = _scatter_operands(lowered.as_text())
    assert len(operands) > 20, "the parser lost the program's scatters"
    wide = [t for t in operands if t.endswith(("i64", "f64"))]
    assert not wide, f"64-bit scatter operands: {wide}"
