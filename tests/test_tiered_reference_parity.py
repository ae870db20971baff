"""A ledger that has outgrown its hot window, as a deployment
(`benchmarks/configs/tb-tiered-1r`, the cell `tiered-plain-s8`), at sizes a
CPU test can hold.

Seeded plans of the benchmark's `tiered_plain` generator through
`TpuStateMachine` with a hot window of 2^10 slots against the benchmark's
plain reference: the result codes of every request (the retried cold ids
answered `exists`) and every row read back, hot or cold, over seeds and two
filter sizes, across a dozen evictions; a false positive forced and ended by
`cold_checked`; `ColdStore.lookup_many` against `lookup` id for id; the
filter's shape across evictions under and past its design load; and the
untiered programs, which this deployment must leave as they were."""

import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.generators import tiered_plain
from benchmarks.harness import check
from benchmarks.reference.ledger import ReferenceLedger
from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.ops import cold as cold_mod
from tigerbeetle_tpu.ops import state_machine as sm
from tigerbeetle_tpu.ops import transfer_full as tf

EXISTS = int(types.CreateTransferResult.exists)
LANES = 64
HOT_LOG2 = 10
MIX = {
    "generator": "tiered_plain", "accounts": 200, "batch": LANES,
    "sessions": 4, "preload_per_session": 10, "window_cap_per_session": 12,
    "amount_max": 1000, "retry_every": 4, "retry_first": 1,
    "retry_events": 8, "retry_sources": 3,
}


def _requests(plan):
    """(operation, rows) of the plan in a commit order: set-up's phases,
    each session by session round-robin, then the window's likewise."""
    for queues in [p["queues"] for p in plan["setup"]] + [plan["window"]]:
        for k in range(max(map(len, queues))):
            for queue in queues:
                if k < len(queue):
                    yield queue[k]


def _machine(tmp_path, bloom_log2):
    return TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=9,
                     transfers_capacity_log2=HOT_LOG2,
                     posted_capacity_log2=6, bloom_bits_log2=bloom_log2),
        batch_lanes=LANES, spill_dir=str(tmp_path / "cold"),
        hot_transfers_capacity_max=1 << HOT_LOG2)


def _execute(m, operation, rows):
    got = getattr(m, operation)(rows.view(
        types.ACCOUNT_DTYPE if operation == "create_accounts"
        else types.TRANSFER_DTYPE), wall_clock_ns=0)
    return [(int(i), int(c)) for i, c in got]


def _pairs(codes):
    return [(int(i), int(c)) for i, c in codes]


@pytest.mark.parametrize("bloom_log2", [14, 20])
@pytest.mark.parametrize("seed", [7, 3000000019, 4800000077])
def test_the_plan_answers_as_the_plain_reference_hot_or_cold(
        tmp_path, seed, bloom_log2):
    plan = tiered_plain.build(MIX, seed)
    m, ref = _machine(tmp_path, bloom_log2), ReferenceLedger()
    shape = m._bloom_dev.shape
    retried = 0
    with registry.enabled_scope(), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # 2^14: it grows
        for operation, rows in _requests(plan):
            got = _execute(m, operation, rows)
            assert got == _pairs(ref.execute(operation, rows))
            assert {c for _i, c in got} <= {EXISTS}
            retried += len(got)
        counters = registry.snapshot()["counters"]
        gauges = registry.snapshot()["gauges"]
    sent = MIX["sessions"] * (10 + 12) * LANES
    # Every retry of the window was answered `exists`, lane by lane.
    assert retried == MIX["sessions"] * 3 * MIX["retry_events"] == 96
    assert m._evictions >= 3 and counters["ops.compactions"] == m._evictions
    assert m.cold.count > sent // 2
    assert m.ledger.transfers.capacity == 1 << HOT_LOG2   # never grew
    # Every retried request met true cold ids and was dispatched again.
    assert counters["cold.redispatches"] >= MIX["sessions"] * 3
    assert counters["cold.rehydrated_rows"] >= retried
    assert counters["cold.flagged_lanes"] >= retried
    assert gauges["cold.rows"] == m.cold.count
    assert gauges["cold.runs"] == len(m.cold.runs)
    assert gauges["cold.bloom_bits_log2"] == m._bloom_log2
    if bloom_log2 == 20:
        # Under its design load (12 bits an id: 87,381 ids) the filter is
        # the array `start` allocated: the general program never recompiles.
        assert m.cold.count * 12 <= 1 << 20
        assert m._bloom_dev.shape == shape == ((1 << 20) // 32,)
        assert "cold.bloom.grows" not in counters and m._bloom_grows == 0
        assert counters.get("cold.false_redispatches", 0) == 0
    else:
        # 2^14 bits hold 1,365 ids at that load; past it the filter grows
        # as it did, counted.
        assert m.cold.count * 12 > 1 << 14
        assert counters["cold.bloom.grows"] == m._bloom_grows >= 1
        assert m._bloom_dev.shape[0] > shape[0]
    # Every row reads back field for field, whichever tier holds it, and an
    # id never created reads back as nothing.
    ids = [int(i) for _op, rows in _requests(plan)
           if _op == "create_transfers" for i in rows["id_lo"]]
    ids = sorted(set(ids)) + [1 << 48, (1 << 48) + 5]
    for at in range(0, len(ids), 1000):
        got = m.lookup_transfers(ids[at:at + 1000])
        want = ref.lookup_transfers(ids[at:at + 1000])
        assert check._rows_differing(got, want) == 0
        assert check._bad_timestamps(got) == 0
    accounts = plan["account_ids"]
    assert check._rows_differing(
        m.lookup_accounts(accounts), ref.lookup_accounts(accounts)) == 0


def test_a_false_positive_costs_one_more_dispatch_and_cold_checked_ends_it(
        tmp_path):
    """A filter with every bit set flags every new id; the cold store holds
    none of them, the host certifies the batch (`cold_checked`), and the
    second dispatch commits: one re-dispatch, never a loop."""
    plan = tiered_plain.build(MIX, 11)
    m, ref = _machine(tmp_path, 14), ReferenceLedger()
    requests = list(_requests(plan))
    for operation, rows in requests[:14]:      # accounts, 10 preloaded:
        assert _execute(m, operation, rows) == _pairs(
            ref.execute(operation, rows))      # one eviction behind us
    assert m._evictions == 1
    m._bloom_np[:] = 0xFFFFFFFF
    m._bloom_dev = jnp.asarray(m._bloom_np)
    operation, rows = requests[14]
    with registry.enabled_scope():
        assert _execute(m, operation, rows) == _pairs(
            ref.execute(operation, rows)) == []
        counters = registry.snapshot()["counters"]
        spans = registry.snapshot()["histograms"]
    assert counters["ops.dispatch"] == 2
    assert counters["cold.redispatches"] == 1
    assert counters["cold.false_redispatches"] == 1
    assert counters["cold.flagged_lanes"] == LANES
    assert counters["cold.false_positive_lanes"] == LANES
    assert "cold.rehydrated_rows" not in counters
    assert spans["txtrace.stage.cold_resolve"]["count"] == 1
    assert "txtrace.stage.cold_rehydrate" not in spans


def test_an_evictions_spans_are_its_six_steps(tmp_path):
    plan = tiered_plain.build(MIX, 5)
    m = _machine(tmp_path, 20)
    with registry.enabled_scope():
        for operation, rows in list(_requests(plan))[:14]:
            _execute(m, operation, rows)
        spans = registry.snapshot()["histograms"]
    assert m._evictions == 1
    for name in ("cold_evict", "cold_threshold", "cold_extract", "cold_fetch",
                 "cold_spill", "cold_rehash", "cold_filter"):
        assert spans[f"txtrace.stage.{name}"]["count"] == 1, name
    children = sum(spans[f"txtrace.stage.{name}"]["sum"] for name in (
        "cold_threshold", "cold_extract", "cold_fetch", "cold_spill",
        "cold_rehash", "cold_filter"))
    assert children <= spans["txtrace.stage.cold_evict"]["sum"]
    # The run file was on disk before the eviction returned.
    (path,) = m.cold.run_paths
    assert len(np.load(path)) == m.cold.count == 257


def test_a_restart_keeps_the_filter_this_start_asked_for(tmp_path):
    """The filter is rebuilt from the runs at restore: a start that asks
    for more bits than the checkpoint held gets them, and a retried cold id
    is still refused exactly."""
    plan = tiered_plain.build(MIX, 5)
    m, ref = _machine(tmp_path, 14), ReferenceLedger()
    requests = list(_requests(plan))
    for operation, rows in requests[:14]:
        assert _execute(m, operation, rows) == _pairs(
            ref.execute(operation, rows))
    assert m._evictions == 1 and m._bloom_log2 == 14
    again = _machine(tmp_path, 20)
    again.ledger = m.ledger
    again.restore_host_state(m.host_state())
    assert again._bloom_dev.shape == ((1 << 20) // 32,)
    assert again.cold.count == m.cold.count == 257
    operation, rows = requests[4]              # the oldest preloaded request
    want = _pairs(ref.execute(operation, rows))
    assert _execute(again, operation, rows) == want
    assert want == [(i, EXISTS) for i in range(LANES)]


def test_the_size_class_of_an_eviction():
    assert cold_mod.size_class(4_193_281) == 1 << 22
    assert cold_mod.size_class((1 << 22) + 1) == 1 << 23
    assert cold_mod.size_class(1, 64) == 64
    assert cold_mod.size_class(257, 64) == 512


# -- `start --cold-bloom-log2` -----------------------------------------------------

def _args(**given):
    import argparse

    return argparse.Namespace(**dict(
        dict(cache_accounts_log2=None, cache_transfers_log2=None,
             cache_posted_log2=None, shards=None,
             hot_transfers_log2_max=None, cold_bloom_log2=None), **given))


@pytest.mark.parametrize("given, want", [
    (dict(), 20),                                  # no tier: the floor
    (dict(hot_transfers_log2_max=24), 30),         # 12 bits x 8 windows
    (dict(hot_transfers_log2_max=10), 20),
    (dict(hot_transfers_log2_max=30), 34),
    (dict(hot_transfers_log2_max=24, cold_bloom_log2=29), 29),
    (dict(hot_transfers_log2_max=10, cold_bloom_log2=14), 14),
])
def test_start_sizes_the_filter(given, want):
    from tigerbeetle_tpu import cli

    assert cli._ledger_config(_args(**given)).bloom_bits_log2 == want


@pytest.mark.parametrize("given", [
    dict(cold_bloom_log2=29),                      # no tier to size
    dict(hot_transfers_log2_max=24, cold_bloom_log2=9),
    dict(hot_transfers_log2_max=24, cold_bloom_log2=35),
])
def test_start_refuses_a_filter_it_cannot_make(given):
    from tigerbeetle_tpu import cli

    with pytest.raises(ValueError, match="cold-bloom-log2"):
        cli._ledger_config(_args(**given))


def test_warmup_compiles_the_tiers_programs_and_a_request_compiles_none(
        tmp_path):
    """After `warmup` at the ceiling an eviction, a resolution and a
    rehydration run programs that are there: `jit.compiles` stands still
    from the second request of each kind on (the index's first merge of a
    level aside, which is the index's to warm)."""
    from tigerbeetle_tpu import jaxenv

    assert jaxenv.instrument_compiles()
    plan = tiered_plain.build(MIX, 21)
    m = _machine(tmp_path, 20)
    m.warmup()
    assert jaxenv.compile_count() > 0
    requests = list(_requests(plan))
    # Accounts, the preload and the window's first six rounds (two of
    # retries): 64 `create_transfers` requests, so the index has merged
    # level 6 for the first time, and the next sixteen fill no new level.
    warm = 4 + 40 + 6 * 4
    for operation, rows in requests[:warm]:
        _execute(m, operation, rows)
    assert m._evictions >= 3
    before = jaxenv.compile_count()
    evictions = m._evictions
    for operation, rows in requests[warm:warm + 16]:
        _execute(m, operation, rows)
    assert m._evictions > evictions            # evictions, retries: both ran
    assert jaxenv.compile_count() == before


# -- the cold store's vectorised search --------------------------------------------

def _run(rng, n, hi_values):
    rows = np.zeros(n, dtype=types.TRANSFER_DTYPE)
    rows["id_lo"] = rng.choice(1 << 20, n, replace=False).astype(np.uint64)
    rows["id_hi"] = rng.choice(hi_values, n).astype(np.uint64)
    rows["amount_lo"] = rng.integers(1, 1 << 40, n, dtype=np.uint64)
    rows["timestamp"] = rng.integers(1, 1 << 60, n, dtype=np.uint64)
    return rows


@pytest.mark.parametrize("on_disk", [False, True])
def test_lookup_many_is_lookup_id_for_id(tmp_path, on_disk):
    """Runs whose ids share halves (the same `id_lo` under several `id_hi`,
    and the other way round), an id in two runs (the newest wins), absent
    ids on every side of the runs' ranges."""
    rng = np.random.default_rng(48)
    store = cold_mod.ColdStore(str(tmp_path / "runs") if on_disk else None)
    runs = [_run(rng, n, [0, 1, 2, 1 << 63]) for n in (300, 1, 77)]
    runs[2][:5] = runs[0][:5]                  # in two runs:
    runs[2]["amount_lo"][:5] += 1              # the newer copy differs
    twin = runs[0][10:20].copy()               # same id_lo, another id_hi
    twin["id_hi"] = 7
    runs.append(twin)
    for rows in runs:
        store.append_run(rows)
    present = np.concatenate(runs)
    ids = [(int(r["id_lo"]), int(r["id_hi"])) for r in present]
    ids += [(int(r["id_lo"]), 9) for r in present[:50]]        # absent hi
    ids += [(int(lo), 0) for lo in range((1 << 20), (1 << 20) + 50)]
    ids += [(0, 0), (1 << 63, 1 << 63), ((1 << 64) - 1, (1 << 64) - 1)]
    found = store.lookup_many(ids)
    hits = 0
    for key in ids:
        one = store.lookup(*key)
        if one is None:
            assert key not in found
        else:
            hits += 1
            assert found[key].tobytes() == one.tobytes()
    assert hits == len(present) and len(found) == len(set(
        k for k in ids if store.lookup(*k) is not None))
    for r in runs[2][:5]:                      # the newest run's copy
        key = (int(r["id_lo"]), int(r["id_hi"]))
        assert int(found[key]["amount_lo"]) == int(r["amount_lo"])
    assert store.lookup_many([]) == {}
    none, rows = cold_mod.ColdStore(None).lookup_arrays(
        np.array([5], np.uint64), np.array([0], np.uint64))
    assert not none.any() and len(rows) == 1


# -- what an untiered deployment runs is as it was -----------------------------------

def _lowered(bloom_log2=None, **static):
    led = jax.eval_shape(lambda: sm.make_ledger(1 << 9, 1 << 10, 1 << 6,
                                                1 << 6))
    staged = (jax.ShapeDtypeStruct((14, LANES), jnp.uint64),
              jax.ShapeDtypeStruct((5, LANES), jnp.uint32),
              jax.ShapeDtypeStruct((2,), jnp.uint64))
    if static.pop("fast", False):
        return sm.create_transfers_fast.jitted.lower(led, *staged)
    tier = (None, None) if bloom_log2 is None else (
        jax.ShapeDtypeStruct(((1 << bloom_log2) // 32,), jnp.uint32),
        jax.ShapeDtypeStruct((LANES,), jnp.bool_))
    return tf.create_transfers_full.lower(
        led, *staged, *tier, max_passes=8, has_history=False, **static)


def _shape(lowered):
    name = re.search(r"module @(\w+)", lowered.as_text()).group(1)
    return (name, len(jax.tree_util.tree_leaves(lowered.args_info)),
            len(jax.tree_util.tree_leaves(lowered.out_info)))


@pytest.mark.parametrize("static, want", [
    (dict(has_postvoid=False, use_waves=True),
     ("jit_create_transfers_full_impl", 74, 82)),
    (dict(has_postvoid=True, use_waves=True),
     ("jit_create_transfers_full_impl", 74, 82)),
    (dict(has_postvoid=True, use_waves=False),
     ("jit_create_transfers_full_impl", 74, 81)),
    (dict(fast=True), ("jit_create_transfers_impl", 74, 72)),
])
def test_the_untiered_programs_keep_their_name_arguments_and_results(
        static, want):
    """As lowered at the parent commit (PR 47): the six accepted cells'
    programs neither take the filter nor return the lanes it flagged."""
    assert _shape(_lowered(**static)) == want


def test_the_tiered_general_program_takes_the_filter_and_returns_the_lanes():
    name, args, outs = _shape(_lowered(
        14, has_postvoid=False, use_waves=True))
    assert (name, args, outs) == ("jit_create_transfers_full_impl", 76, 83)
    text = _lowered(14, has_postvoid=True, use_waves=True).as_text(
        debug_info=True)
    assert "tb/full_bloom" in text
    plain = _lowered(has_postvoid=True, use_waves=True).as_text(
        debug_info=True)
    assert "tb/full_bloom" not in plain
