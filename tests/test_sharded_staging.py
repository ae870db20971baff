"""Under `--shards` a batch is staged once, already replicated on the mesh
(`machine._stage_sharded`, `ops/staging.stage_batch`; PERF.md PR 38).

Four things the serving path leans on, on each of the four sharded transfer
routes (the lone deferred request, the grouped run, the blocking fast
request, the blocking general one): the operands a commit program is handed
are in place on every chip of the mesh; a request after `warmup()` finds its
program compiled, because warm-up stages as the routes do; every sharded
request took that staging (`sharding.staged`); and a staged batch is zero
beyond its count, the pad contract the kernels rely on."""

import threading

import jax
import numpy as np
import pytest

from tigerbeetle_tpu import jaxenv, types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.obs.txtrace import txtrace
from tigerbeetle_tpu.ops import staging as staging_mod
from tigerbeetle_tpu.parallel import sharded

LANES = 64
SHARDS = 4
N_ACCOUNTS = 16
PENDING = types.TransferFlags.PENDING
ROUTES = ["lone_deferred", "grouped_run", "blocking_fast", "blocking_general"]


def _machine():
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} devices, have {len(jax.devices())}")
    m = TpuStateMachine(
        LedgerConfig(accounts_capacity_log2=9, transfers_capacity_log2=12,
                     posted_capacity_log2=8),
        batch_lanes=LANES, shards=SHARDS)
    assert m.pipeline_depth == 2 and m.waves_enabled
    return m


def _accounts():
    return types.accounts_array([
        types.account(id=i + 1, ledger=1, code=10) for i in range(N_ACCOUNTS)
    ])


def _transfers(first_id, n, flags=0):
    return types.transfers_array([
        types.transfer(
            id=first_id + i, debit_account_id=1 + i % N_ACCOUNTS,
            credit_account_id=1 + (i + 3) % N_ACCOUNTS, amount=3 + i % 5,
            ledger=1, code=10, flags=flags,
        )
        for i in range(n)
    ])


def _serve(m, route, first_id):
    """One request (three on the grouped route) of ``route``, as the
    serving loop sends it; returns how many batches it committed."""
    if route == "lone_deferred":
        batch = _transfers(first_id, 10)
        handle = m.commit_fast_deferred(
            batch, m.prepare("create_transfers", len(batch), 0))
        assert handle.resolve() == [[]]
        return 1
    if route == "grouped_run":
        batches = [_transfers(first_id + 100 * j, 7 + j) for j in range(3)]
        stamps = [m.prepare("create_transfers", len(b), 0) for b in batches]
        assert m.commit_group_fast(batches, stamps) == [[], [], []]
        return 3
    flags = PENDING if route == "blocking_general" else 0
    assert m.create_transfers(_transfers(first_id, 10, flags)) == []
    return 1


@pytest.mark.parametrize("route", ROUTES)
def test_a_commit_program_finds_its_operands_in_place(route):
    m = _machine()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    mesh_devices = set(m._shard_mesh.devices.flat)
    seen = []

    def recording(step):
        def call(ledger, *operands):
            seen.append(operands)
            return step(ledger, *operands)
        return call

    # The machine's own view of the process-wide step cache, wrapped.
    m._shard_steps = {k: recording(v) for k, v in m._shard_steps.items()}
    batches = _serve(m, route, 10_000)
    assert len(seen) == batches
    for operands in seen:
        cols64, cols32, meta = operands
        assert cols64.shape == (14, LANES) and cols64.dtype == np.uint64
        assert cols32.shape == (5, LANES) and cols32.dtype == np.uint32
        assert meta.shape == (2,) and meta.dtype == np.uint64
        for x in operands:
            assert x.committed
            assert x.sharding.is_fully_replicated
            assert set(x.sharding.device_set) == mesh_devices
    assert m._ledger_is_sharded


@pytest.mark.parametrize("route", ROUTES)
def test_the_first_request_after_warmup_compiles_nothing(route):
    assert jaxenv.instrument_compiles()
    m = _machine()
    m.warmup()
    steps = m._shard_steps
    warmed = {k: steps[k]._cache_size()
              for k in ("accounts", "fast", "fast_probed", "full_waves")}
    assert all(n >= 1 for n in warmed.values()), warmed
    with registry.enabled_scope():
        before = registry.snapshot()["counters"].get("jit.compiles", 0)
        assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
        _serve(m, route, 20_000)
        after = registry.snapshot()["counters"].get("jit.compiles", 0)
    # Neither a second executable of a sharded program (one keyed on
    # operands staged otherwise than warm-up's) nor any other compile.
    assert {k: steps[k]._cache_size() for k in warmed} == warmed
    assert after - before == 0


def test_every_sharded_request_is_staged_on_the_mesh():
    m = _machine()
    with registry.enabled_scope(), txtrace.attribution_scope():
        assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
        batches = sum(
            _serve(m, route, 30_000 + 1_000 * i)
            for i, route in enumerate(ROUTES + ROUTES[::-1])
        )
        counters = registry.snapshot()["counters"]
        totals = txtrace.stage_totals()
    assert batches == 2 * (1 + 3 + 1 + 1)
    assert counters["sharding.batches"] == batches
    assert counters["sharding.staged"] == batches + 1   # the account request
    assert counters.get("sharding.seq_fallbacks", 0) == 0
    # Each staging sits inside a `stage_h2d` span of its own: one a
    # request, and one a batch of the grouped run (staged right before its
    # own enqueue, PR 40), which alone count as staged on the lane.
    assert totals["stage_h2d"]["count"] == 1 + batches
    assert counters["sharding.staged.lane"] == 2 * 3


def _run(first_id, k):
    """A run of ``k`` batches whose codes are not all OK: batch j's lane 2
    debits an account that does not exist, its lane 4 repeats lane 3's id
    with another amount."""
    batches = []
    for j in range(k):
        batch = _transfers(first_id + 100 * j, 9 + j)
        batch["debit_account_id_lo"][2] = 999
        batch["id_lo"][4] = batch["id_lo"][3]
        batches.append(batch)
    return batches


def _recorded(m, monkeypatch, events):
    """Every staging and every enqueue of ``m`` appended to ``events`` as
    (what, the thread's name), in the order they happen."""
    stage_batch = staging_mod.stage_batch

    def staging(*args):
        events.append(("stage", threading.current_thread().name))
        return stage_batch(*args)

    def recording(step):
        def call(ledger, *operands):
            events.append(("dispatch", threading.current_thread().name))
            return step(ledger, *operands)
        return call

    monkeypatch.setattr(staging_mod, "stage_batch", staging)
    m._shard_steps = {k: recording(v) for k, v in m._shard_steps.items()}


@pytest.mark.parametrize("k", [2, 7])
def test_a_grouped_run_is_staged_on_the_lane_batch_by_batch(k, monkeypatch):
    m = _machine()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    batches = _run(40_000, k)
    stamps = [m.prepare("create_transfers", len(b), 0) for b in batches]
    events = []
    _recorded(m, monkeypatch, events)
    with registry.enabled_scope(), txtrace.attribution_scope():
        before = registry.snapshot()
        handle = m.commit_group_fast(batches, stamps, deferred=True)
        handle.resolve()
        after = registry.snapshot()

    def rose(kind, name):
        return after[kind].get(name, 0) - before[kind].get(name, 0)

    # Stage 0, dispatch 0, stage 1, dispatch 1, ...: each batch staged
    # right before its own enqueue, and all of it on the lane's one thread.
    assert [what for what, _ in events] == ["stage", "dispatch"] * k
    assert {thread.split("_")[0] for _, thread in events} == {"tb-dispatch"}
    assert threading.current_thread().name.split("_")[0] != "tb-dispatch"
    assert rose("counters", "sharding.staged.lane") == k
    assert rose("counters", "sharding.staged") == k
    assert rose("counters", "sharding.batches") == k
    # The spans say the same: K of them, their self time the lane's alone.
    spans = after["histograms"]["txtrace.stage.stage_h2d"]["count"]
    spans -= before["histograms"].get(
        "txtrace.stage.stage_h2d", {"count": 0})["count"]
    assert spans == k
    assert rose("counters", "txtrace.self_us.lane.stage_h2d") > 0
    assert rose("counters", "txtrace.self_us.serving.stage_h2d") == 0


@pytest.mark.parametrize("k", [2, 7])
def test_a_grouped_run_equals_the_blocking_route_batch_by_batch(k):
    grouped, blocking = _machine(), _machine()
    overflow = {"grouped": [], "blocking": []}
    step = grouped._shard_steps["fast_probed"]

    def probed(ledger, *operands):
        out = step(ledger, *operands)
        overflow["grouped"].append(np.asarray(out[2]))
        return out

    grouped._shard_steps = dict(grouped._shard_steps, fast_probed=probed)
    for m in (grouped, blocking):
        assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    batches = _run(50_000, k)
    stamps = [grouped.prepare("create_transfers", len(b), 0) for b in batches]
    got = grouped.commit_group_fast(batches, stamps, deferred=True).resolve()
    want = []
    for batch in batches:
        want.append(blocking.create_transfers(batch))
        overflow["blocking"].append(
            np.asarray(blocking.ledger.transfers.probe_overflow))
    assert got == want and all(len(r) == 2 for r in got)
    assert len(overflow["grouped"]) == k
    for mine, theirs in zip(overflow["grouped"], overflow["blocking"]):
        assert mine.shape == (SHARDS,) and np.array_equal(mine, theirs)
    ids = list(range(1, N_ACCOUNTS + 1))
    mine, theirs = grouped.lookup_accounts(ids), blocking.lookup_accounts(ids)
    for name in ("debits_posted_lo", "credits_posted_lo",
                 "debits_pending_lo", "credits_pending_lo"):
        assert np.array_equal(mine[name], theirs[name]), name
    assert mine["debits_posted_lo"].sum() > 0
    sample = [int(b["id_lo"][0]) for b in batches]
    assert np.array_equal(
        grouped.lookup_transfers(sample)["amount_lo"],
        blocking.lookup_transfers(sample)["amount_lo"])


def test_a_staging_that_fails_on_the_lane_fails_the_resolve(monkeypatch):
    m = _machine()
    assert m.create_accounts(_accounts(), wall_clock_ns=1000) == []
    batches = _run(60_000, 3)
    stamps = [m.prepare("create_transfers", len(b), 0) for b in batches]
    stage_batch, calls = staging_mod.stage_batch, []

    def failing(*args):
        calls.append(threading.current_thread().name)
        if len(calls) == 2:
            raise ValueError("staging failed")
        return stage_batch(*args)

    monkeypatch.setattr(staging_mod, "stage_batch", failing)
    handle = m.commit_group_fast(batches, stamps, deferred=True)
    # The submit itself staged nothing and raised nothing: the failure is
    # the lane's, and comes out of the handle as a failed enqueue does.
    with pytest.raises(ValueError, match="staging failed"):
        handle.resolve()
    assert len(calls) == 2 and calls[0].startswith("tb-dispatch")
    # The chain stands where the last good execution left it: batch 0 is
    # in the ledger, batches 1 and 2 are not, and the next commit works.
    found = m.lookup_transfers([int(b["id_lo"][0]) for b in batches])
    assert [int(i) for i in found["id_lo"]] == [int(batches[0]["id_lo"][0])]
    assert m.create_transfers(_transfers(70_000, 5)) == []


@pytest.mark.parametrize("n", [0, 1, 37, LANES])
@pytest.mark.parametrize("dtype", [types.ACCOUNT_DTYPE, types.TRANSFER_DTYPE],
                         ids=["accounts", "transfers"])
def test_a_staged_batch_is_zero_beyond_its_count(dtype, n):
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} devices, have {len(jax.devices())}")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:SHARDS]), (sharded.AXIS,))
    rng = np.random.default_rng(n)
    batch = np.zeros(n, dtype=dtype)
    for name in dtype.names:   # every field non-zero in every lane
        info = np.iinfo(dtype.fields[name][0])
        batch[name] = rng.integers(1, info.max, n, dtype=info.dtype)
    timestamp = 7_000_000_000_000 + n
    staged = staging_mod.stage_batch(
        batch, LANES, timestamp, NamedSharding(mesh, PartitionSpec()))
    columns, count, stamp = staging_mod.unstage(dtype, *staged)
    assert (int(count), int(stamp)) == (n, timestamp)
    # What the kernels' bodies take: each column by name, widened as `to_soa`
    # widens it, its lanes beyond the count zero.
    padded = np.zeros(LANES, dtype=dtype)
    padded[:n] = batch
    want = types.to_soa(padded)
    assert set(columns) == set(want) and len(columns) == 19
    for name, column in columns.items():
        got = np.asarray(column)
        assert got.dtype == want[name].dtype and got.shape == (LANES,)
        assert np.array_equal(got, want[name]), name
        assert not got[n:].any() and got[:n].all(), name
