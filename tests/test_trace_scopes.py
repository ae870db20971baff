"""Stable device names: the `tb/<phase>` scopes of the programs the accepted
cells' profiler windows show (the fast kernel, the grouped scan, the general
kernel, the index) and of the sharded commit programs (`start --shards N`),
whose names a trace must also tell apart.

An operation's `op_name` in a device trace carries the `jax.named_scope` it
was traced under, so the scopes must be in each program's lowered text; and
they are metadata only, so the programs still answer as `testing/model.py`
does.  The read programs (`lookup_accounts`, `lookup_transfers`) carry none
of their own yet (their scopes come with the cell whose window reaches
them); what they show is `ht.lookup`'s three parts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmarks.harness import commit_programs
from test_pipeline import (
    CFG, LANES, accounts_batch, batch, make_machine, make_model,
)
from tigerbeetle_tpu import machine, types
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.ops import index
from tigerbeetle_tpu.ops import state_machine as sm
from tigerbeetle_tpu.ops import transfer_full as tf
from tigerbeetle_tpu.parallel import sharded
from tigerbeetle_tpu.testing import model as M


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _mesh4():
    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, have {len(jax.devices())}")
    return Mesh(np.array(jax.devices()[:4]), (sharded.AXIS,))


def _staged(rows=None):
    """`staging.stage_batch`'s operands as shapes: the batch's 14 uint64
    columns, its 5 narrower ones, (count, timestamp); with ``rows``,
    `staging.stage_group`'s stack of that many."""
    lead = () if rows is None else (rows,)
    return (jax.ShapeDtypeStruct(lead + (14, LANES), jnp.uint64),
            jax.ShapeDtypeStruct(lead + (5, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((2,) + lead, jnp.uint64))


def _lowered(program):
    led = jax.eval_shape(lambda: sm.make_ledger(1 << 10, 1 << 12, 1 << 10,
                                                1 << 10))
    ids = jax.ShapeDtypeStruct((LANES,), jnp.uint64)
    ok = jax.ShapeDtypeStruct((LANES,), jnp.bool_)
    k = machine.TpuStateMachine.GROUP_K
    if program == "fast":
        return sm.create_transfers_fast_probed.jitted.lower(led, *_staged())
    if program == "grouped":
        return machine._group_fast_dispatch.lower(led, *_staged(k))
    if program == "general":  # the two-phase variant the machine serves
        return tf.create_transfers_full.lower(
            led, *_staged(), max_passes=8, has_postvoid=True,
            has_history=False, use_waves=True)
    if program.startswith("sharded"):
        mesh = _mesh4()
        steps = sharded.machine_steps(mesh, 8)
        led = jax.eval_shape(lambda: sharded.make_sharded_ledger(
            mesh, 1 << 10, 1 << 12, 1 << 10))
        step = steps[{"sharded_fast": "fast_probed",
                      "sharded_general": "full_waves",
                      "sharded_general_no_waves": "full"}[program]]
        return step.lower(led, *_staged())
    if program == "index_build":
        keys = {name: ids for name in sm.INDEX_KEY_COLS}
        return index.build_runs.lower(keys, ids, ids, ok)
    if program == "index_build_row":  # a row of a grouped dispatch's outputs
        ids, ok = (jax.ShapeDtypeStruct((k,) + x.shape, x.dtype)
                   for x in (ids, ok))
        keys = {name: ids for name in sm.INDEX_KEY_COLS}
        return index.build_runs.lower(
            keys, ids, ids, ok, jax.ShapeDtypeStruct((), jnp.int32))
    if program == "index_probe":
        return index.probe_keys.lower(led, ids, ids, ok)
    assert program == "index_merge"
    level = _shapes(index._sentinel_level(LANES))
    return index._merge_jit.lower([level, level])


# ht.lookup's own parts, innermost under whichever scope calls it.
LOOKUP = ("tb/lookup_wide", "tb/lookup_compact", "tb/lookup_narrow")


@pytest.mark.parametrize("program,scopes", [
    ("fast", ("tb/probe", "tb/validate", "tb/balance", "tb/insert") + LOOKUP),
    ("grouped", ("tb/group_step", "tb/probe", "tb/validate", "tb/balance",
                 "tb/insert") + LOOKUP),
    ("general", ("tb/full_gather", "tb/full_waves", "tb/full_pass",
                 "tb/full_apply", "tb/full_posted") + LOOKUP),
    ("index_build", ("tb/index_sort",)),
    ("index_build_row", ("tb/index_sort",)),
    ("index_probe", ("tb/index_probe",) + LOOKUP),
    ("index_merge", ("tb/index_merge",)),
    ("sharded_fast", ("tb/shard_gather", "tb/shard_combine", "tb/validate",
                      "tb/balance", "tb/insert") + LOOKUP),
    ("sharded_general", ("tb/shard_gather", "tb/shard_combine",
                         "tb/full_waves", "tb/full_pass", "tb/full_apply",
                         "tb/full_posted") + LOOKUP),
    ("sharded_general_no_waves", ("tb/shard_gather", "tb/shard_combine",
                                  "tb/full_pass", "tb/full_apply",
                                  "tb/full_posted") + LOOKUP),
])
def test_scopes_are_in_the_lowered_text(program, scopes):
    text = _lowered(program).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, f"{program}: no {scope} in the lowered text"


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("use_waves", [False, True])
def test_every_psum_of_the_sharded_general_program_is_a_combine(use_waves):
    """The exchange of `sharded_create_transfers_full` is its psums (found
    flags, global slots, row columns, the overflow and claim flags): each
    one is traced under `tb/shard_combine`, so a device trace shows the
    collectives' share by scope as `shard_general_collective_pct` shows it
    by operation."""
    mesh = _mesh4()
    step = sharded.machine_steps(mesh, 8)["full_waves" if use_waves
                                          else "full"]
    led = jax.eval_shape(lambda: sharded.make_sharded_ledger(
        mesh, 1 << 10, 1 << 12, 1 << 10))
    jaxpr = jax.make_jaxpr(step)(led, *_staged())
    psums = [eqn for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name.startswith("psum")]
    assert len(psums) > 100          # 7 key sets' found, slots and columns
    for eqn in psums:
        assert "tb/shard_combine" in str(eqn.source_info.name_stack), eqn


def test_build_runs_reads_no_table():
    """A level-0 run is sorted from the keys the commit program returned:
    `build_runs` holds no probe loop and no operand of a table's size (the
    helper, for the routes without kernel keys, holds both)."""
    table = f"tensor<{1 << 12}x"  # _lowered's transfers table
    for program in ("index_build", "index_build_row"):
        text = _lowered(program).as_text(debug_info=True)
        assert "tb/index_probe" not in text
        assert "stablehlo.while" not in text and table not in text
        # the sorts move the rows themselves: no permutation to gather by
        assert "stablehlo.sort" in text and "stablehlo.gather" not in text
    text = _lowered("index_probe").as_text()
    assert "stablehlo.while" in text and table in text


def test_sharded_programs_have_names_of_their_own():
    """Every builder's inner function is `step`: a trace would show each as
    `jit_step`.  The commit twins' names contain the single-device
    programs' (`benchmarks/harness/commit_programs.py` finds them by it)."""
    mesh = _mesh4()
    programs = dict(sharded.machine_steps(mesh, 8))
    programs.update({f"merkle_{k}": v
                     for k, v in sharded.merkle_steps(mesh).items()})
    for table in ("accounts", "transfers"):
        programs[f"lookup_{table}"] = sharded.sharded_lookup(mesh, table)
    names = {key: step.__name__ for key, step in programs.items()}
    assert len(set(names.values())) == len(names), names
    assert all(n.startswith("sharded_") for n in names.values()), names
    assert names["fast_probed"] == "sharded_create_transfers_fast_probed"
    assert names["full"] == "sharded_create_transfers_full"
    for key in ("fast_probed", "full", "full_waves"):
        assert commit_programs.commits([f"jit_{names[key]}", 0, 0, 0])
    assert commit_programs.GENERAL not in names["fast_probed"]
    assert names["full_waves"] == "sharded_create_transfers_full_waves"
    assert names["lookup_transfers"] == "sharded_lookup_transfers"
    text = _lowered("sharded_fast").as_text()
    assert "jit_sharded_create_transfers_fast_probed" in text
    assert "jit_step" not in text


def test_scoped_programs_answer_as_the_model_does():
    """Fast, grouped and general commits, both lookups and the index query
    on one small ledger, beside the scalar oracle."""
    m = make_machine()
    ref = make_model()

    def both(b):
        got = m.create_transfers(b, wall_clock_ns=0)
        want = ref.create_transfers([M.transfer_from_row(r) for r in b])
        assert got == want
        return got

    both(batch(1000, 20))                       # the fast kernel
    run = [batch(2000, 9), batch(3000, 12), batch(1000, 20)]
    timestamps = [m.prepare("create_transfers", len(b), 0) for b in run]
    got = m.commit_group_fast(run, timestamps)  # the grouped scan
    assert got is not None
    for b, res in zip(run, got):
        assert res == ref.create_transfers(
            [M.transfer_from_row(r) for r in b])
    pending = batch(4000, 10, flags=int(types.TransferFlags.PENDING))
    both(pending)                               # the general kernel
    post = types.transfers_array([
        types.transfer(id=5000 + i, pending_id=4000 + i, ledger=1, code=10,
                       flags=int(types.TransferFlags.POST_PENDING_TRANSFER))
        for i in range(6)
    ])
    both(post)
    assert m.balances_snapshot() == ref.balances_snapshot()

    ids = [1000, 2003, 4001, 5002, 77]
    rows = m.lookup_transfers(ids)
    want = ref.lookup_transfers(ids)
    assert [(int(r["id_lo"]), int(r["amount_lo"]), int(r["timestamp"]))
            for r in rows] == [(t.id, t.amount, t.timestamp) for t in want]
    accounts = m.lookup_accounts([1, 2, 99])
    assert [(int(a["id_lo"]), int(a["debits_posted_lo"]),
             int(a["credits_pending_lo"])) for a in accounts] == [
        (a.id, a.debits_posted, a.credits_pending)
        for a in ref.lookup_accounts([1, 2, 99])]
    f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
    f["account_id_lo"], f["limit"], f["flags"] = 1, 100, 3
    assert [int(r["id_lo"]) for r in m.get_account_transfers(f)] == [
        t.id for t in ref.get_account_transfers(1, 0, 0, 100, 3)]


@pytest.mark.parametrize("mode", ["lazy_index", "shards4"])
def test_a_lazy_index_appends_no_run_and_rebuilds_for_a_query(mode):
    """Under `lazy_index`, and under `--shards` (whose ledger no
    single-device program could read), every commit route resets the index
    and appends nothing, keyed or probed; a query rebuilds it whole."""
    if mode == "shards4":
        _mesh4()
        m = make_machine(shards=4)
    else:
        m = machine.TpuStateMachine(
            dataclasses.replace(CFG, lazy_index=True), batch_lanes=LANES)
        assert m.create_accounts(accounts_batch(), wall_clock_ns=1000) == []
    ref = make_model()

    def model(b):
        return ref.create_transfers([M.transfer_from_row(r) for r in b])

    with registry.enabled_scope():
        lone = batch(1000, 20)
        (got,) = m.commit_fast_deferred(
            lone, m.prepare("create_transfers", len(lone), 0)).resolve()
        assert got == model(lone)
        run = [batch(2000, 9), batch(3000, 12)]
        tss = [m.prepare("create_transfers", len(b), 0) for b in run]
        assert m.commit_group_fast(run, tss) == [model(b) for b in run]
        pending = batch(4000, 10, flags=int(types.TransferFlags.PENDING))
        assert m.create_transfers(pending) == model(pending)
        post = types.transfers_array([
            types.transfer(
                id=5000 + i, pending_id=4000 + i, ledger=1, code=10,
                flags=int(types.TransferFlags.POST_PENDING_TRANSFER))
            for i in range(6)
        ])
        assert m.create_transfers(post) == model(post)  # the general kernel
        assert registry.counter("ops.route.general").value == 1
        assert registry.counter("index.runs.keyed").value == 0
        assert registry.counter("index.runs.probed").value == 0
    assert m.index.stale and not m.index.occupied
    f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
    f["account_id_lo"], f["limit"], f["flags"] = 1, 100, 3
    want = [t.id for t in ref.get_account_transfers(1, 0, 0, 100, 3)]
    assert len(want) > 4
    assert [int(r["id_lo"]) for r in m.get_account_transfers(f)] == want
    assert not m.index.stale and any(m.index.occupied)
