"""Compile the commit path's programs for a DESCRIBED TPU v5e (no chip).

The chip's compiler is installed here and compiles for a topology that is
described, not attached: what it refuses here costs no chip time.  Nothing
runs, so this says nothing about results or speed.  This is the only test
file that describes the chip; the topology, shardings and shapes are built
in fixtures/tests (never at import — one worker may load libtpu), and the
persistent compile cache is off around the compiles (an entry compiled for a
described chip cannot be read back without one).

8192 lanes as served; small tables (compile time and legality do not depend
on the table size — the real-size memory analysis is a by-hand rehearsal,
CHANGES.md PR 22)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tigerbeetle_tpu import machine, types
from tigerbeetle_tpu.ops import index
from tigerbeetle_tpu.ops import state_machine as sm
from tigerbeetle_tpu.ops import transfer_full as tf
from tigerbeetle_tpu.parallel import sharded

LANES = 8192
ACC, TR, POSTED, HIST = 1 << 16, 1 << 18, 1 << 12, 1 << 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _soa(dtype, sharding, lead=()):
    cols = types.to_soa(np.zeros(1, dtype=dtype))
    return {
        k: jax.ShapeDtypeStruct(lead + (LANES,), v.dtype, sharding=sharding)
        for k, v in cols.items()
    }


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _padded_scan_dispatch(ledger, stacked, counts, timestamps):
    """The grouped dispatch as it was before PR 27: a lax.scan over all
    GROUP_K rows, zero-count rows included.  Kept here as the yardstick
    for the loop's temp bytes only."""

    def step(led, xs):
        soa, cnt, ts = xs
        return sm.create_transfers_impl(led, soa, cnt, ts)

    ledger, codes = jax.lax.scan(step, ledger, (stacked, counts, timestamps))
    return (
        ledger, codes, ledger.transfers.probe_overflow.astype(jnp.uint32),
        stacked["id_lo"], stacked["id_hi"],
    )


def _staged(sharding, rows=None):
    """The staged operands (``staging.stage_batch``): the batch's 14 uint64
    columns, its 5 narrower ones, (count, timestamp); with ``rows``, the
    grouped stack of that many (``staging.stage_group``)."""
    lead = () if rows is None else (rows,)
    return (
        jax.ShapeDtypeStruct(lead + (14, LANES), jnp.uint64, sharding=sharding),
        jax.ShapeDtypeStruct(lead + (5, LANES), jnp.uint32, sharding=sharding),
        jax.ShapeDtypeStruct((2,) + lead, jnp.uint64, sharding=sharding),
    )


def _one_chip_lowerings(topo):
    one = SingleDeviceSharding(topo.devices[0])
    led = _on(jax.eval_shape(lambda: sm.make_ledger(ACC, TR, POSTED, HIST)),
              one)
    k = machine.TpuStateMachine.GROUP_K
    short = machine.TpuStateMachine.GROUP_ROWS_SHORT
    kvec = jax.ShapeDtypeStruct((k,), jnp.uint64, sharding=one)

    def full(has_postvoid):
        return lambda: tf.create_transfers_full.lower(
            led, *_staged(one), None, None, max_passes=8,
            has_postvoid=has_postvoid, has_history=False, use_waves=True,
        )

    lanes = jax.ShapeDtypeStruct((LANES,), jnp.uint64, sharding=one)
    ok = jax.ShapeDtypeStruct((LANES,), jnp.bool_, sharding=one)
    return {
        "fast": lambda: sm.create_transfers_fast.jitted.lower(
            led, *_staged(one)),
        "index_build": lambda: index.build_runs.lower(
            {name: lanes for name in sm.INDEX_KEY_COLS}, lanes, lanes, ok),
        "index_build_row": lambda: index.build_runs.lower(
            *jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    (k,) + x.shape, x.dtype, sharding=one),
                ({name: lanes for name in sm.INDEX_KEY_COLS}, lanes, lanes,
                 ok)),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)),
        "grouped": lambda: machine._group_fast_dispatch.lower(
            led, *_staged(one, k)),
        "grouped_short": lambda: machine._group_fast_dispatch.lower(
            led, *_staged(one, short)),
        "grouped_padded_scan": lambda: jax.jit(
            _padded_scan_dispatch, donate_argnames=("ledger",)
        ).lower(led, _soa(types.TRANSFER_DTYPE, one, lead=(k,)), kvec, kvec),
        "full_scan_plain": full(False),
        "full_scan_postvoid": full(True),
    }


def _sharded_lowering(topo, make_step):
    mesh = Mesh(np.array(topo.devices[:4]), (sharded.AXIS,))
    shard = NamedSharding(mesh, P(sharded.AXIS))
    repl = NamedSharding(mesh, P())
    led = jax.eval_shape(lambda: sm.make_ledger(ACC, TR, POSTED, HIST))

    def table(t):
        # make_sharded_ledger's layout: rows sharded, per-shard counters.
        return _on(dataclasses.replace(
            t, count=jax.ShapeDtypeStruct((4,), np.uint64),
            probe_overflow=jax.ShapeDtypeStruct((4,), np.bool_),
        ), shard)

    led = sm.Ledger(
        accounts=table(led.accounts), transfers=table(led.transfers),
        posted=table(led.posted), history=_on(led.history, repl),
    )
    return make_step(mesh).lower(led, *_staged(repl))


@pytest.mark.parametrize("program", [
    "fast", "grouped", "grouped_short", "full_scan_plain",
    "full_scan_postvoid",
    "sharded_fast_4", "sharded_full_scan_4", "index_build",
    "index_build_row",
])
def test_compiles_for_v5e(topo, no_persistent_cache, program):
    if program == "sharded_fast_4":
        lowered = _sharded_lowering(
            topo, lambda mesh: sharded.sharded_create_transfers(
                mesh, probed=True)
        )
    elif program == "sharded_full_scan_4":
        lowered = _sharded_lowering(
            topo, lambda mesh: sharded.sharded_create_transfers_full(
                mesh, max_passes=8, use_waves=True)
        )
        assert "stablehlo.case" in lowered.as_text()  # the gate, per pass
    else:
        lowered = _one_chip_lowerings(topo)[program]()
    compiled = lowered.compile()  # raises what the chip's compiler raises
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    if program.startswith("sharded"):
        # The cross-shard context exchange is a psum: the compiler must
        # have put an all-reduce in.
        assert "all-reduce" in compiled.as_text()
    if program.startswith("index_build"):
        # A level-0 run is sorted from keys it is handed: no probe loop.
        assert " while(" not in compiled.as_text()


def test_grouped_loop_needs_no_more_temp_than_the_padded_scan(
    topo, no_persistent_cache
):
    """The run-time trip count must not cost the program a second copy of
    anything table-sized (at the served 2^21 / 2^23 by hand: 929,940,992 B
    against the scan's 929,973,248, PERF.md PR 27)."""
    lowerings = _one_chip_lowerings(topo)
    loop = lowerings["grouped"]().compile().memory_analysis()
    scan = lowerings["grouped_padded_scan"]().compile().memory_analysis()
    assert 0 < loop.temp_size_in_bytes <= scan.temp_size_in_bytes
    assert loop.argument_size_in_bytes == scan.argument_size_in_bytes
