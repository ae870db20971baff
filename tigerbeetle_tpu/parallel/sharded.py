"""Sharded (multi-chip) state machine: the ledger partitioned over a device mesh.

The reference scales by *replicating* the whole state machine over a TCP bus
(SURVEY §2.8-2.9; message_bus.zig) — every replica holds all state.  On a TPU
slice we can additionally *shard* one state machine across chips, with XLA
collectives over ICI doing the data movement:

- Ownership: account/transfer keys are assigned to shards by the low bits of
  their hash (owner = mix64(key) & (n_shards-1)); the remaining bits index an
  open-addressing table local to the owner (hash_shift in ops/hash_table.py),
  so probe chains never cross chips.
- Gather phase: every shard probes its local table for the whole (replicated)
  batch, masks to the keys it owns, and one ``psum`` per gathered quantity
  combines the results — after which every shard holds the full gather context
  (~1 MiB per table per batch riding ICI).
- Validation: the pure passes (ops/state_machine.py transfer_codes /
  account_codes) run *replicated* on every shard — deterministic, no
  communication.
- Apply phase: balance deltas are planned over global slot ids (replicated),
  then each shard scatters only the slots it owns; inserts likewise. No
  further communication.

Determinism: every collective is a sum of disjoint (owner-masked) terms, and
all apply-phase writes are owner-local — byte-identical to the single-chip
kernels, which the tests check on a virtual 8-device CPU mesh.

Scope: the sharded kernels cover plain create_accounts/create_transfers
(the benchmark shape), point lookups, AND the fully-general two-phase/
balancing kernel (sharded_create_transfers_full): ops/transfer_full.py's
round-3 split into GatherCtx -> pure core -> apply means the mesh path
builds the context with masked probes + psum combines, runs the identical
Jacobi/ladder math replicated on every shard, and applies owner-locally.
Admission: history-flagged accounts stay single-chip (history is an
append-ordered log, not a hash-partitioned table) — the kernel routes such
batches instead of applying; cold tiering is likewise a single-chip
concern (no bloom on the mesh path).

Device names (docs/tracing.md; metadata only): every jitted program here has
a name of its own (``_named_jit``: ``jit_sharded_create_transfers_fast_probed``
and so on; the commit twins' names contain their single-device twins'), and
the phases carry ``tb/shard_gather`` (the masked local probes and gathers),
``tb/shard_combine`` (the psums) and, after the exchange, the single-device
kernels' own scope names.  In the general program
(``jit_sharded_create_transfers_full[_waves]``) the masks over the combined
rows and the kflags word assembled from the psums are ``tb/shard_combine``
too; its core keeps ``tb/full_waves|full_pass``, its owner-local claims and
writes ``tb/full_apply|full_posted``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import types
from ..u128 import mix64
from ..ops import hash_table as ht
from ..ops import staging
from ..ops import state_machine as sm
from ..ops.state_machine import (
    ACCOUNT_COLS,
    Ledger,
    MAX_PROBE,
    POSTED_COLS,
    TRANSFER_COLS,
    TransferCtx,
)

# One shared version-portable wrapper (check_rep -> check_vma rename shim)
# lives in jaxenv so machine.py, this module, and future mesh callers stay
# on a single spelling — re-exported here for existing importers.
from ..jaxenv import shard_map  # noqa: F401  (re-export)

AXIS = "shard"


def _named_jit(step, name: str, **jit_kwargs):
    """jit ``step`` as the program ``jit_<name>``: every builder's inner
    function is called ``step``, and a device trace names a program by its
    function (docs/tracing.md)."""
    step.__name__ = step.__qualname__ = name
    return jax.jit(step, **jit_kwargs)


def make_sharded_ledger(
    mesh: Mesh,
    accounts_capacity: int,
    transfers_capacity: int,
    posted_capacity: int,
    history_capacity: int = 1,
) -> Ledger:
    """Build a Ledger whose table arrays are sharded over ``mesh`` axis 0.

    Capacities are *global* (power of two, divisible by the shard count).
    Table ``count``/``probe_overflow`` become per-shard vectors of length
    n_shards.  The history log is NOT hash-partitioned (it is an
    append-ordered log): it stays a real single-device History, replicated
    over the mesh (spec P()) and written only by the sequential fallback —
    the sharded kernels route history-touching batches (FLAG_SEQ) instead
    of applying them."""
    n = mesh.devices.size
    for cap in (accounts_capacity, transfers_capacity, posted_capacity):
        assert cap % n == 0 and (cap & (cap - 1)) == 0

    def table(capacity, col_specs):
        return ht.Table(
            key_lo=np.zeros((capacity,), np.uint64),
            key_hi=np.zeros((capacity,), np.uint64),
            tombstone=np.zeros((capacity,), np.bool_),
            cols={k: np.zeros((capacity,), dt) for k, dt in col_specs.items()},
            count=np.zeros((n,), np.uint64),
            probe_overflow=np.zeros((n,), np.bool_),
        )

    ledger = Ledger(
        accounts=table(accounts_capacity, ACCOUNT_COLS),
        transfers=table(transfers_capacity, TRANSFER_COLS),
        posted=table(posted_capacity, POSTED_COLS),
        history=sm.make_history(history_capacity),
    )
    shard = NamedSharding(mesh, P(AXIS))
    repl = NamedSharding(mesh, P())
    return Ledger(
        accounts=jax.tree_util.tree_map(
            lambda x: jax.device_put(x, shard), ledger.accounts
        ),
        transfers=jax.tree_util.tree_map(
            lambda x: jax.device_put(x, shard), ledger.transfers
        ),
        posted=jax.tree_util.tree_map(
            lambda x: jax.device_put(x, shard), ledger.posted
        ),
        history=jax.tree_util.tree_map(
            lambda x: jax.device_put(x, repl), ledger.history
        ),
    )


def _specs_like(tree):
    """Ledger partition specs: tables shard over axis 0, history (an
    append-ordered log the mesh kernels never touch) stays replicated."""
    if isinstance(tree, Ledger):
        return Ledger(
            accounts=jax.tree_util.tree_map(lambda _: P(AXIS), tree.accounts),
            transfers=jax.tree_util.tree_map(
                lambda _: P(AXIS), tree.transfers
            ),
            posted=jax.tree_util.tree_map(lambda _: P(AXIS), tree.posted),
            history=jax.tree_util.tree_map(lambda _: P(), tree.history),
        )
    return jax.tree_util.tree_map(lambda _: P(AXIS), tree)


def _psum_one_owner(x):
    """psum of a value that at most ONE shard holds non-zero (every other
    shard contributes 0).  The TPU lowers only plain 32-bit Sum all-reduces
    — a u64 add is an emulated u32 pair with a carry, UNIMPLEMENTED at
    compile — and with a single owner the two u32 halves sum without
    carries, so splitting is exact."""
    if x.dtype != jnp.uint64:
        return jax.lax.psum(x, AXIS)
    lo = jax.lax.psum(x.astype(jnp.uint32), AXIS)
    hi = jax.lax.psum((x >> jnp.uint64(32)).astype(jnp.uint32), AXIS)
    return lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))


class _ShardGather:
    """Per-shard masked probe + psum combine for one key set."""

    def __init__(self, table: ht.Table, lo, hi, n_shards: int, shift: int):
        with jax.named_scope("tb/shard_gather"):
            my = jax.lax.axis_index(AXIS).astype(jnp.uint64)
            h = mix64(lo, hi)
            self.owner_mask = (h & jnp.uint64(n_shards - 1)) == my
            look = ht.lookup(table, lo, hi, MAX_PROBE, hash_shift=shift)
            local_cap = table.capacity
            self.found_l = look.found & self.owner_mask
            self.slot_l = look.slot
            self.overflow_l = look.overflow  # local probe exhaustion (bool)
            gslot = my * jnp.uint64(local_cap) + look.slot
        with jax.named_scope("tb/shard_combine"):
            self.found = (
                jax.lax.psum(self.found_l.astype(jnp.uint32), AXIS) > 0
            )
            self.gslot = _psum_one_owner(
                jnp.where(self.found_l, gslot, jnp.uint64(0))
            )

    def rows(self, table: ht.Table) -> Dict[str, jax.Array]:
        with jax.named_scope("tb/shard_gather"):
            local = ht.gather_cols(table, self.slot_l, self.found_l)
        with jax.named_scope("tb/shard_combine"):
            return {k: _psum_one_owner(v) for k, v in local.items()}


def sharded_create_transfers(mesh: Mesh, probed: bool = False):
    """Build the jitted sharded create_transfers step for ``mesh``.

    Returns fn(ledger, *staging.stage_batch(...)) -> (ledger, codes), with the
    ledger sharded per make_sharded_ledger and the staged batch replicated.

    ``probed`` (STATIC) additionally returns the per-shard transfers
    probe_overflow lanes widened into a FRESH uint32[n_shards] output —
    the sharded twin of sm.create_transfers_fast_probed: a deferred
    readback handle must be able to fetch the overflow flag after a later
    dispatch on the FIFO lane has donated this ledger, and riding the
    codes readback it costs zero extra syncs (docs/commit_pipeline.md)."""
    n_shards = mesh.devices.size
    shift = n_shards.bit_length() - 1

    def local_step(ledger: Ledger, cols64, cols32, meta):
        batch, count, timestamp = staging.unstage(
            types.TRANSFER_DTYPE, cols64, cols32, meta
        )
        acc, tr = ledger.accounts, ledger.transfers
        local_acc_cap = acc.capacity

        dr_g = _ShardGather(
            acc, batch["debit_account_id_lo"], batch["debit_account_id_hi"],
            n_shards, shift,
        )
        cr_g = _ShardGather(
            acc, batch["credit_account_id_lo"], batch["credit_account_id_hi"],
            n_shards, shift,
        )
        ex_g = _ShardGather(tr, batch["id_lo"], batch["id_hi"], n_shards, shift)

        lane = jnp.arange(batch["id_lo"].shape[0], dtype=jnp.int32)
        valid = lane < count.astype(jnp.int32)
        ctx = TransferCtx(
            dr_found=dr_g.found & valid,
            cr_found=cr_g.found & valid,
            dr_slot=dr_g.gslot,
            cr_slot=cr_g.gslot,
            dr=dr_g.rows(acc),
            cr=cr_g.rows(acc),
            ex_found=ex_g.found & valid,
            e=ex_g.rows(tr),
        )

        # Replicated validation (identical on every shard).  The phases
        # carry the single-device kernel's scope names (docs/tracing.md):
        # the same work, after the exchange.
        with jax.named_scope("tb/validate"):
            codes, ok, ts, pending = sm.transfer_codes(
                batch, ctx, count, timestamp
            )

        # Balance plan over global slots, applied owner-locally.
        with jax.named_scope("tb/balance"):
            global_cap = local_acc_cap * n_shards
            plan = sm.balance_plan(
                ctx.dr_slot, ctx.cr_slot, ok,
                batch["amount_lo"], pending, global_cap,
            )
            my = jax.lax.axis_index(AXIS).astype(jnp.uint64)
            base = my * jnp.uint64(local_acc_cap)
            in_range = (plan.s_slot >= base) & (
                plan.s_slot < base + jnp.uint64(local_acc_cap)
            )
            local_plan = sm.BalancePlan(
                s_slot=jnp.where(
                    in_range, plan.s_slot - base, jnp.uint64(local_acc_cap)
                ),
                head=plan.head & in_range,
                deltas=plan.deltas,
            )
            accounts = sm.apply_balance_plan(acc, local_plan)

        # Owner-local transfer inserts.
        with jax.named_scope("tb/insert"):
            rows = sm.transfer_rows(batch, count, timestamp)
            transfers, _ = ht.insert(
                tr, batch["id_lo"], batch["id_hi"],
                ok & ex_g.owner_mask, rows, MAX_PROBE, hash_shift=shift,
            )

        out = ledger.replace(accounts=accounts, transfers=transfers)
        if probed:
            # Fresh (non-aliasing) per-shard overflow lanes: local (1,)
            # widens to the global uint32[n_shards] vector.
            return out, codes, transfers.probe_overflow.astype(jnp.uint32)
        return out, codes

    def step(ledger, cols64, cols32, meta):
        out_specs = (_specs_like(ledger), P())
        if probed:
            out_specs = out_specs + (P(AXIS),)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(_specs_like(ledger), P(), P(), P()),
            out_specs=out_specs,
            # vma-checking is off because ht.lookup's probe while_loop mixes
            # replicated (keys) and shard-varying (table) carry values; the
            # library kernels are backend-agnostic and cannot pvary-annotate.
            # Correctness is covered by byte-parity vs single-chip in
            # tests/test_sharded.py instead.
            check_vma=False,
        )(ledger, cols64, cols32, meta)

    return _named_jit(
        step,
        "sharded_create_transfers_fast" + ("_probed" if probed else ""),
        donate_argnames=("ledger",),
    )


def sharded_create_transfers_full(
    mesh: Mesh, max_passes: int = None, use_waves: bool = False
):
    """The fully-general transfer kernel (two-phase/balancing/limits) over
    the device mesh.  ``max_passes`` mirrors LedgerConfig.jacobi_max_passes
    (defaults to the kernel's budget) so both serving paths honor the knob.

    Context is gathered by masked probes + psum (after which every shard
    holds the full replicated GatherCtx), the pure Jacobi/ladder core runs
    replicated, and claims/scatters/inserts apply owner-locally — so the
    result is byte-identical to the single-chip kernel. History-flagged
    accounts route (FLAG_SEQ) instead of applying: history is an ordered
    append log, which stays a single-chip structure.

    ``use_waves`` (STATIC; TB_WAVES at the machine level) arms the
    conflict-index wave scheduler INSIDE the replicated kernel core: the
    hazard-lane wave bounds are computed over the shard-local batch view
    (which is the full replicated batch, so every shard certifies the same
    bound) and certified batches commit after the proved pass count — the
    exact docs/waves.md semantics, now on the mesh path.  On, a FOURTH
    replicated int32[11] wave-profile vector is returned.

    Returns fn(ledger, *staging.stage_batch(...)) -> (ledger, codes, kflags
    [, wave_vec]).
    """
    from ..ops import transfer_full as _tf

    if max_passes is None:
        max_passes = _tf._MAX_PASSES
    from ..ops import transfer_full as tf
    from ..ops.state_machine import TF_POST, TF_VOID

    n_shards = mesh.devices.size
    shift = n_shards.bit_length() - 1

    def _view(g: _ShardGather, table: ht.Table, found) -> tf.AccountView:
        rows = g.rows(table)
        return tf.AccountView(
            found=found,
            slot=g.gslot,
            flags=rows["flags"],
            ledger=rows["ledger"],
            bal={
                f + l: rows[f + l]
                for f in ("debits_pending", "debits_posted",
                          "credits_pending", "credits_posted")
                for l in ("_lo", "_hi")
            },
        )

    def local_step(ledger: Ledger, cols64, cols32, meta):
        batch, count, timestamp = staging.unstage(
            types.TRANSFER_DTYPE, cols64, cols32, meta
        )
        acc, tr, posted_t = ledger.accounts, ledger.transfers, ledger.posted
        n = batch["id_lo"].shape[0]
        with jax.named_scope("tb/shard_gather"):
            lane = jnp.arange(n, dtype=jnp.int32)
            valid = lane < count.astype(jnp.int32)
            postvoid = (
                ((batch["flags"] & TF_POST) != 0)
                | ((batch["flags"] & TF_VOID) != 0)
            ) & valid

        @jax.named_scope("tb/shard_combine")
        def masked(rows, keep):
            # The combined rows, zeroed where the core must see "no row":
            # part of the exchange's result, like the psums it follows.
            return {k: jnp.where(keep, v, jnp.zeros_like(v))
                    for k, v in rows.items()}

        ex_g = _ShardGather(tr, batch["id_lo"], batch["id_hi"], n_shards, shift)
        # Zero-mask by `valid` exactly like the single-chip gather
        # (ex_found = found & valid there): every current consumer is gated
        # on ex_found anyway, but an unmasked row would be a latent
        # byte-parity divergence if e_tab ever gains another consumer.
        e_tab = masked(ex_g.rows(tr), ex_g.found & valid)
        p_g = _ShardGather(
            tr, batch["pending_id_lo"], batch["pending_id_hi"], n_shards, shift
        )
        p_tab_found = p_g.found & postvoid
        # Zero-mask rows exactly like the single-chip gather (mask includes
        # postvoid): the core treats zeros as "no row".
        p_tab = masked(p_g.rows(tr), p_tab_found)

        drT_g = _ShardGather(
            acc, batch["debit_account_id_lo"], batch["debit_account_id_hi"],
            n_shards, shift,
        )
        crT_g = _ShardGather(
            acc, batch["credit_account_id_lo"], batch["credit_account_id_hi"],
            n_shards, shift,
        )
        pdr_g = _ShardGather(
            acc, p_tab["debit_account_id_lo"], p_tab["debit_account_id_hi"],
            n_shards, shift,
        )
        pcr_g = _ShardGather(
            acc, p_tab["credit_account_id_lo"], p_tab["credit_account_id_hi"],
            n_shards, shift,
        )
        postedT_g = _ShardGather(
            posted_t, p_tab["timestamp"], jnp.zeros_like(p_tab["timestamp"]),
            n_shards, shift,
        )
        postedT_found = postedT_g.found & p_tab_found
        postedT_val = postedT_g.rows(posted_t)["fulfillment"]

        @jax.named_scope("tb/shard_combine")
        def any_shard(local_bool):
            return jax.lax.psum(local_bool.astype(jnp.uint32), AXIS) > 0

        def flag_if_any(local_bool, flag):
            return jnp.where(
                any_shard(local_bool), jnp.uint32(flag), jnp.uint32(0)
            )

        with jax.named_scope("tb/shard_combine"):
            probe_grow = (
                flag_if_any(drT_g.overflow_l | crT_g.overflow_l
                            | pdr_g.overflow_l | pcr_g.overflow_l,
                            tf.FLAG_GROW_ACCOUNTS)
                | flag_if_any(ex_g.overflow_l | p_g.overflow_l,
                              tf.FLAG_GROW_TRANSFERS)
                | flag_if_any(postedT_g.overflow_l, tf.FLAG_GROW_POSTED)
            )
            ctx = tf.GatherCtx(  # the found masks of the combined context
                ex_found=ex_g.found & valid,
                e_tab=e_tab,
                p_tab_found=p_tab_found,
                p_tab=p_tab,
                drT=_view(drT_g, acc, drT_g.found & valid),
                crT=_view(crT_g, acc, crT_g.found & valid),
                pdr=_view(pdr_g, acc, pdr_g.found & p_tab_found),
                pcr=_view(pcr_g, acc, pcr_g.found & p_tab_found),
                postedT_found=postedT_found,
                postedT_val=postedT_val,
                probe_grow=probe_grow,
                accounts_capacity=jnp.uint64(acc.capacity * n_shards),
            )
        plan = tf._kernel_core(
            ctx, batch, count, timestamp, max_passes, use_waves=use_waves
        )

        # History admission: the mesh ledger has no history log — route
        # instead of silently dropping rows.
        route = plan.route | jnp.where(
            jnp.any(plan.do_hist), jnp.uint32(tf.FLAG_SEQ), jnp.uint32(0)
        )

        # Owner-local claims (insert-probe overflow routes with nothing
        # applied, exactly like single-chip).  Claims and writes carry the
        # single-device general kernel's scope names.
        with jax.named_scope("tb/full_apply"):
            t_claim, t_ovf = ht.claim_slots(
                tr, batch["id_lo"], batch["id_hi"],
                plan.ok & ex_g.owner_mask, MAX_PROBE, hash_shift=shift,
            )
        my = jax.lax.axis_index(AXIS).astype(jnp.uint64)
        with jax.named_scope("tb/full_posted"):
            pk_owner = (
                mix64(plan.posted_key, jnp.zeros_like(plan.posted_key))
                & jnp.uint64(n_shards - 1)
            ) == my
            p_claim, p_ovf = ht.claim_slots(
                posted_t, plan.posted_key, jnp.zeros_like(plan.posted_key),
                plan.pv_ok & pk_owner, MAX_PROBE, hash_shift=shift,
            )
        with jax.named_scope("tb/shard_combine"):
            kflags = (
                probe_grow
                | route
                | flag_if_any(t_ovf, tf.FLAG_GROW_TRANSFERS)
                | flag_if_any(p_ovf, tf.FLAG_GROW_POSTED)
            )
            commit = kflags == jnp.uint32(0)

        # Balance scatter: global slot runs, owner-local writes.
        with jax.named_scope("tb/full_apply"):
            local_cap = acc.capacity
            base = my * jnp.uint64(local_cap)
            in_range = (plan.s_slot >= base) & (
                plan.s_slot < base + jnp.uint64(local_cap)
            )
            scat = plan.scat & commit & in_range
            sentinel = jnp.uint64(local_cap)
            accounts = ht.scatter_cols(
                acc, jnp.where(scat, plan.s_slot - base, sentinel), scat,
                plan.bal_incl,
            )

            ins_rows = {
                name: plan.row[name].astype(dt)
                for name, dt in TRANSFER_COLS.items()
            }
            transfers = ht.write_rows(
                tr, batch["id_lo"], batch["id_hi"], t_claim,
                plan.ok & commit & ex_g.owner_mask, ins_rows,
            )
        with jax.named_scope("tb/full_posted"):
            posted_out = ht.write_rows(
                posted_t, plan.posted_key, jnp.zeros_like(plan.posted_key),
                p_claim, plan.pv_ok & commit & pk_owner,
                {"fulfillment": jnp.where(
                    plan.post, jnp.uint32(1), jnp.uint32(2))},
            )

        out = ledger.replace(
            accounts=accounts, transfers=transfers, posted=posted_out
        )
        if use_waves:
            wave_vec = jnp.concatenate([
                plan.passes.reshape(1), plan.wave_bound.reshape(1),
                plan.wave_hist,
            ])
            return out, plan.codes, kflags, wave_vec
        return out, plan.codes, kflags

    def step(ledger, cols64, cols32, meta):
        out_specs = (_specs_like(ledger), P(), P())
        if use_waves:
            out_specs = out_specs + (P(),)
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(_specs_like(ledger), P(), P(), P()),
            out_specs=out_specs,
            check_vma=False,  # see sharded_create_transfers' justification
        )(ledger, cols64, cols32, meta)

    return _named_jit(
        step,
        "sharded_create_transfers_full" + ("_waves" if use_waves else ""),
        donate_argnames=("ledger",),
    )


def sharded_lookup(mesh: Mesh, table_name: str):
    """Jitted sharded point-lookup over ``ledger.<table_name>``: every
    shard probes its local partition for the replicated id batch; one psum
    per column assembles the full rows on every chip.

    Returns fn(ledger, id_lo, id_hi) -> (found[b], rows{col: [b]})."""
    n_shards = mesh.devices.size
    shift = n_shards.bit_length() - 1

    def local_step(ledger: Ledger, id_lo, id_hi):
        table = getattr(ledger, table_name)
        g = _ShardGather(table, id_lo, id_hi, n_shards, shift)
        rows = g.rows(table)
        # Match the single-chip lookup shape (sm.lookup_* include the id
        # columns so types.from_soa can build full wire rows).
        rows["id_lo"] = jnp.where(g.found, id_lo, jnp.uint64(0))
        rows["id_hi"] = jnp.where(g.found, id_hi, jnp.uint64(0))
        return g.found, rows

    def step(ledger, id_lo, id_hi):
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(_specs_like(ledger), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,  # see sharded_create_transfers' justification
        )(ledger, id_lo, id_hi)

    return _named_jit(step, f"sharded_lookup_{table_name}")


def sharded_create_accounts(mesh: Mesh):
    """Jitted sharded create_accounts step for ``mesh``:
    fn(ledger, *staging.stage_batch(...)) -> (ledger, codes)."""
    n_shards = mesh.devices.size
    shift = n_shards.bit_length() - 1

    def local_step(ledger: Ledger, cols64, cols32, meta):
        batch, count, timestamp = staging.unstage(
            types.ACCOUNT_DTYPE, cols64, cols32, meta
        )
        acc = ledger.accounts
        g = _ShardGather(acc, batch["id_lo"], batch["id_hi"], n_shards, shift)
        lane = jnp.arange(batch["id_lo"].shape[0], dtype=jnp.int32)
        valid = lane < count.astype(jnp.int32)
        existing = g.rows(acc)
        with jax.named_scope("tb/validate"):
            codes, ok = sm.account_codes(
                batch, g.found & valid, existing, count
            )
        with jax.named_scope("tb/insert"):
            rows = sm.account_rows(batch, count, timestamp)
            accounts, _ = ht.insert(
                acc, batch["id_lo"], batch["id_hi"],
                ok & g.owner_mask, rows, MAX_PROBE, hash_shift=shift,
            )
        return ledger.replace(accounts=accounts), codes

    def step(ledger, cols64, cols32, meta):
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(_specs_like(ledger), P(), P(), P()),
            out_specs=(_specs_like(ledger), P()),
            # vma-checking is off because ht.lookup's probe while_loop mixes
            # replicated (keys) and shard-varying (table) carry values; the
            # library kernels are backend-agnostic and cannot pvary-annotate.
            # Correctness is covered by byte-parity vs single-chip in
            # tests/test_sharded.py instead.
            check_vma=False,
        )(ledger, cols64, cols32, meta)

    return _named_jit(
        step, "sharded_create_accounts", donate_argnames=("ledger",)
    )


# ---------------------------------------------------------------------------
# Per-shard scrub lanes (machine.scrub_check under TB_SHARDS)
# ---------------------------------------------------------------------------


def sharded_scrub_digest(mesh: Mesh):
    """Per-shard scrub fold lanes: uint64[n_shards, 3] where row s is shard
    s's partial (accounts, transfers, posted) fold over its local partition.

    The scrub folds are wrap-adds over live rows (ops/scrub.py), so the
    GLOBAL digests are the per-shard lanes summed mod 2^64 — the host
    compares that sum against the mirror's expectation, and the lanes
    themselves localize a mismatch to one shard.  ONE readback through the
    commit-barrier funnel, like the single-device fold."""
    from ..ops import scrub as scrub_ops

    def local_step(ledger: Ledger):
        lanes = jnp.stack([
            scrub_ops._fold_accounts(ledger.accounts),
            scrub_ops._fold_transfers(ledger.transfers),
            scrub_ops._fold_posted(ledger.posted),
        ])
        return lanes[None, :]  # (1, 3) local -> (n_shards, 3) global

    def step(ledger):
        return shard_map(
            local_step,
            mesh=mesh,
            in_specs=(_specs_like(ledger),),
            out_specs=P(AXIS),
            check_vma=False,  # see sharded_create_transfers' justification
        )(ledger)

    # Deliberately NOT donated: the scrub must never consume the ledger.
    return _named_jit(step, "sharded_scrub_digest")


# ---------------------------------------------------------------------------
# Per-shard Merkle subtrees (machine merkle mode under TB_SHARDS)
# ---------------------------------------------------------------------------
#
# The commitment forest (ops/merkle.py) composes with sharding as one
# subtree per shard over the shard's LOCAL slot layout: heaps carry a
# leading shard partition (global uint64[n * 2 * local_cap] sharded over
# the mesh axis), updates touch owner-locally (a non-owned key is simply
# absent from the local table, so its probe misses and the lane drops),
# and the canonical live commitment is the per-shard roots folded by
# wrap-sum — read back through the same per-shard uint64 lanes the scrub
# fold uses.  Pending references resolve through the _ShardGather psum
# (the pending transfer's row lives on ONE shard; its posted key and
# account sides must reach THEIR owners).
#
# Under TB_MERKLE_ASYNC (docs/commitments.md deferred lane) the update
# steps below run from machine.merkle_settle() instead of inside each
# commit closure: the settle drains COALESCED touch records (up to
# batch_lanes rows per step call) through these same jitted programs —
# same size classes, same owner-local probe semantics — so the deferred
# lane composes with sharding with no sharded-specific state.  Settle
# runs only on a drained dispatch lane (the closures swap/donate the
# sharded ledger buffers), which the hard barriers guarantee.


def merkle_steps(mesh: Mesh) -> Dict[str, object]:
    """Jitted sharded merkle build/update/verify/roots steps, cached
    process-wide like machine_steps."""
    key = (
        tuple(int(d.id) for d in mesh.devices.flat),
        mesh.axis_names,
        "merkle",
    )
    steps = _STEP_CACHE.get(key)
    if steps is not None:
        return steps
    from ..ops import merkle as mk

    n_shards = mesh.devices.size
    shift = n_shards.bit_length() - 1

    def build_local(ledger: Ledger):
        return mk.build_forest_impl(ledger)

    def build(ledger):
        return shard_map(
            build_local,
            mesh=mesh,
            in_specs=(_specs_like(ledger),),
            out_specs=jax.tree_util.tree_map(
                lambda _: P(AXIS), mk.Forest(0, 0, 0)
            ),
            check_vma=False,  # see sharded_create_transfers' justification
        )(ledger)

    def upd_accounts_local(forest, ledger, lo, hi):
        return mk.update_accounts_impl(
            forest, ledger, lo, hi, max_probe=MAX_PROBE, hash_shift=shift
        )

    def upd_transfers_local(has_postvoid):
        def fn(forest, ledger, id_lo, id_hi, acc_lo, acc_hi,
               pend_lo, pend_hi):
            if has_postvoid:
                # Resolve pending refs cluster-wide: the row lives on one
                # shard; psum carries its posted key + account sides to
                # every shard, whose local touches keep only what they own.
                p_g = _ShardGather(
                    ledger.transfers, pend_lo, pend_hi, n_shards, shift
                )
                rows = p_g.rows(ledger.transfers)

                def masked(name):
                    return jnp.where(p_g.found, rows[name], jnp.uint64(0))

                pend_ts = masked("timestamp")
                acc_lo = jnp.concatenate([
                    acc_lo, masked("debit_account_id_lo"),
                    masked("credit_account_id_lo"),
                ])
                acc_hi = jnp.concatenate([
                    acc_hi, masked("debit_account_id_hi"),
                    masked("credit_account_id_hi"),
                ])
                posted = mk.touch_tree(
                    forest.posted, ledger.posted, pend_ts,
                    jnp.zeros_like(pend_ts), "posted", MAX_PROBE, shift,
                )
            else:
                posted = forest.posted
            transfers = mk.touch_tree(
                forest.transfers, ledger.transfers, id_lo, id_hi,
                "transfers", MAX_PROBE, shift,
            )
            accounts = mk.touch_tree(
                forest.accounts, ledger.accounts, acc_lo, acc_hi,
                "accounts", MAX_PROBE, shift,
            )
            return mk.Forest(
                accounts=accounts, transfers=transfers, posted=posted
            )

        return fn

    def verify_local(forest, ledger):
        return mk.verify_roots_impl(forest, ledger)[None]  # (1, 2, 3)

    def verify(forest, ledger):
        return shard_map(
            verify_local,
            mesh=mesh,
            in_specs=(
                jax.tree_util.tree_map(lambda _: P(AXIS), mk.Forest(0, 0, 0)),
                _specs_like(ledger),
            ),
            out_specs=P(AXIS),
            check_vma=False,  # see sharded_create_transfers' justification
        )(forest, ledger)

    def roots_local(forest):
        return jnp.stack([
            forest.accounts[1], forest.transfers[1], forest.posted[1]
        ])[None]

    def roots(forest):
        return shard_map(
            roots_local,
            mesh=mesh,
            in_specs=(
                jax.tree_util.tree_map(lambda _: P(AXIS), mk.Forest(0, 0, 0)),
            ),
            out_specs=P(AXIS),
            check_vma=False,  # see sharded_create_transfers' justification
        )(forest)

    forest_specs = jax.tree_util.tree_map(lambda _: P(AXIS), mk.Forest(0, 0, 0))

    def wrap_update(fn, name):
        def step(forest, ledger, *keys):
            return shard_map(
                fn,
                mesh=mesh,
                in_specs=(forest_specs, _specs_like(ledger))
                + tuple(P() for _ in keys),
                out_specs=forest_specs,
                check_vma=False,  # see sharded_create_transfers
            )(forest, ledger, *keys)

        return _named_jit(
            step, f"sharded_merkle_{name}", donate_argnames=("forest",)
        )

    steps = {
        # build/verify/roots deliberately NOT donated (reads).
        "build": _named_jit(build, "sharded_merkle_build"),
        "verify": _named_jit(verify, "sharded_merkle_verify"),
        "roots": _named_jit(roots, "sharded_merkle_roots"),
        "update_accounts": wrap_update(
            upd_accounts_local, "update_accounts"
        ),
        "update_transfers": wrap_update(
            upd_transfers_local(False), "update_transfers"
        ),
        "update_transfers_pv": wrap_update(
            upd_transfers_local(True), "update_transfers_pv"
        ),
    }
    _STEP_CACHE[key] = steps
    return steps


# ---------------------------------------------------------------------------
# Host-side layout converters (sequential fallback, checkpoints, queries)
# ---------------------------------------------------------------------------
#
# The sharded and single-device layouts hold identical CONTENT under
# different slot assignment: single-device homes at mix64(key) & (C-1);
# sharded homes at shard (mix64 & (n-1)), local slot ((mix64 >> shift) &
# (C/n - 1)).  These converters re-place every live row host-side with the
# exact linear-probe discipline of ht.claim_slots for distinct keys
# (insertion in row order == the batched claim protocol, since unplaced
# lanes sharing a probe slot always share a home).  Both are deterministic
# functions of the input layout, so every replica replaying the same commit
# stream converges to byte-identical canonical arrays (checkpoint file
# checksums must agree across the cluster).  Cost is O(rows) host work —
# paid only at sequential fallbacks, checkpoint captures, and the first
# query after a commit, never on the sharded commit hot path.


def _host_rows(table: ht.Table):
    """(key_lo, key_hi, cols, live_idx) host copies; live rows in slot
    order (deterministic given the layout), tombstones dropped."""
    key_lo = np.asarray(table.key_lo)
    key_hi = np.asarray(table.key_hi)
    tomb = np.asarray(table.tombstone)
    live = ((key_lo != 0) | (key_hi != 0)) & ~tomb
    idx = np.flatnonzero(live)
    cols = {k: np.asarray(v) for k, v in table.cols.items()}
    return key_lo, key_hi, cols, idx


def _probe_place_ref(homes: np.ndarray, region_base: np.ndarray,
                     region_mask: int, capacity: int) -> np.ndarray:
    """Reference linear-probe placement (the original per-row host loop):
    row i lands at the first free slot of region_base[i] + ((homes[i] + k)
    & region_mask).  O(rows) interpreted work — kept as the oracle the
    vectorized _probe_place is pinned bit-identical against
    (tests/test_sharded.py)."""
    occupied = np.zeros(capacity, bool)
    slots = np.empty(len(homes), np.int64)
    for i in range(len(homes)):
        s = int(homes[i])
        base = int(region_base[i])
        while occupied[base + s]:
            s = (s + 1) & region_mask
        occupied[base + s] = True
        slots[i] = base + s
    return slots


def _probe_place(homes: np.ndarray, region_base: np.ndarray, region_mask: int,
                 capacity: int) -> np.ndarray:
    """Vectorized linear-probe placement, bit-identical to
    _probe_place_ref (ROADMAP item 1 follow-up: the canonical-view
    rebuild's per-row host loop was O(live rows) interpreted work — a real
    tax on the first query after every sharded commit).

    Sequential FCFS insertion satisfies one invariant that pins the
    assignment uniquely: every slot a row probes PAST holds a row with a
    smaller row index (it was already there when the later row walked).
    So the fixpoint of a displacement sweep — every unplaced row proposes
    to its current probe slot, each slot keeps the smallest row index it
    has ever been offered (np.minimum.at), losers and stolen-from rows
    advance — IS the sequential assignment, computed in O(max displacement)
    vector rounds instead of O(live rows) interpreted probe walks.  The
    PR 7 claim_slots cost discipline (one upfront (home, lane) ordering
    per round, occupancy as flat vectors, no per-row Python), applied to
    the converter's FCFS protocol; tests/test_sharded.py pins parity
    against the scalar oracle including forced same-home and
    cross-group-displacement collisions."""
    n = len(homes)
    if n == 0:
        return np.empty(0, np.int64)
    base = region_base.astype(np.int64)
    homes64 = homes.astype(np.int64)
    owner = np.full(capacity, n, np.int64)  # n = unowned sentinel
    offset = np.zeros(n, np.int64)
    row_slot = np.full(n, -1, np.int64)
    active = np.arange(n, dtype=np.int64)
    while active.size:
        cur = base[active] + ((homes64[active] + offset[active]) & region_mask)
        prev = owner[cur].copy()
        np.minimum.at(owner, cur, active)
        won = owner[cur] == active
        row_slot[active[won]] = cur[won]
        offset[active[~won]] += 1  # lost the proposal: advance one
        # Stolen-from rows (a smaller index claimed their slot) rejoin one
        # past the stolen slot.  One victim per slot, winners' slots are
        # unique, so victims are unique.
        victims = prev[won]
        victims = victims[victims < n]
        if victims.size:
            offset[victims] = (
                (row_slot[victims] - base[victims] - homes64[victims])
                & region_mask
            ) + 1
            row_slot[victims] = -1
            active = np.concatenate([active[~won], victims])
        else:
            active = active[~won]
    return row_slot


def _fill_table(capacity: int, key_lo, key_hi, cols, slots,
                col_specs) -> ht.Table:
    out_lo = np.zeros(capacity, np.uint64)
    out_hi = np.zeros(capacity, np.uint64)
    out_lo[slots] = key_lo
    out_hi[slots] = key_hi
    out_cols = {}
    for name, dt in col_specs.items():
        buf = np.zeros(capacity, dt)
        buf[slots] = cols[name]
        out_cols[name] = jnp.asarray(buf)
    return ht.Table(
        key_lo=jnp.asarray(out_lo),
        key_hi=jnp.asarray(out_hi),
        tombstone=jnp.zeros((capacity,), jnp.bool_),
        cols=out_cols,
        count=jnp.uint64(len(slots)),
        probe_overflow=jnp.bool_(False),
    )


_COL_SPECS = {
    "accounts": ACCOUNT_COLS,
    "transfers": TRANSFER_COLS,
    "posted": POSTED_COLS,
}


def unshard_ledger(ledger: Ledger, mesh: Mesh) -> sm.Ledger:
    """Canonical single-device Ledger with the sharded ledger's exact
    content (single-device probe layout, scalar counts).  The history log
    is already single-device (replicated) and passes through unchanged."""
    from ..ops.scrub import mix64_np

    def un_table(table: ht.Table, name: str) -> ht.Table:
        cap = table.capacity
        key_lo, key_hi, cols, idx = _host_rows(table)
        k_lo, k_hi = key_lo[idx], key_hi[idx]
        homes = mix64_np(k_lo, k_hi) & np.uint64(cap - 1)
        slots = _probe_place(
            homes, np.zeros(len(idx), np.int64), cap - 1, cap
        )
        return _fill_table(
            cap, k_lo, k_hi, {k: v[idx] for k, v in cols.items()}, slots,
            _COL_SPECS[name],
        )

    return sm.Ledger(
        accounts=un_table(ledger.accounts, "accounts"),
        transfers=un_table(ledger.transfers, "transfers"),
        posted=un_table(ledger.posted, "posted"),
        history=sm.History(
            cols={k: jnp.asarray(np.asarray(v))
                  for k, v in ledger.history.cols.items()},
            count=jnp.uint64(int(np.asarray(ledger.history.count))),
        ),
    )


def _shard_table(table: ht.Table, name: str, mesh: Mesh,
                 new_capacity: int = None) -> ht.Table:
    """Host-side (re)placement of one table into the sharded layout at
    ``new_capacity`` (default: same global capacity) — used by
    shard_ledger and by growth under sharding."""
    from ..ops.scrub import mix64_np

    n = mesh.devices.size
    shift = n.bit_length() - 1
    cap = new_capacity if new_capacity is not None else table.capacity
    assert cap % n == 0 and (cap & (cap - 1)) == 0
    local_cap = cap // n
    key_lo, key_hi, cols, idx = _host_rows(table)
    k_lo, k_hi = key_lo[idx], key_hi[idx]
    h = mix64_np(k_lo, k_hi)
    owner = (h & np.uint64(n - 1)).astype(np.int64)
    homes = (h >> np.uint64(shift)) & np.uint64(local_cap - 1)
    slots = _probe_place(homes, owner * local_cap, local_cap - 1, cap)
    out = _fill_table(
        cap, k_lo, k_hi, {k: v[idx] for k, v in cols.items()}, slots,
        _COL_SPECS[name],
    )
    counts = np.bincount(owner, minlength=n).astype(np.uint64)
    out = out.replace(
        count=counts, probe_overflow=np.zeros((n,), np.bool_)
    )
    spec = NamedSharding(mesh, P(AXIS))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, spec), out)


def shard_ledger(single: sm.Ledger, mesh: Mesh) -> Ledger:
    """Sharded Ledger with the single-device ledger's exact content
    (owner-partitioned probe layout, per-shard count vectors)."""
    repl = NamedSharding(mesh, P())
    return Ledger(
        accounts=_shard_table(single.accounts, "accounts", mesh),
        transfers=_shard_table(single.transfers, "transfers", mesh),
        posted=_shard_table(single.posted, "posted", mesh),
        history=jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(np.asarray(x)), repl),
            single.history,
        ),
    )


def grow_sharded_table(table: ht.Table, name: str, new_capacity: int,
                       mesh: Mesh) -> ht.Table:
    """ht.grow for a sharded table: owners are the LOW hash bits so every
    row stays on its shard; only the local homes rehash (the hash_shift
    discipline).  Host-side re-placement, same determinism argument as the
    converters."""
    assert new_capacity >= table.capacity
    return _shard_table(table, name, mesh, new_capacity)


# ---------------------------------------------------------------------------
# Jitted step cache (machine.py's serving surface)
# ---------------------------------------------------------------------------

_STEP_CACHE: Dict[tuple, dict] = {}


def machine_steps(mesh: Mesh, max_passes: int) -> dict:
    """The jitted sharded commit/scrub steps for ``mesh``, cached process-
    wide by (device ids, max_passes): a VOPR cluster's replicas (or any two
    machines on one mesh) share ONE set of compiled programs instead of
    re-tracing per machine.  Kernels are pure, so sharing is sound."""
    key = (
        tuple(int(d.id) for d in mesh.devices.flat),
        mesh.axis_names,
        int(max_passes),
    )
    steps = _STEP_CACHE.get(key)
    if steps is None:
        steps = {
            "accounts": sharded_create_accounts(mesh),
            "fast": sharded_create_transfers(mesh),
            # Deferred-dispatch twin (overflow as a fresh output): the
            # commit-pipeline lane under TB_SHARDS dispatches this one.
            "fast_probed": sharded_create_transfers(mesh, probed=True),
            "full": sharded_create_transfers_full(mesh, max_passes),
            "full_waves": sharded_create_transfers_full(
                mesh, max_passes, use_waves=True
            ),
            "scrub": sharded_scrub_digest(mesh),
        }
        _STEP_CACHE[key] = steps
    return steps


# ---------------------------------------------------------------------------
# Online shard split (docs/reconfiguration.md)
# ---------------------------------------------------------------------------


def split_moved_mask(key_lo: np.ndarray, key_hi: np.ndarray,
                     old_shards: int) -> np.ndarray:
    """Boolean mask of canonical slots whose OWNER changes on an
    old_shards -> 2*old_shards split.  Owners are the low hash bits, so
    doubling adds exactly one bit: a live row moves iff
    ``mix64(key) & old_shards != 0`` (it lands on shard s + old_shards),
    and stays resident otherwise.  Empty slots (key == 0) never move —
    only the moved subset crosses the verified migration channel
    (vsr/statesync.ship_chunk / verify_chunk); the stayed subset never
    leaves its device."""
    from ..ops.scrub import mix64_np

    assert old_shards >= 1 and old_shards & (old_shards - 1) == 0
    lo = np.asarray(key_lo, dtype=np.uint64)
    hi = np.asarray(key_hi, dtype=np.uint64)
    live = (lo | hi) != 0
    owners = mix64_np(lo, hi)
    return live & ((owners & np.uint64(old_shards)) != 0)
