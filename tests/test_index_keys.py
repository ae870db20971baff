"""The secondary index's level-0 runs are built from the key columns a
commit program returns (both sides' account ids and the stored timestamp),
not from a second probe of the transfers table (ops/index.py).

Every commit route, beside a `TransferIndex` fed by the probe helper
(`index.probe_keys`) from the same ledger: the levels must be equal element
for element, and `get_account_transfers` must answer as the scalar oracle
does.  The benchmark's `correct` reads no index, so these are the guard."""

import jax.numpy as jnp
import numpy as np
import pytest

from test_pipeline import LANES, N_ACCOUNTS, batch, make_machine, make_model
from tigerbeetle_tpu import types
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry
from tigerbeetle_tpu.ops import index
from tigerbeetle_tpu.ops import state_machine as sm
from tigerbeetle_tpu.ops import transfer_full as tf
from tigerbeetle_tpu.testing import model as M

GROUP_K = TpuStateMachine.GROUP_K
PENDING = int(types.TransferFlags.PENDING)
POST = int(types.TransferFlags.POST_PENDING_TRANSFER)
VOID = int(types.TransferFlags.VOID_PENDING_TRANSFER)


class Pair:
    """A machine beside the oracle and a shadow index that only ever sees
    what the probe helper reads back from the machine's ledger."""

    def __init__(self, **kwargs):
        self.m = make_machine(**kwargs)
        self.ref = make_model()
        self.shadow = index.TransferIndex(base=LANES)

    def _committed(self, b, res):
        """One committed batch: the oracle agrees, the shadow appends."""
        assert res == self.ref.create_transfers(
            [M.transfer_from_row(r) for r in b])
        ok = np.zeros(LANES, dtype=bool)
        ok[: len(b)] = True
        ok[[i for i, _ in res]] = False
        padded = np.zeros(LANES, dtype=types.TRANSFER_DTYPE)
        padded[: len(b)] = b
        id_lo, id_hi = jnp.asarray(padded["id_lo"]), jnp.asarray(padded["id_hi"])
        keys, written = index.probe_keys(
            self.m.ledger, id_lo, id_hi, jnp.asarray(ok))
        assert np.array_equal(np.asarray(written), ok)
        self.shadow.append_batch(keys, id_lo, id_hi, written)

    def lone(self, b):
        """One fast request on the lane: `create_transfers_fast_probed`."""
        handle = self.m.commit_fast_deferred(
            b, self.m.prepare("create_transfers", len(b), 0))
        assert handle is not None
        (res,) = handle.resolve()
        self._committed(b, res)
        return res

    def grouped(self, run):
        """One grouped dispatch: the loop of `_group_fast_dispatch_impl`."""
        tss = [self.m.prepare("create_transfers", len(b), 0) for b in run]
        got = self.m.commit_group_fast(run, tss)
        assert got is not None
        for b, res in zip(run, got):
            self._committed(b, res)

    def blocking(self, b):
        """`commit_batch`: the general kernel, or what it routes to."""
        res = self.m.create_transfers(b, wall_clock_ns=0)
        self._committed(b, res)
        return res

    def check(self):
        """The levels equal the shadow's; the queries answer as the oracle."""
        got, want = self.m.index, self.shadow
        assert not got.stale
        assert got.occupied == want.occupied
        for side in ("dr_levels", "cr_levels"):
            for k, (a, b) in enumerate(
                    zip(getattr(got, side), getattr(want, side))):
                for name in index.COLS:
                    assert np.array_equal(
                        np.asarray(a[name]), np.asarray(b[name])
                    ), f"{side}[{k}][{name}]"
        for account in range(1, N_ACCOUNTS + 1):
            for flags in (1, 2, 3, 3 | 4):  # debits, credits, both, reversed
                f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
                f["account_id_lo"], f["limit"], f["flags"] = (
                    account, 8000, flags)
                assert [int(r["id_lo"])
                        for r in self.m.get_account_transfers(f)] == [
                    t.id for t in self.ref.get_account_transfers(
                        account, 0, 0, 8000, flags)
                ], (account, flags)


def _rejecting(first_id, n):
    """A plain batch with one lane the kernels reject (no such account)."""
    b = batch(first_id, n)
    b["debit_account_id_lo"][n // 2] = 999
    return b


def _resolving(first_id, pending_first, n_post, n_void, missing=1):
    """Posts, then voids, of the pendings from `pending_first` on, sent as
    the two-phase cell sends them (account ids 0: the index's keys must be
    the PENDING's accounts), then `missing` posts of pendings that do not
    exist (rejected)."""
    rows = [
        types.transfer(
            id=first_id + i, pending_id=pending_first + i, ledger=1, code=10,
            flags=POST if i < n_post else VOID)
        for i in range(n_post + n_void)
    ] + [
        types.transfer(id=first_id + 900 + i, pending_id=777_000 + i,
                       ledger=1, code=10, flags=POST)
        for i in range(missing)
    ]
    b = types.transfers_array(rows)
    assert not b["debit_account_id_lo"].any()
    assert not b["credit_account_id_lo"].any()
    return b


def test_a_lone_fast_request_hands_over_its_keys():
    p = Pair()
    p.lone(batch(1000, 20))
    p.check()
    assert len(p.lone(_rejecting(2000, 31))) == 1   # a rejected lane
    p.check()
    assert len(p.lone(batch(1000, 20))) == 20       # every lane `exists`
    p.lone(batch(3000, LANES))                      # a full batch
    p.check()


@pytest.mark.parametrize("k", [3, GROUP_K])
def test_a_grouped_loop_hands_over_every_trips_keys(k):
    p = Pair()
    run = [batch(1000 * (j + 1), 9 + j % 7) for j in range(k)]
    run[1] = _rejecting(2000, 12)
    run[-1] = batch(1000, 9)  # the first batch again: every lane `exists`
    p.grouped(run)
    p.check()
    p.lone(batch(90_000, 5))  # a run behind the group's carries
    p.check()


@pytest.mark.parametrize("use_waves", [True, False])
def test_the_general_kernel_hands_over_the_pendings_accounts(use_waves):
    p = Pair()
    p.m.waves_enabled = use_waves
    p.blocking(batch(4000, 24, flags=PENDING))
    p.check()
    res = p.blocking(_resolving(5000, 4000, n_post=10, n_void=8))
    assert len(res) == 1  # the post of a pending that does not exist
    p.check()
    # A posted, a voided and a rejected transfer, by both of their accounts.
    posted, voided = p.ref.transfers[5000], p.ref.transfers[5010]
    assert posted.debit_account_id == 1 and posted.credit_account_id == 4
    assert voided.debit_account_id == 11 and voided.credit_account_id == 14
    assert 5900 not in p.ref.transfers
    # Resolved twice: every lane is rejected, the run is all sentinels.
    assert len(p.blocking(_resolving(6000, 4000, 10, 8, missing=0))) == 18
    p.check()


def test_a_retried_general_attempt_appends_once(monkeypatch):
    """An attempt that comes back with a flag applied nothing: its keys are
    never appended; the retry's are, once."""
    p = Pair()
    p.blocking(batch(4000, 24, flags=PENDING))
    real, calls = tf.create_transfers_full, []

    def flagged_once(ledger, soa, *args, **kwargs):
        calls.append(1)
        if len(calls) > 1:
            return real(ledger, soa, *args, **kwargs)
        junk = {name: jnp.full((LANES,), 7, jnp.uint64)
                for name in sm.INDEX_KEY_COLS}
        out = (ledger, jnp.zeros((LANES,), jnp.uint32),
               jnp.uint32(tf.FLAG_GROW_TRANSFERS))
        if kwargs["use_waves"]:
            out += (jnp.zeros((11,), jnp.int32),)
        return out + (junk,)

    monkeypatch.setattr(tf, "create_transfers_full", flagged_once)
    with registry.enabled_scope():
        p.blocking(_resolving(5000, 4000, n_post=10, n_void=8))
        assert registry.counter("ops.general.retries").value == 1
        assert registry.counter("index.runs.keyed").value == 1
        assert registry.counter("index.runs.probed").value == 0
    assert len(calls) == 2
    p.check()


def _order_dependent(first_id):
    """A linked chain through a balancing transfer, then a lane that fails:
    the general kernel sends it to the sequential path (FLAG_SEQ)."""
    linked = int(types.TransferFlags.LINKED)
    return types.transfers_array([
        types.transfer(id=first_id, debit_account_id=1, credit_account_id=2,
                       amount=2, ledger=1, code=10, flags=linked),
        types.transfer(id=first_id + 1, debit_account_id=1,
                       credit_account_id=2, amount=0, ledger=1, code=10,
                       flags=linked
                       | int(types.TransferFlags.BALANCING_DEBIT)),
        types.transfer(id=first_id + 2, debit_account_id=1,
                       credit_account_id=99, amount=1, ledger=1, code=10),
        types.transfer(id=first_id + 3, debit_account_id=5,
                       credit_account_id=6, amount=1, ledger=1, code=10),
    ])


@pytest.mark.parametrize("route", ["general_routes_out", "force_sequential",
                                   "unprobed_fast"])
def test_a_route_without_kernel_keys_probes_them(route):
    """The sequential path and the unprobed fast kernel return no keys: the
    helper reads them back, and the levels are what the keyed routes build."""
    p = Pair(force_sequential=route == "force_sequential")
    with registry.enabled_scope():
        if route == "general_routes_out":
            p.blocking(batch(1000, 20, flags=PENDING))  # the fast kernel
            p.blocking(_resolving(3000, 1000, 5, 5))    # general: keyed
            p.blocking(_order_dependent(2000))          # ... and out of it
            probed, keyed = 2, 1
        else:
            p.blocking(batch(1000, 20))   # `create_transfers_fast` or scan
            p.blocking(_rejecting(2000, 8))
            p.blocking(batch(1000, 20))
            probed, keyed = 3, 0
        sequential = registry.counter("ops.sequential_batches").value
        assert sequential == {"general_routes_out": 1, "force_sequential": 3,
                              "unprobed_fast": 0}[route]
        assert registry.counter("index.runs.probed").value == probed
        assert registry.counter("index.runs.keyed").value == keyed
    p.check()
