"""Differential tests: the fully-vectorized transfer kernel vs the scalar
oracle (testing/model.py) on mixed two-phase workloads — the round-2
centerpiece (VERDICT.md next-round #2/#3).

Strategy mirrors the reference's workload/auditor ring (SURVEY.md §4): seeded
random batches mixing plain / pending / post / void / duplicates / expiry,
executed through the full TpuStateMachine dispatcher (so kernel routing flags
are exercised) and compared code-for-code and balance-for-balance."""

import numpy as np
import pytest

from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.testing import model as M

CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=11,
)


def make_pair(n_accounts=16, lanes=256, history=(), limits=()):
    dev = TpuStateMachine(CFG, batch_lanes=lanes)
    ref = M.ReferenceStateMachine()
    rows = []
    for i in range(n_accounts):
        flags = 0
        if i in history:
            flags |= types.AccountFlags.HISTORY
        if i in limits:
            flags |= types.AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
        rows.append(types.account(id=i + 1, ledger=1, code=10, flags=flags))
    accounts = types.accounts_array(rows)
    got = dev.create_accounts(accounts, wall_clock_ns=1)
    want = ref.create_accounts([M.account_from_row(r) for r in accounts], 1)
    assert got == want
    return dev, ref


def run_batch(dev, ref, batch):
    got = dev.create_transfers(batch)
    want = ref.create_transfers([M.transfer_from_row(r) for r in batch])
    assert got == want, f"codes diverge: {got[:8]} vs {want[:8]}"
    assert dev.balances_snapshot() == ref.balances_snapshot()


def transfers_array(specs):
    return types.transfers_array([types.transfer(**s) for s in specs])


class TestTwoPhaseVectorized:
    def test_pending_then_post_separate_batches(self):
        dev, ref = make_pair()
        run_batch(dev, ref, transfers_array([
            dict(id=100 + i, debit_account_id=1 + i % 8,
                 credit_account_id=9 + i % 8, amount=10 + i, ledger=1, code=1,
                 flags=types.TransferFlags.PENDING)
            for i in range(32)
        ]))
        run_batch(dev, ref, transfers_array([
            dict(id=200 + i, pending_id=100 + i, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER
                 if i % 2 == 0 else types.TransferFlags.VOID_PENDING_TRANSFER)
            for i in range(32)
        ]))

    def test_pending_and_post_same_batch(self):
        """In-batch pending reference: depth-1 Jacobi resolution."""
        dev, ref = make_pair()
        specs = [
            dict(id=300 + i, debit_account_id=1 + i % 8,
                 credit_account_id=9 + i % 8, amount=50, ledger=1, code=1,
                 flags=types.TransferFlags.PENDING)
            for i in range(16)
        ] + [
            dict(id=400 + i, pending_id=300 + i, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER)
            for i in range(16)
        ]
        run_batch(dev, ref, transfers_array(specs))

    def test_double_post_same_batch(self):
        """Second post of the same pending gets already_posted (33)."""
        dev, ref = make_pair()
        run_batch(dev, ref, transfers_array([
            dict(id=500, debit_account_id=1, credit_account_id=2, amount=9,
                 ledger=1, code=1, flags=types.TransferFlags.PENDING),
        ]))
        run_batch(dev, ref, transfers_array([
            dict(id=501, pending_id=500, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
            dict(id=502, pending_id=500, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
            dict(id=503, pending_id=500, ledger=1, code=1,
                 flags=types.TransferFlags.VOID_PENDING_TRANSFER),
        ]))

    def test_partial_post_amount(self):
        dev, ref = make_pair()
        run_batch(dev, ref, transfers_array([
            dict(id=600, debit_account_id=1, credit_account_id=2, amount=100,
                 ledger=1, code=1, flags=types.TransferFlags.PENDING),
            dict(id=601, pending_id=600, amount=40, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
            # amount > pending -> exceeds_pending_transfer_amount
            dict(id=602, pending_id=600, amount=200, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
        ]))

    def test_void_with_different_amount_fails(self):
        dev, ref = make_pair()
        run_batch(dev, ref, transfers_array([
            dict(id=610, debit_account_id=3, credit_account_id=4, amount=100,
                 ledger=1, code=1, flags=types.TransferFlags.PENDING),
            dict(id=611, pending_id=610, amount=40, ledger=1, code=1,
                 flags=types.TransferFlags.VOID_PENDING_TRANSFER),
            dict(id=612, pending_id=610, ledger=1, code=1,
                 flags=types.TransferFlags.VOID_PENDING_TRANSFER),
        ]))

    def test_expiry(self):
        dev, ref = make_pair()
        # Pending with 1s timeout at wall clock ~1ns; then advance the clock
        # past expiry and try to post.
        run_batch(dev, ref, transfers_array([
            dict(id=700, debit_account_id=1, credit_account_id=2, amount=5,
                 timeout=1, ledger=1, code=1, flags=types.TransferFlags.PENDING),
        ]))
        batch = transfers_array([
            dict(id=701, pending_id=700, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
        ])
        got = dev.create_transfers(batch, wall_clock_ns=3_000_000_000)
        want = ref.create_transfers(
            [M.transfer_from_row(r) for r in batch], 3_000_000_000
        )
        assert got == want
        assert want == [(0, int(types.CreateTransferResult.pending_transfer_expired))]
        assert dev.balances_snapshot() == ref.balances_snapshot()

    def test_post_nonexistent_and_not_pending(self):
        dev, ref = make_pair()
        run_batch(dev, ref, transfers_array([
            dict(id=800, debit_account_id=1, credit_account_id=2, amount=5,
                 ledger=1, code=1),  # plain transfer
            dict(id=801, pending_id=9999, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
            dict(id=802, pending_id=800, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
        ]))

    def test_history_accounts_vectorized(self):
        """History accounts no longer force the sequential path, and the
        recorded balances are exact per event."""
        dev, ref = make_pair(history=(0, 1))
        run_batch(dev, ref, transfers_array([
            dict(id=900 + i, debit_account_id=1, credit_account_id=3 + i % 4,
                 amount=7 + i, ledger=1, code=1)
            for i in range(8)
        ]))
        f = np.zeros(1, dtype=types.ACCOUNT_FILTER_DTYPE)[0]
        f["account_id_lo"] = 1
        f["limit"] = 100
        f["flags"] = int(
            types.AccountFilterFlags.DEBITS | types.AccountFilterFlags.CREDITS
        )
        got = [
            (
                int(r["timestamp"]),
                types.u128_join(r["debits_pending_lo"], r["debits_pending_hi"]),
                types.u128_join(r["debits_posted_lo"], r["debits_posted_hi"]),
                types.u128_join(r["credits_pending_lo"], r["credits_pending_hi"]),
                types.u128_join(r["credits_posted_lo"], r["credits_posted_hi"]),
            )
            for r in dev.get_account_history(f)
        ]
        want = ref.get_account_history(1, 0, 0, 100, int(f["flags"]))
        assert got == want
        assert dev.balances_snapshot() == ref.balances_snapshot()

    def test_limit_account_routes_to_seq(self):
        """Batches touching limit accounts still work (via the scan path)."""
        dev, ref = make_pair(limits=(0,))
        run_batch(dev, ref, transfers_array([
            dict(id=1000, debit_account_id=2, credit_account_id=1, amount=50,
                 ledger=1, code=1),
            # debits of account 1 capped by its credits_posted (50)
            dict(id=1001, debit_account_id=1, credit_account_id=3, amount=40,
                 ledger=1, code=1),
            dict(id=1002, debit_account_id=1, credit_account_id=3, amount=40,
                 ledger=1, code=1),  # would exceed -> exceeds_credits
        ]))

    def test_duplicate_post_ids(self):
        dev, ref = make_pair()
        run_batch(dev, ref, transfers_array([
            dict(id=1100, debit_account_id=1, credit_account_id=2, amount=30,
                 ledger=1, code=1, flags=types.TransferFlags.PENDING),
            dict(id=1101, pending_id=1100, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
            # exact duplicate of the post -> exists
            dict(id=1101, pending_id=1100, ledger=1, code=1,
                 flags=types.TransferFlags.POST_PENDING_TRANSFER),
            # same id, different flags -> exists_with_different_flags
            dict(id=1101, pending_id=1100, ledger=1, code=1,
                 flags=types.TransferFlags.VOID_PENDING_TRANSFER),
        ]))


class TestRandomizedDifferential:
    @pytest.mark.slow  # ~17s/seed; runs whole in the ci integration tier
    @pytest.mark.parametrize("seed", range(8))
    def test_random_two_phase_stream(self, seed):
        rng = np.random.default_rng(seed)
        dev, ref = make_pair(
            n_accounts=12,
            history=(0,) if seed % 3 == 0 else (),
            limits=(11,) if seed % 4 == 0 else (),
        )
        next_id = 2000
        live_pending: list = []
        for _batch in range(6):
            specs = []
            for _ in range(int(rng.integers(20, 60))):
                kind = rng.random()
                if kind < 0.45 or not live_pending:
                    dr = int(rng.integers(1, 13))
                    cr = dr % 12 + 1
                    flags = 0
                    if rng.random() < 0.5:
                        flags = types.TransferFlags.PENDING
                    specs.append(dict(
                        id=next_id, debit_account_id=dr, credit_account_id=cr,
                        amount=int(rng.integers(1, 100)), ledger=1, code=1,
                        timeout=int(rng.integers(0, 3)) if flags else 0,
                        flags=flags,
                    ))
                    if flags:
                        live_pending.append(next_id)
                    next_id += 1
                else:
                    pid = int(rng.choice(live_pending))
                    if rng.random() < 0.3:
                        live_pending.remove(pid)
                    flags = (
                        types.TransferFlags.POST_PENDING_TRANSFER
                        if rng.random() < 0.6
                        else types.TransferFlags.VOID_PENDING_TRANSFER
                    )
                    amount = 0 if rng.random() < 0.7 else int(rng.integers(1, 120))
                    specs.append(dict(
                        id=next_id, pending_id=pid, amount=amount,
                        ledger=1, code=1, flags=flags,
                    ))
                    next_id += 1
            # Occasionally duplicate a spec inside the batch.
            if len(specs) > 4 and rng.random() < 0.6:
                specs.insert(
                    int(rng.integers(1, len(specs))),
                    dict(specs[int(rng.integers(0, len(specs) - 1))]),
                )
            run_batch(dev, ref, transfers_array(specs))

    @pytest.mark.parametrize("seed", range(4))
    def test_random_same_batch_pending_post(self, seed):
        """Pending + its post/void in the SAME batch, heavy interleave."""
        rng = np.random.default_rng(100 + seed)
        dev, ref = make_pair(n_accounts=8)
        next_id = 5000
        for _batch in range(4):
            specs = []
            pending_ids = []
            for _ in range(int(rng.integers(10, 30))):
                dr = int(rng.integers(1, 9))
                cr = dr % 8 + 1
                specs.append(dict(
                    id=next_id, debit_account_id=dr, credit_account_id=cr,
                    amount=int(rng.integers(1, 50)), ledger=1, code=1,
                    flags=types.TransferFlags.PENDING,
                ))
                pending_ids.append(next_id)
                next_id += 1
                if rng.random() < 0.8:
                    pid = int(rng.choice(pending_ids))
                    flags = (
                        types.TransferFlags.POST_PENDING_TRANSFER
                        if rng.random() < 0.5
                        else types.TransferFlags.VOID_PENDING_TRANSFER
                    )
                    specs.append(dict(
                        id=next_id, pending_id=pid, ledger=1, code=1,
                        flags=flags,
                    ))
                    next_id += 1
            rng.shuffle(specs[len(specs) // 2:])  # scramble the tail order
            run_batch(dev, ref, transfers_array(specs))


class TestGrowth:
    @pytest.mark.slow  # ~15s; runs whole in the ci integration tier
    def test_table_growth_under_insert_pressure(self):
        """4x the initial capacity inserts complete with zero spurious codes
        (VERDICT.md next-round #5)."""
        cfg = LedgerConfig(
            accounts_capacity_log2=6, transfers_capacity_log2=7,
            posted_capacity_log2=6,
        )
        dev = TpuStateMachine(cfg, batch_lanes=256)
        ref = M.ReferenceStateMachine()
        n_acc = 24
        accounts = types.accounts_array(
            [types.account(id=i + 1, ledger=1, code=10) for i in range(n_acc)]
        )
        assert dev.create_accounts(accounts, 1) == ref.create_accounts(
            [M.account_from_row(r) for r in accounts], 1
        )
        total = (1 << 7) * 4  # 4x initial transfers capacity
        next_id = 10_000
        done = 0
        while done < total:
            n = min(200, total - done)
            batch = transfers_array([
                dict(id=next_id + i, debit_account_id=1 + (next_id + i) % n_acc,
                     credit_account_id=1 + (next_id + i + 7) % n_acc,
                     amount=1 + i, ledger=1, code=1)
                for i in range(n)
            ])
            run_batch(dev, ref, batch)
            next_id += n
            done += n
        assert not bool(np.asarray(dev.ledger.transfers.probe_overflow))


# ---------------------------------------------------------------------------
# The Jacobi loop (one gated lax.scan on every backend) runs the passes its
# batch needs: the count of passes run, the flags, and tables a routed batch
# leaves as it found them, batch by batch.
# ---------------------------------------------------------------------------

_LOOP_LANES, _LOOP_ACCOUNTS = 32, 12
_LIMITED = range(6)  # accounts 1..6: debits_must_not_exceed_credits
_PENDING = int(types.TransferFlags.PENDING)
_POST = int(types.TransferFlags.POST_PENDING_TRANSFER)
_VOID = int(types.TransferFlags.VOID_PENDING_TRANSFER)


def _t(id, dr=0, cr=0, amount=0, flags=0, pending_id=0):
    funded = 0 if flags & (_POST | _VOID) else 1
    return types.transfer(
        id=id, debit_account_id=dr, credit_account_id=cr, amount=amount,
        ledger=funded, code=10 * funded, flags=flags, pending_id=pending_id,
    )


# Accounts 7..12 are unrestricted.  A limited account holds nothing, so in
# `_CHAIN` transfer k is accepted only once transfer k-1 has been: pass k
# settles lane k, and one more pass observes the fixpoint.
_FUND = [_t(1, dr=7, cr=1, amount=10)]
_CHAIN = [_t(10 + k, dr=1 + k, cr=2 + k, amount=10) for k in range(5)]
_PENDINGS = [
    _t(100 + k, dr=7 + k % 3, cr=10 + k % 3, amount=5 + k, flags=_PENDING)
    for k in range(12)
]

# case -> (use_waves, max_passes, [(batch, passes the loop runs, flags)])
_LOOP_CASES = {
    "table_postvoid_waves_bound_1": (True, 8, [
        (_PENDINGS, 1, 0),
        ([_t(200 + k, flags=_VOID if k % 4 == 3 else _POST,
             pending_id=100 + k) for k in range(12)], 1, 0),
    ]),
    "plain_uncontended_stable_at_2": (False, 8, [
        ([_t(300 + k, dr=7 + k % 3, cr=10 + k % 3, amount=1 + k)
          for k in range(9)], 2, 0),
    ]),
    "pending_and_post_in_one_batch_3": (True, 8, [
        (_PENDINGS[:6] + [_t(400 + k, flags=_POST, pending_id=100 + k)
                          for k in range(6)], 3, 0),
    ]),
    "limit_chain_past_the_old_head_6": (False, 8, [
        (_FUND, 2, 0), (_CHAIN, 6, 0),
    ]),
    "max_passes_too_small_routes_seq": (False, 3, [
        (_FUND, 2, 0), (_CHAIN, 3, 1),  # FLAG_SEQ: nothing applied
    ]),
}


def _loop_ledger():
    from tigerbeetle_tpu.ops import staging
    from tigerbeetle_tpu.ops import state_machine as sm

    acc = types.accounts_array([
        types.account(
            id=i + 1, ledger=1, code=10,
            flags=(types.AccountFlags.DEBITS_MUST_NOT_EXCEED_CREDITS
                   if i in _LIMITED else 0),
        )
        for i in range(_LOOP_ACCOUNTS)
    ])
    led, codes = sm.create_accounts(
        sm.make_ledger(1 << 6, 1 << 8, 1 << 6),
        *staging.stage_batch(acc, _LOOP_LANES, _LOOP_ACCOUNTS),
    )
    assert not np.asarray(codes)[:_LOOP_ACCOUNTS].any()
    return led


def _run_loop_case(monkeypatch, use_waves, max_passes, batches):
    """The case's batches through ONE jitted create_transfers_full_impl;
    ``passes`` comes out of the same trace (the impl returns it only with
    waves on)."""
    import jax
    import jax.numpy as jnp

    from tigerbeetle_tpu.ops import transfer_full as tf

    core, seen = tf._kernel_core, {}

    def spy(*args, **kwargs):
        plan = core(*args, **kwargs)
        seen["passes"] = plan.passes
        return plan

    monkeypatch.setattr(tf, "_kernel_core", spy)

    @jax.jit
    def fn(led, soa, count, ts):
        out = tf.create_transfers_full_impl(
            led, soa, count, ts, max_passes=max_passes, use_waves=use_waves,
        )
        return out[0], out[1], out[2], seen["passes"]

    led, ts, got = _loop_ledger(), 1_000, []
    for rows, _, _ in batches:
        padded = np.zeros(_LOOP_LANES, dtype=types.TRANSFER_DTYPE)
        padded[: len(rows)] = types.transfers_array(rows)
        soa = {k: jnp.asarray(v) for k, v in types.to_soa(padded).items()}
        ts += _LOOP_LANES
        led, codes, kflags, passes = fn(
            led, soa, jnp.uint64(len(rows)), jnp.uint64(ts)
        )
        got.append({
            "codes": np.asarray(codes), "flags": int(kflags),
            "passes": int(passes),
            **{
                f"{name}.{col}": np.asarray(v)
                for name, t in (("accounts", led.accounts),
                                ("transfers", led.transfers),
                                ("posted", led.posted))
                for col, v in {"key_lo": t.key_lo, "count": t.count,
                               **t.cols}.items()
            },
        })
    return got


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _eqns(j)


def _named(eqns, *names):
    return [e for e in eqns if e.primitive.name in names]


class TestJacobiLoopForms:
    @pytest.mark.parametrize("case", list(_LOOP_CASES))
    def test_loop_runs_the_passes_the_batch_needs(self, case, monkeypatch):
        use_waves, max_passes, batches = _LOOP_CASES[case]
        got = _run_loop_case(monkeypatch, use_waves, max_passes, batches)
        for (rows, passes, flags), g in zip(batches, got):
            assert (g["passes"], g["flags"]) == (passes, flags)
            if not flags:
                assert not g["codes"][: len(rows)].any()
        if got[-1]["flags"]:
            # Routed: the batch left every table as it found it.
            for k in (k for k in got[-1] if "." in k):
                np.testing.assert_array_equal(
                    got[-2][k], got[-1][k], err_msg=k
                )

    @pytest.mark.parametrize("max_passes", [3, 8])
    def test_loop_is_one_scan_of_gated_passes(self, max_passes):
        """The jaxpr has ONE pass loop: a scan of length max_passes whose
        body is the gate and a cond (skip | pass).  A pass is known by its
        leg sorts; the program holds each of them twice (the loop's pass
        and the aux pass)."""
        import jax
        import jax.numpy as jnp

        from tigerbeetle_tpu.ops import transfer_full as tf

        padded = np.zeros(_LOOP_LANES, dtype=types.TRANSFER_DTYPE)
        soa = {k: jnp.asarray(v) for k, v in types.to_soa(padded).items()}
        u64 = jnp.uint64(0)
        eqns = list(_eqns(jax.make_jaxpr(
            lambda led: tf.create_transfers_full_impl(
                led, soa, u64, u64, max_passes=max_passes,
            )
        )(_loop_ledger()).jaxpr))
        (scan,) = [
            e for e in _named(eqns, "scan", "while")
            if _named(_eqns(e.params.get("jaxpr", e.params.get(
                "body_jaxpr")).jaxpr), "sort")
        ]
        assert scan.primitive.name == "scan"
        assert scan.params["length"] == max_passes
        body = scan.params["jaxpr"].jaxpr.eqns
        (cond,) = _named(body, "cond")
        assert len(cond.params["branches"]) == 2
        assert not _named(body, "sort", "scan", "while")

        def kinds(es):
            return sorted(
                (e.params["num_keys"], tuple(str(v.aval) for v in e.invars))
                for e in _named(es, "sort")
            )

        in_loop = kinds(_eqns(scan.params["jaxpr"].jaxpr))
        assert in_loop
        assert [k for k in kinds(eqns) if k in in_loop] == sorted(2 * in_loop)
