"""TB_SANITIZE runtime sanitizer (tigerbeetle_tpu/sanitize.py): every
check proven to (a) stay quiet on a clean run and (b) catch one
intentionally-injected violation of its class.

The machine-level cells build a real TpuStateMachine with TB_SANITIZE=1
(the flag is read at construction) and drive the grouped commit path the
sanitizer instruments: the post-warmup recompile tripwire; the registry
cells hold `sanitize._count`'s rule (a `sanitize.*` series only while
TB_SANITIZE and the registry are both on) through that tripwire.  (The staging
pool's poisoning and the cached zero template's guard went with the pool
and the template in PR 46; tests/test_staging.py holds what replaced
them: fresh operands for every request, one put a staging.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tigerbeetle_tpu import sanitize as san
from tigerbeetle_tpu import types
from tigerbeetle_tpu.config import LedgerConfig
from tigerbeetle_tpu.machine import TpuStateMachine
from tigerbeetle_tpu.obs.metrics import registry

LANES = 64
CFG = LedgerConfig(
    accounts_capacity_log2=10, transfers_capacity_log2=12,
    posted_capacity_log2=10,
)
N_ACCOUNTS = 16


@pytest.fixture(autouse=True)
def _fresh_counts():
    san._reset_counts()
    yield
    san._reset_counts()


def make_sanitized_machine(monkeypatch, **kwargs) -> TpuStateMachine:
    monkeypatch.setenv("TB_SANITIZE", "1")
    m = TpuStateMachine(CFG, batch_lanes=LANES, **kwargs)
    assert m._sanitize
    accs = types.accounts_array([
        types.account(id=i + 1, ledger=1, code=10)
        for i in range(N_ACCOUNTS)
    ])
    assert m.create_accounts(accs, wall_clock_ns=1000) == []
    return m


def transfer_batch(first_id: int, n: int) -> np.ndarray:
    return types.transfers_array([
        types.transfer(
            id=first_id + i,
            debit_account_id=1 + i % (N_ACCOUNTS - 1),
            credit_account_id=2 + i % (N_ACCOUNTS - 2),
            amount=1 + i, ledger=1, code=1,
        )
        for i in range(n)
    ])


def commit_group(m: TpuStateMachine, first_id: int, k: int = 2,
                 n: int = 8):
    batches = [transfer_batch(first_id + 100 * j, n) for j in range(k)]
    tss = [m.prepare("create_transfers", n, 0) for _ in batches]
    res = m.commit_group_fast(batches, tss)
    assert res is not None, "run was not groupable"
    assert all(r == [] for r in res), res
    return res


# -- recompile tripwire ------------------------------------------------------

def test_compile_tripwire_fires_on_forced_recompile():
    from tigerbeetle_tpu import jaxenv

    assert jaxenv.instrument_compiles(), "compile listener unavailable"

    @jax.jit
    def _fresh(x):
        return x * 3 + 1

    with pytest.raises(san.SanitizeError, match="recompile tripwire"):
        with san.compile_tripwire("test region", raise_on_trip=True):
            _fresh(jnp.ones((41,), jnp.uint32)).block_until_ready()
    assert san.counts()["recompiles"] >= 1


def test_compile_tripwire_quiet_on_warm_program():
    @jax.jit
    def _warmed(x):
        return x + 2

    _warmed(jnp.ones((23,), jnp.uint32)).block_until_ready()  # compile now
    with san.compile_tripwire("warm region", raise_on_trip=True) as report:
        _warmed(jnp.ones((23,), jnp.uint32)).block_until_ready()
    assert report.compiles == 0


def _trip_once():
    """One compile inside a tripwire that only counts: ``sanitize._count``
    is driven through the check that survives."""
    @jax.jit
    def _fresh(x):
        return x * 5 + 7

    with san.compile_tripwire("gating test", raise_on_trip=False, quiet=True):
        _fresh(jnp.ones((37,), jnp.uint32)).block_until_ready()


def test_tripwire_counter_lands_in_registry(monkeypatch):
    monkeypatch.setenv("TB_SANITIZE", "1")
    with registry.enabled_scope():
        _trip_once()
        snap = registry.snapshot()["counters"]
    assert snap["sanitize.recompiles"] >= 1
    assert snap["sanitize.recompiles"] == san.counts()["recompiles"]
    assert not registry.enabled


def test_registry_series_gated_on_sanitize_env(monkeypatch):
    """A compile_tripwire armed by a plain bench run (TB_SANITIZE unset)
    must not make METRICS.json claim the sanitizer ran: only the
    module-local count records."""
    monkeypatch.delenv("TB_SANITIZE", raising=False)
    with registry.enabled_scope():
        _trip_once()
        assert "sanitize.recompiles" not in registry.snapshot()["counters"]
    assert san.counts()["recompiles"] >= 1


def test_serving_recompile_check_warns_and_rebaselines(monkeypatch, capsys):
    m = make_sanitized_machine(monkeypatch)
    m.warmup()
    assert m._sanitize_compile_base is not None
    from tigerbeetle_tpu import jaxenv

    # Injected violation: pretend warmup's baseline predates compiles.
    m._sanitize_compile_base = jaxenv.compile_count() - 3
    m._sanitize_recompile_check("unit region")
    assert san.counts()["recompiles"] == 3
    assert "SANITIZE: 3 XLA compile(s)" in capsys.readouterr().err
    # Re-baselined: a second check is quiet.
    m._sanitize_recompile_check("unit region")
    assert san.counts()["recompiles"] == 3


def test_serving_recompile_check_strict_raises(monkeypatch):
    m = make_sanitized_machine(monkeypatch)
    m.warmup()
    monkeypatch.setenv("TB_SANITIZE_STRICT", "1")
    from tigerbeetle_tpu import jaxenv

    m._sanitize_compile_base = jaxenv.compile_count() - 1
    with pytest.raises(san.SanitizeError, match="recompile tripwire"):
        m._sanitize_recompile_check("strict region")


def test_read_path_first_use_compile_not_attributed_to_serving(monkeypatch):
    """A first lookup after warmup jit-compiles its READ kernel; the
    serving tripwire must absorb it (not strict-raise out of the next
    commit, not pollute sanitize.recompiles)."""
    m = make_sanitized_machine(monkeypatch)
    m.warmup()
    monkeypatch.setenv("TB_SANITIZE_STRICT", "1")
    m.lookup_accounts([1, 2])       # first-use compile of the read path
    before = san.counts().get("recompiles", 0)
    ts = m.prepare("create_transfers", 4, 0)
    assert m.commit_batch("create_transfers",
                          transfer_batch(70_000, 4), ts) == []
    assert san.counts().get("recompiles", 0) == before


def test_steady_serving_has_zero_recompiles(monkeypatch):
    """The acceptance shape: after warmup + one warm group, further
    same-shape grouped commits compile NOTHING (strict tripwire armed)."""
    m = make_sanitized_machine(monkeypatch)
    m.warmup()
    commit_group(m, 40_000, n=8)     # warm group: first-use index/scan jits
    m._sanitize_arm_tripwire()       # re-baseline at the steady state
    monkeypatch.setenv("TB_SANITIZE_STRICT", "1")
    before = san.counts().get("recompiles", 0)
    commit_group(m, 50_000, n=8)
    commit_group(m, 60_000, n=8)
    assert san.counts().get("recompiles", 0) == before


# -- registry leak guard -----------------------------------------------------

def test_registry_guard_trips_on_leaked_enable():
    registry.enable()
    with pytest.raises(san.SanitizeError, match="registry leak"):
        san.assert_registry_disabled("test scope")
    # The guard disarmed the leak so it cannot cascade.
    assert not registry.enabled
    assert san.counts()["registry_leaks"] == 1


def test_registry_guard_quiet_when_disabled():
    assert not registry.enabled
    san.assert_registry_disabled("test scope")
    assert "registry_leaks" not in san.counts()


def test_enabled_scope_always_disables():
    with pytest.raises(RuntimeError, match="boom"):
        with registry.enabled_scope():
            assert registry.enabled
            raise RuntimeError("boom")
    assert not registry.enabled
    assert registry.snapshot()["counters"] == {}
