"""donation fixtures: use-after-donate, plus clean and suppressed
instances."""

import jax


def _commit_impl(ledger, batch):
    return ledger + batch, batch * 2


_commit = jax.jit(_commit_impl, donate_argnames=("ledger",))


def use_after_donate(ledger, batch):
    new_ledger, codes = _commit(ledger, batch)
    total = ledger.sum()  # BAD: ledger was donated above
    return new_ledger, codes, total


def clean_rebind(ledger, batch):
    ledger, codes = _commit(ledger, batch)  # rebinds: no finding
    return ledger, codes


def suppressed_use_after_donate(ledger, batch):
    new_ledger, codes = _commit(ledger, batch)
    total = ledger.sum()  # tblint: ignore[donation] freshness proven by caller
    return new_ledger, codes, total
