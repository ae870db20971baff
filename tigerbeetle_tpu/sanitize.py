"""TB_SANITIZE=1 runtime sanitizer: make the tbsan bug classes fail LOUD.

The static suite (tools/tblint rules donation / size-class / lane-race /
shard-rep) proves discipline over the source; this module is its runtime
twin for the cases static analysis cannot close — test/CI-only (the
checks cost real work), never armed in production serving.  Two checks, in
the VOPR spirit of "assert the invariant, then search for the violation"
(a third, donation poisoning of the pooled staging sets and a guard on the
cached zero-count template, went with the pool and the template in PR 46:
a request's operands are fresh host arrays that no program donates):

- RECOMPILE TRIPWIRE — ``compile_tripwire`` diffs
  ``jaxenv.compile_count()`` around a region that must not compile
  (serving after warmup, a bench timed loop).  The PR 10 merkle
  recompile bug was found after the fact in bench p99; the tripwire
  makes the same class fail at the region, with the count.

- REGISTRY LEAK GUARD — ``assert_registry_disabled`` catches a test or
  tool that enabled the process-global obs registry and leaked it on
  (the PR 10 metrics-registry leak class): every later test then
  silently pays recording costs and inherits foreign series.

Every trip increments both a module-local counter (``counts()`` — works
with the registry off) and, when the registry is enabled, a
``sanitize.*`` series so CI smokes can assert them in METRICS.json.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

__all__ = [
    "SanitizeError", "enabled", "strict",
    "compile_tripwire", "assert_registry_disabled", "counts",
]


class SanitizeError(AssertionError):
    """A sanitizer invariant was violated (loud by design)."""


def enabled() -> bool:
    """TB_SANITIZE=1 arms the runtime checks (test/CI-only)."""
    return os.environ.get("TB_SANITIZE", "") not in ("", "0")


def strict() -> bool:
    """TB_SANITIZE_STRICT=1 escalates tripwire warnings to raises."""
    return os.environ.get("TB_SANITIZE_STRICT", "") not in ("", "0")


# Module-local trip counters: assertable without the obs registry.
_COUNTS: Dict[str, int] = {}


def counts() -> Dict[str, int]:
    """Snapshot of the sanitizer's own trip counters."""
    return dict(_COUNTS)


def _count(name: str, n: int = 1) -> None:
    _COUNTS[name] = _COUNTS.get(name, 0) + n
    from .obs.metrics import registry

    # The registry series keep their documented TB_SANITIZE=1 semantics:
    # a plain bench run that arms a compile_tripwire must not make an
    # operator's METRICS.json claim the sanitizer ran.  The module-local
    # count above still records for such callers.
    if registry.enabled and enabled():
        registry.counter(f"sanitize.{name}").inc(n)


def _reset_counts() -> None:
    """Tests only."""
    _COUNTS.clear()


# -- recompile tripwire ------------------------------------------------------

def _warn_unarmed(where: str) -> None:
    """The jax.monitoring listener failed to install (private-API drift):
    compile_count() is frozen and every tripwire delta is vacuously 0.
    Say so loudly ONCE — a silent always-green tripwire is worse than
    none."""
    if _COUNTS.get("tripwire_unarmed"):
        _COUNTS["tripwire_unarmed"] += 1
        return
    _count("tripwire_unarmed")
    import sys

    print(
        f"# SANITIZE: compile listener unavailable (jax.monitoring import "
        f"failed) — the recompile tripwire for {where!r} cannot observe "
        "compiles; its zero count is VACUOUS",
        file=sys.stderr,
    )

class TripwireReport:
    """Result of one compile_tripwire region.  ``armed`` is False when
    the jax.monitoring listener could not install — the count is then
    VACUOUS (always 0), not proof of a compile-free region."""

    __slots__ = ("label", "compiles", "armed")

    def __init__(self, label: str) -> None:
        self.label = label
        self.compiles = 0
        self.armed = False


class compile_tripwire:
    """Context manager asserting ZERO XLA compiles inside the region.

    Requires jaxenv.instrument_compiles() (installed on entry).  On a
    nonzero delta: counts ``sanitize.recompiles``, warns loudly, and —
    when ``raise_on_trip`` (default: TB_SANITIZE_STRICT) — raises
    SanitizeError.  The report object is yielded so callers can record
    the count either way; ``quiet=True`` suppresses this module's stderr
    warning for callers that print their own context-specific one."""

    def __init__(self, label: str,
                 raise_on_trip: Optional[bool] = None,
                 quiet: bool = False) -> None:
        self.report = TripwireReport(label)
        self._raise = raise_on_trip
        self._quiet = quiet
        self._base = 0

    def __enter__(self) -> TripwireReport:
        from . import jaxenv

        self.report.armed = jaxenv.instrument_compiles()
        if not self.report.armed:
            _warn_unarmed(self.report.label)
        self._base = jaxenv.compile_count()
        return self.report

    def __exit__(self, exc_type, exc, tb) -> None:
        from . import jaxenv

        delta = jaxenv.compile_count() - self._base
        self.report.compiles = delta
        if delta and exc_type is None:
            _count("recompiles", delta)
            if not self._quiet:
                import sys

                print(
                    f"# SANITIZE: {delta} XLA compile(s) inside "
                    f"{self.report.label!r} — a region that must not "
                    "compile (warmup bled into the clock / an input shape "
                    "is not size-class stable)",
                    file=sys.stderr,
                )
            if self._raise if self._raise is not None else strict():
                raise SanitizeError(
                    f"recompile tripwire: {delta} compile(s) inside "
                    f"{self.report.label!r}"
                )


def recompile_trip(where: str, delta: int, strict_ok: bool = True) -> None:
    """Record ``delta`` unexpected compiles observed in ``where`` (the
    machine's post-warmup serving check): count, warn loudly, raise under
    TB_SANITIZE_STRICT.  Callers re-baseline so one burst warns once.

    ``strict_ok=False`` downgrades a strict raise to the warning: the
    machine passes it after a capacity growth, when kernel variants not
    yet exercised at the NEW capacity may legitimately first-compile long
    after the growth's one-readback grace window closed."""
    _count("recompiles", delta)
    import sys

    print(
        f"# SANITIZE: {delta} XLA compile(s) in {where} after warmup — "
        "an input shape or static arg is not size-class stable "
        "(tools/tblint --rule size-class names the usual suspects)",
        file=sys.stderr,
    )
    if strict_ok and strict():
        raise SanitizeError(
            f"recompile tripwire: {delta} compile(s) in {where} "
            "after warmup"
        )


# -- metrics-registry leak guard ---------------------------------------------

def assert_registry_disabled(where: str = "teardown") -> None:
    """The process-global obs registry must be DISABLED outside an
    explicitly-armed scope; a leaked enable taxes every later test and
    mixes foreign series into the next snapshot (the PR 10 leak class).
    Disables the registry before raising so one leak doesn't cascade."""
    from .obs.metrics import registry

    if registry.enabled:
        _count("registry_leaks")
        # Disable (stop the cascade) but do NOT reset: the leaked series
        # are the postmortem evidence of WHAT ran enabled.
        registry.disable()
        raise SanitizeError(
            f"metrics-registry leak at {where}: the process-global obs "
            "registry was left ENABLED — wrap enable() in "
            "registry.enabled_scope() or try/finally disable()+reset()"
        )
