"""One place that touches JAX's backend for every fresh-process entry point.

The program runs on whatever platform JAX gives it and never changes that
platform behind the caller: an accelerator that fails to initialize is an
error, not a reason to serve from the CPU.

- ``backend_info()``: initialize the default backend once and return
  ``(platform, device_kind, device_count)``; an init error propagates.
- ``force_cpu(n)``: for tests, the simulator and the formatter — pin THIS
  process to the CPU backend with >= n virtual devices, even if a backend
  already initialized (clears jax's backend caches and re-inits; jax 0.9
  keeps a memoized ``get_backend`` that must be cleared too).
- ``child_env()``: environment that pins a spawned python subprocess to the
  CPU with n virtual devices.
- ``enable_compile_cache()``: the persistent compile cache lives where
  ``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed ``.jax_cache/`` of
  the checkout (the path is part of the cache key, so it never moves).

The reference has no analogue (a Zig binary owns its process); this is the
TPU-runtime equivalent of src/io.zig:11-16 choosing a working event loop.
"""

from __future__ import annotations

import os
import warnings
from typing import List, Optional, Tuple

__all__ = [
    "force_cpu", "backend_info", "child_env", "current_platform",
    "COMPILE_CACHE_DIR", "enable_compile_cache", "instrument_compiles",
    "compile_count", "shard_map",
]

# Set when force_cpu had to settle for fewer virtual devices than requested
# (backend initialized before XLA_FLAGS could take effect).
# Tests and tools can key on this instead of re-deriving it from warnings.
DEGRADED_DEVICE_COUNT: Optional[int] = None

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def _set_host_device_flag(n: int, env=os.environ) -> None:
    """Merge ``--xla_force_host_platform_device_count=n`` into ``env``'s
    XLA_FLAGS, replacing any previous value (XLA's handling of duplicate
    flags is undocumented).  XLA parses the env var once per process at
    first backend creation, so this only takes effect if it runs before init —
    callers still verify the resulting device count."""
    parts = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_HOST_COUNT_FLAG)]
    parts.append(f"{_HOST_COUNT_FLAG}={n}")
    env["XLA_FLAGS"] = " ".join(parts)

# Persistent XLA compilation cache, shared by the server, the tools and the
# tests so a second start never re-pays the first compile.  One
# definition here — two independently-spelled paths would silently diverge.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Point jax at the persistent cache (before the first compile): where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``COMPILE_CACHE_DIR``.  The
    env var reaches child processes; THIS process needs the config update
    too, because jax read the variable when the package imported it."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path


def shard_map(f, *, mesh, in_specs, out_specs, check_vma):
    """``jax.shard_map`` behind ONE shared wrapper for machine.py,
    parallel/sharded.py, and future mesh callers.  Imported lazily: jax
    must not be imported at module import time (this module configures the
    environment BEFORE the first backend init)."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


_COMPILE_LISTENER_INSTALLED = False

# Monotonic count of XLA backend compiles in THIS process, maintained by
# the instrument_compiles listener UNCONDITIONALLY (one int add per
# compile — compiles are rare by definition).  Unlike the jit.compiles
# registry series this does not require the obs registry to be enabled,
# so the bench recompile tripwire and the TB_SANITIZE serving check can
# diff it around timed regions with zero arming ceremony.
_COMPILE_COUNT = 0


def compile_count() -> int:
    """Process-wide XLA backend compile count (0 until instrument_compiles
    has been installed — callers diff deltas, so the base is irrelevant)."""
    return _COMPILE_COUNT


def instrument_compiles() -> bool:
    """Feed jit compile accounting into the obs metrics registry.

    Registers a ``jax.monitoring`` duration listener: every XLA backend
    compile increments ``jit.compiles`` and lands its duration in the
    ``jit.compile_ms`` histogram (re-traces count under ``jit.traces``).
    This is how a bench or server answers "did that latency spike pay a
    compile?" without a profiler attached.  Idempotent; returns whether
    the hook is live.  The listener itself is registered once and gates on
    ``registry.enabled``, so it costs one branch per compile (compiles are
    rare by definition) when metrics are off."""
    global _COMPILE_LISTENER_INSTALLED
    if _COMPILE_LISTENER_INSTALLED:
        return True
    try:
        from jax._src import monitoring
    except ImportError:
        return False
    from .obs.metrics import registry

    def _on_duration(event: str, duration: float, **kwargs) -> None:
        global _COMPILE_COUNT
        if event.endswith("backend_compile_duration"):
            # The bare count is maintained even with the registry off —
            # compile_count() feeds the recompile tripwires.
            _COMPILE_COUNT += 1
        if not registry.enabled:
            return
        if event.endswith("backend_compile_duration"):
            registry.counter("jit.compiles").inc()
            registry.histogram("jit.compile_ms", "ms").observe(
                duration * 1e3
            )
        elif event.endswith("jaxpr_trace_duration"):
            registry.counter("jit.traces").inc()

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
    except Exception:  # private-API probe: degrade to "no hook"
        return False
    _COMPILE_LISTENER_INSTALLED = True
    return True


def _bridge():
    from jax._src import xla_bridge

    return xla_bridge


def _reset_backends() -> None:
    """Clear all initialized backends and memoized lookups (jax 0.9 private
    API, guarded so a rename degrades to a no-op rather than a crash)."""
    xb = _bridge()
    for fn in ("_clear_backends",):
        try:
            getattr(xb, fn)()
        except Exception:  # tblint: ignore[swallow] private-API probe
            pass
    try:
        xb.get_backend.cache_clear()
    except Exception:  # tblint: ignore[swallow] private-API probe
        pass
    # Newer jax caches the device list on jax.devices too; clear defensively.
    import jax

    for obj in (jax.devices, jax.local_devices):
        try:
            obj.cache_clear()  # type: ignore[attr-defined]
        except Exception:  # tblint: ignore[swallow] private-API probe
            pass


def force_cpu(n_devices: Optional[int] = None) -> List:
    """Force this process onto the CPU backend with >= n_devices devices.

    Safe whether or not a backend has already initialized.  Returns the
    device list.
    """
    global DEGRADED_DEVICE_COUNT
    DEGRADED_DEVICE_COUNT = None  # re-judged below on every call
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        # Must land in the environment BEFORE the first backend creation
        # (XLA's flag parse is once-per-process); child processes inherit
        # it too.
        _set_host_device_flag(n_devices)
    import jax

    def _configure():
        jax.config.update("jax_platforms", "cpu")
        if n_devices is not None:
            try:
                jax.config.update("jax_num_cpu_devices", n_devices)
            except Exception:  # tblint: ignore[swallow] verified below
                pass  # backend already up: reset + retry below

    if _bridge().backends_are_initialized():
        _reset_backends()
    _configure()
    devs = jax.devices()
    ok = devs and devs[0].platform == "cpu" and (
        n_devices is None or len(devs) >= n_devices
    )
    if not ok:
        # A backend slipped in (or too few devices): hard reset and re-init.
        _reset_backends()
        _configure()
        devs = jax.devices()
    if not devs or devs[0].platform != "cpu":
        raise RuntimeError(
            f"force_cpu: CPU backend unavailable, got {devs!r}"
        )
    if n_devices is not None and len(devs) < n_devices:
        # A backend initialized before our XLA_FLAGS could take effect (the
        # flag parse is once-per-process).  Raising here used to take down
        # the whole test collection; degrade to what exists instead —
        # device-count-sensitive callers (tests/test_sharded.py's mesh
        # fixture) check DEGRADED_DEVICE_COUNT or len() of the returned
        # list and skip/shrink accordingly.
        DEGRADED_DEVICE_COUNT = len(devs)
        warnings.warn(
            f"force_cpu: wanted {n_devices} CPU devices, got {len(devs)} "
            "(backend initialized before XLA_FLAGS took effect); "
            "continuing with the available devices",
            RuntimeWarning,
            stacklevel=2,
        )
    return devs


def current_platform() -> Optional[str]:
    """Platform of the default backend if one is initialized, else None
    (without triggering initialization)."""
    try:
        xb = _bridge()
        if not xb.backends_are_initialized():
            return None
        import jax

        return jax.devices()[0].platform
    except Exception:
        return None


def backend_info() -> Tuple[str, str, int]:
    """Initialize the default backend and return ``(platform, device_kind,
    device_count)`` as JAX reports them.  Strict: whatever ``jax.devices()``
    raises propagates — no watchdog, no fallback, no re-exec."""
    import jax

    devs = jax.devices()
    return devs[0].platform, devs[0].device_kind, len(devs)


def child_env(
    cpu: bool = True, n_devices: Optional[int] = None, base: Optional[dict] = None
) -> dict:
    """Environment for a spawned python subprocess pinned to the CPU
    backend (``cpu=True``) with ``n_devices`` virtual devices."""
    env = dict(os.environ if base is None else base)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        _set_host_device_flag(n_devices, env)
    return env
