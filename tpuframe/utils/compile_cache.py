"""Shared persistent-compilation-cache startup helper.

One helper for ``train.py``, ``serve``, ``launch/launcher.py`` and
``bench.py`` — so preemption relaunches and crash-loop restarts stop
recompiling every program from scratch.  Cache traffic is surfaced as
process-wide counters in ``obs.metrics``:

    compile_cache.hits    — programs served from the on-disk cache
    compile_cache.misses  — fresh compiles written to it

(train.py folds both into its final metrics next to the ``retry.*``
counters, so a warm restart is visible in the run log.)

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` if set — jax reads
it itself, and this module then sets no directory in code — else the
fixed ``<repo>/.xla_cache``.  The path is part of the cache key, so it
never moves by pid, time or temp name.

Knobs:
    TPUFRAME_COMPILE_CACHE        "0" / "off" disables; nothing else
    TPUFRAME_COMPILE_CACHE_MIN_S  min compile seconds worth persisting
                                  (default 1.0)
"""

from __future__ import annotations

import os

_ENV_OFF = "TPUFRAME_COMPILE_CACHE"
_ENV_STD = "JAX_COMPILATION_CACHE_DIR"
_ENV_MIN_S = "TPUFRAME_COMPILE_CACHE_MIN_S"
_OFF = ("", "0", "off", "none")

_LISTENER_INSTALLED = False
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def reset_cache() -> bool:
    """Drop jax's latched in-process view of the persistent cache so the
    next compile re-initializes against the currently-configured dir.
    Needed by anything that re-points the cache mid-process (the serve
    loadgen cache-hit test, tune's AOT harness).  Returns False when the
    private hook is unavailable (then only early-set dirs engage)."""
    try:
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
        return True
    except Exception:  # noqa: BLE001 — private API
        return False


def default_cache_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def location() -> tuple[str | None, str]:
    """``(directory, source)`` of the cache :func:`enable` would use:
    source is ``env`` (the standard variable), ``default`` (the fixed
    in-checkout path) or ``off``."""
    off = os.environ.get(_ENV_OFF)
    if off is not None and off.strip().lower() in _OFF:
        return None, "off"
    std = os.environ.get(_ENV_STD, "").strip()
    if std:
        return std, "env"
    return default_cache_dir(), "default"


def enable(*, min_compile_secs: float | None = None,
           min_entry_size_bytes: int | None = None) -> str | None:
    """Turn on the persistent compilation cache + hit/miss counters.

    Returns the cache dir, or None when disabled via env.  Call before
    the first compile; safe to call more than once (jax.config updates
    are idempotent, the monitoring listener installs once).  jax is
    imported lazily so stdlib-only callers can import this module freely.
    """
    cache_dir, source = location()
    if cache_dir is None:
        return None

    import jax

    if source == "default":
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if min_compile_secs is None:
        min_compile_secs = float(os.environ.get(_ENV_MIN_S, "1.0"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    if min_entry_size_bytes is not None:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          min_entry_size_bytes)
    # If anything compiled before enable(), jax has already latched its
    # cache singleton as "no cache" and ignores the dir we just set —
    # reset so the next compile re-initializes against it.
    reset_cache()
    _install_listener()
    return cache_dir


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    import jax

    from tpuframe.obs import events as obs_events
    from tpuframe.obs import metrics

    def _on_event(event: str, **kwargs) -> None:
        if event == _HIT_EVENT:
            metrics.bump("compile_cache.hits")
            obs_events.emit("compile", cached=True, source="persistent_cache")
        elif event == _MISS_EVENT:
            metrics.bump("compile_cache.misses")
            obs_events.emit("compile", cached=False,
                            source="persistent_cache")

    jax.monitoring.register_event_listener(_on_event)
    _LISTENER_INSTALLED = True
