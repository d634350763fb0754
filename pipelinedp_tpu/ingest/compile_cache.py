"""Persistent XLA compilation cache, wired at engine init.

Every cold process pays full XLA compilation for the fused kernels
(seconds per program shape on CPU, tens of seconds on a TPU). JAX's
persistent on-disk compilation cache lets repeated cold runs (bench
re-runs, checkpoint-resumed jobs, sweep restarts) reuse compiled
executables across processes. Where the cache lives, in order:

1. ``JAX_COMPILATION_CACHE_DIR``: JAX reads it itself, and no
   directory is set in code;
2. ``PIPELINEDP_TPU_COMPILE_CACHE``: the library's own opt-in;
3. ``default_dir``: the entry points (``bench.py``, ``chip_smoke.py``)
   pass one fixed path inside their checkout. The path is part of the
   cache key, so a moving directory would never hit.

With none of them, the library picks no location (the cache directory
is a shared mutable resource on multi-tenant hosts). The
min-compile-time / min-entry-size thresholds are zeroed so even the
small test-scale programs cache.

Idempotent and failure-safe: configuring twice is a no-op, and a
read-only directory degrades to a no-op rather than breaking
aggregation.

:func:`compile_stats` counts this process's backend compiles, their
seconds and the persistent-cache hits and misses, from
``jax.monitoring`` events; enabling the cache starts the count.
"""

from __future__ import annotations

import os
from typing import Optional

JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
ENV_VAR = "PIPELINEDP_TPU_COMPILE_CACHE"

_configured: Optional[str] = None


class CompileStats:
    """Backend compile seconds and count, and persistent-cache hits and
    misses, since the first :func:`compile_stats` call. A program
    served from the persistent cache still counts its retrieval as a
    backend compile."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def _on_duration(self, event, secs, **_):
        if event == self._COMPILE:
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def snapshot(self):
        """(compile_s, compiles, hits, misses) so far."""
        return (self.compile_s, self.compiles, self.hits, self.misses)


_stats: Optional[CompileStats] = None


def compile_stats() -> CompileStats:
    """The process's one :class:`CompileStats`; the first call
    registers its ``jax.monitoring`` listeners."""
    global _stats
    if _stats is None:
        import jax
        _stats = CompileStats()
        jax.monitoring.register_event_duration_secs_listener(
            _stats._on_duration)
        jax.monitoring.register_event_listener(_stats._on_event)
    return _stats


def maybe_enable_compile_cache(default_dir: Optional[str] = None
                               ) -> Optional[str]:
    """Turns on jax's persistent compilation cache (see the module
    docstring for where it lives). Returns the directory in use, or
    None when the cache stays off. Safe to call on every
    engine/backend construction."""
    global _configured
    path = (os.environ.get(JAX_ENV_VAR) or os.environ.get(ENV_VAR) or
            default_dir)
    if not path:
        return None
    compile_stats()
    if _configured == path:
        return _configured
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    try:
        os.makedirs(path, exist_ok=True)
        if not os.environ.get(JAX_ENV_VAR):
            jax.config.update("jax_compilation_cache_dir", path)
        # Cache everything: the default thresholds skip fast-compiling
        # programs, but resumed/test-scale runs want those too.
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax latches the persistent-cache state at the process's FIRST
        # compilation; a backend constructed after any jit has run
        # would silently get no caching without this re-init.
        compilation_cache.reset_cache()
        _configured = path
        # Entry count at enable time is the observable warmth: a warm
        # dir means later compilations load instead of build.
        with os.scandir(path) as it:
            entries = sum(1 for _ in it)
        from pipelinedp_tpu import obs
        obs.inc("compile_cache.enabled")
        obs.inc("compile_cache.warm_entries", entries)
        obs.event("compile_cache.enabled", dir=path, entries=entries)
    except OSError:
        # Never let an unwritable cache dir break the aggregation.
        return None
    return _configured
