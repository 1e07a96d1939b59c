"""Compile-event tracking — the runtime complement to jaxlint JL005.

Two sources, both feeding the one registry:

  - ``jax.monitoring`` listeners (graceful no-op when the API is
    absent): every XLA compile increments ``jax_compiles_total`` and
    observes ``jax_compile_seconds`` — process-wide, catches compiles
    from ANY program including library internals.
  - ``track(name, fn)``: per-program retrace counting via the jit
    cache size of registered compiled steps.  ``sample()`` (called at
    the engine's periodic sync) turns cache growth into
    ``recompiles_total{program=...}`` — cache entries beyond the first
    are retraces, the production signal that a shape/static-arg leak is
    recompiling the hot path (JL005's runtime shadow).

A recompile storm (>= ``storm_threshold`` retraces of one program seen
within a single sample window) logs a loud warning with the program
name — the failure mode is a silent 40s/step trickle otherwise.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from ..utils.logging import logger
from .registry import MetricsRegistry

_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMonitor:
    def __init__(self, registry: MetricsRegistry, storm_threshold: int = 3):
        self.registry = registry
        self.storm_threshold = max(int(storm_threshold), 1)
        self.compiles = registry.counter(
            "jax_compiles_total", "XLA backend compiles (jax.monitoring)")
        self.compile_seconds = registry.histogram(
            "jax_compile_seconds", "XLA backend compile durations")
        self.recompiles = registry.counter(
            "recompiles_total",
            "retraces of tracked jitted programs (cache entries beyond "
            "the first)")
        self._tracked: List[Tuple[str, object]] = []
        self._seen_sizes: Dict[str, int] = {}
        self._warned_storm: set = set()
        self._installed = False
        self._listener = None

    # -- jax.monitoring hook --------------------------------------------
    def install(self) -> bool:
        """Register the duration listener (idempotent)."""
        if self._installed:
            return True
        from jax import monitoring

        def on_duration(event: str, duration: float, **kwargs):
            if event == _COMPILE_DURATION_EVENT:
                self.compiles.inc()
                self.compile_seconds.observe(duration)

        monitoring.register_event_duration_secs_listener(on_duration)
        self._listener = on_duration
        self._installed = True
        return True

    def uninstall(self):
        if not self._installed:
            return
        self._installed = False
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._listener)
        self._listener = None

    # -- per-program retrace tracking -----------------------------------
    def track(self, name: str, fn) -> bool:
        """Register a compiled callable for retrace counting.  Accepts
        anything; silently skips objects without a jit cache (the
        chunked offload paths hand the engine plain Python drivers)."""
        if not hasattr(fn, "_cache_size"):
            return False
        self._tracked.append((name, fn))
        self._seen_sizes.setdefault(name, 0)
        return True

    def sample(self):
        """Fold current cache sizes into ``recompiles_total``.  Rides
        the caller's sync cadence — reading ``_cache_size`` is a host
        dict ``len()``, never a device sync."""
        for name, fn in self._tracked:
            try:
                size = int(fn._cache_size())
            except Exception:
                continue
            prev = self._seen_sizes.get(name, 0)
            if size <= prev:
                continue
            # entries beyond the first are retraces
            new_retraces = max(size - 1, 0) - max(prev - 1, 0)
            self._seen_sizes[name] = size
            if new_retraces <= 0:
                continue
            self.recompiles.inc(new_retraces, program=name)
            if (new_retraces >= self.storm_threshold
                    and name not in self._warned_storm):
                self._warned_storm.add(name)
                logger.warning(
                    "recompile storm: program %r retraced %d times within "
                    "one sample window (total cache entries: %d). A shape "
                    "or static-arg is varying per call — see jaxlint JL005 "
                    "and docs/observability.md.", name, new_retraces, size)

    def tracked_programs(self) -> List[str]:
        return [name for name, _ in self._tracked]
