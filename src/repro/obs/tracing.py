"""Span-based wall-time tracing over the metrics registry.

``span("csp_rebuild")`` wraps a host-side region and records its wall
time into the histogram ``span_csp_rebuild_ms`` of the *current*
registry.  Three properties make it safe to leave in library code:

* **disabled is one branch.**  With the current registry disabled (the
  process default), entering a span resolves to a shared no-op object;
  nothing is allocated or timed.
* **trace-safe.**  Library functions like ``ReplayBuffer.sample`` or
  ``AmperSampler.build_csp`` run both eagerly (tests, notebooks,
  benchmarks) and under ``jax.jit``.  Under a jit trace the region's
  wall time is *compile* time, not run time — recording it would poison
  the histograms with one bogus multi-second sample per compile — and
  host callbacks have no place on the hot path.  Spans therefore no-op
  whenever ``jax.core.trace_ctx.is_top_level()`` is False.  Instrumentation
  is host-side only either way, so it can never add an XLA dispatch to
  a jitted program (pinned by the tier-1 guard in tests/test_obs.py).
* **profiler-integrated.**  With ``profile=True`` on the registry's
  telemetry config (or ``obs.configure(profile=True)``), spans also
  open a ``jax.profiler.TraceAnnotation`` so they show up as named
  regions in TensorBoard/perfetto traces next to the XLA ops they
  bracket.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

from repro.obs.metrics import Registry, TIME_BUCKETS_MS

# The process-wide current registry.  Disabled by default: every span
# and module-level instrument is a cheap no-op until obs.configure()
# (or a ReplayService run with telemetry) installs an enabled one.
_default_registry = Registry(enabled=False)
_state = threading.local()
_global_registry: Registry = _default_registry
_profile = False


def get_registry() -> Registry:
    """The active registry (thread-local override, then process global)."""
    reg = getattr(_state, "registry", None)
    return reg if reg is not None else _global_registry


def set_registry(registry: Optional[Registry], profile: bool = False
                 ) -> Optional[Registry]:
    """Install ``registry`` as the process-wide current registry
    (None restores the disabled default).  Returns the previously
    installed registry (None if it was the default) so callers can
    restore it when their run ends."""
    global _global_registry, _profile
    prev = _global_registry
    _global_registry = registry if registry is not None else _default_registry
    _profile = profile
    return None if prev is _default_registry else prev


class use_registry:
    """Context manager: route this THREAD's spans/instruments to ``reg``."""

    def __init__(self, reg: Registry):
        self._reg = reg

    def __enter__(self):
        self._prev = getattr(_state, "registry", None)
        _state.registry = self._reg
        return self._reg

    def __exit__(self, *exc):
        _state.registry = self._prev
        return False


class _NullSpan:
    """Shared no-op span (disabled registry or inside a jax trace)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_hist", "_annotation", "_t0")

    def __init__(self, hist, annotation):
        self._hist = hist
        self._annotation = annotation

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._hist.observe((time.perf_counter() - self._t0) * 1e3)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def _trace_state_clean() -> bool:
    """True outside every jax trace (jit, grad, vmap, shard_map)."""
    import jax.core

    return jax.core.trace_ctx.is_top_level()


def span(name: str, registry: Registry | None = None):
    """Wall-time span context manager -> histogram ``span_<name>_ms``.

    No-op (a shared null object) when the resolved registry is disabled
    or the caller is executing inside a jax trace (see module docstring).
    """
    reg = registry if registry is not None else get_registry()
    if not reg.enabled or not _trace_state_clean():
        return _NULL_SPAN
    hist = reg.histogram(f"span_{name}_ms",
                         help=f"wall time of {name} (ms)",
                         bounds=TIME_BUCKETS_MS)
    annotation = None
    if _profile:
        import jax.profiler

        annotation = jax.profiler.TraceAnnotation(name)
    return _Span(hist, annotation)
