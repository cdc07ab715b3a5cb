"""Span-based wall-time tracing over the metrics registry.

``span("learn")`` wraps a host-side region and records its wall time
into the histogram ``span_learn_ms`` of the *current* registry.  Three
properties make it safe to leave in library code:

* **disabled is one branch.**  With the current registry disabled (the
  process default), entering a span resolves to a shared no-op object;
  nothing is allocated or timed.
* **trace-safe.**  Code that holds a span may run both eagerly and
  under ``jax.jit``.  Under a jit trace the region's wall time is
  *compile* time, not run time — recording it would poison the
  histograms with one bogus multi-second sample per compile — and
  host callbacks have no place on the hot path.  Spans therefore no-op
  whenever ``jax.core.trace_ctx.is_top_level()`` is False.  Instrumentation
  is host-side only either way, so it can never add an XLA dispatch to
  a jitted program (pinned by the tier-1 guard in tests/test_obs.py).
* **profiler-integrated.**  With ``profile=True`` on the registry's
  telemetry config (or ``obs.configure(profile=True)``), spans also
  open a ``jax.profiler.TraceAnnotation`` so they show up as named
  regions in TensorBoard/perfetto traces next to the XLA ops they
  bracket, on the profiler's clock and on their own thread's line.
  Keyword arguments (``span("learn", slab=seq0)``) become the
  annotation's event stats; the histogram ignores them, and nothing is
  built from them when profiling is off.

Device regions inside a jitted program are named with
``jax.named_scope`` instead (``csp_build``, ``frame_stack``,
``td_loss`` ...): a span inside a trace is a no-op.

Compiles are counted by one process-wide ``jax.monitoring`` listener,
installed with the first enabled registry: every lowering of a jaxpr to
an MLIR module (``/jax/core/compile/jaxpr_to_mlir_module_duration``,
one per compile whether or not the persistent cache then serves it)
adds one to the counter ``jit_compiles_total`` of the registry current
on the compiling thread and its lowering time to ``jit_compile_ms``.
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

COMPILES = "jit_compiles_total"
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_compile_listener_installed = False
_install_lock = threading.Lock()


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
    if registry is not None and registry.enabled:
        _install_compile_listener()
    prev = _global_registry
    _global_registry = registry if registry is not None else _default_registry
    _profile = profile
    return None if prev is _default_registry else prev


class use_registry:
    """Context manager: route this THREAD's spans/instruments to ``reg``."""

    def __init__(self, reg: Registry):
        self._reg = reg

    def __enter__(self):
        if self._reg.enabled:
            _install_compile_listener()
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


def span(name: str, registry: Registry | None = None, **args):
    """Wall-time span context manager -> histogram ``span_<name>_ms``.

    No-op (a shared null object) when the resolved registry is disabled
    or the caller is executing inside a jax trace (see module docstring).
    ``args`` go to the profiler annotation only, when profiling is on.
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

        annotation = jax.profiler.TraceAnnotation(name, **args)
    return _Span(hist, annotation)


def _on_duration_event(event: str, duration_secs: float, **_) -> None:
    if event != _LOWERING_EVENT:
        return
    reg = get_registry()
    if not reg.enabled:
        return
    _compiles(reg).add()
    reg.histogram("jit_compile_ms", help="lowering wall time (ms)",
                  bounds=TIME_BUCKETS_MS).observe(duration_secs * 1e3)


def _install_compile_listener() -> None:
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    import jax.monitoring

    with _install_lock:
        if not _compile_listener_installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            _compile_listener_installed = True


def _compiles(reg: Registry):
    return reg.counter(COMPILES, help="jaxpr lowerings (one per compile)")


def compile_count(registry: Registry | None = None) -> int:
    """Compiles recorded so far in ``registry`` (default: the current)."""
    reg = registry if registry is not None else get_registry()
    return int(_compiles(reg).value)
