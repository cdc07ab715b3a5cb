"""Unified sampler protocol + registry.

Every priority sampler in the system (uniform / PER sum-tree / PER
cumsum / AMPER-k / AMPER-fr) implements the same five-method state
machine, and everything that consumes one — the replay buffer, the DQN
agent, the LM data pipeline, the benchmarks — should construct it
through ONE factory instead of hand-rolling `if kind == ...` ladders.
This module is that single seam:

* :class:`Sampler` — the formal structural protocol (init / update /
  sample / priorities / total).  All concrete samplers already satisfy
  it; the protocol is ``runtime_checkable`` so tests can assert it.
* :func:`register_sampler` — decorator adding a builder to the registry,
  so new samplers (future PRs: rank-based PER, multi-host replay
  services) plug in without touching any call site.
* :func:`make_sampler` — the registry-backed factory.  Builders accept
  one unified kwargs vocabulary and ignore hyper-parameters they don't
  consume, so a call site can forward its whole config dict regardless
  of which sampler the user picked.

The sharded fronts promised by PR 1 exist: ``"amper-fr-sharded"`` and
``"per-sharded"`` build :class:`repro.core.sharded.ShardedAmperSampler` /
:class:`~repro.core.sharded.ShardedPERSampler`, whose priority tables live
partitioned over a ``jax.sharding.Mesh`` (pass ``mesh=``; defaults to a
1-D mesh over every visible device).  They satisfy the same protocol, so
the replay buffer and the DQN agent use them unchanged.

Shared kwargs vocabulary (all optional):
  m, lam_fr, csp_ratio, v_max, knn_mode, fr_mode, exact_radius,
  frac_bits  — AMPER hyper-parameters (Algorithm 1); ``fr_mode`` picks
  the search implementation (broadcast / interval / window / kernel /
  fused — "fused" runs the whole draw as one Pallas dispatch, see
  :mod:`repro.kernels.amper_sample`), all bit-identical;
  csp_capacity — overrides the csp_ratio-derived CSP size;
  min_csp      — floor for the derived CSP size (usually the train batch);
  mesh, axis_names, local_csp_capacity — sharded samplers only: the mesh
  to partition the priority table over, which of its axes to use, and the
  per-shard CSP buffer override.
"""
from __future__ import annotations

from typing import Any, Callable, Protocol, runtime_checkable

import jax


@runtime_checkable
class Sampler(Protocol):
    """Structural interface every replay-priority sampler implements.

    State is an opaque pytree produced by :meth:`init`; all methods are
    pure and jit/vmap/shard-compatible.
    """

    def init(self) -> Any:
        """Fresh sampler state (empty table)."""
        ...

    def update(self, state: Any, idx: jax.Array, priority: jax.Array) -> Any:
        """Write ``priority[i]`` (already |td|^alpha-exponentiated) at
        row ``idx[i]``.  ``idx`` may be any batch of DISTINCT indices."""
        ...

    def sample(self, state: Any, key: jax.Array, batch: int) -> jax.Array:
        """Draw ``batch`` int32 row indices by the sampler's law."""
        ...

    def priorities(self, state: Any) -> jax.Array:
        """Dense float32[capacity] view of the stored priorities."""
        ...

    def total(self, state: Any) -> jax.Array:
        """Sum of stored priorities (the PER normaliser)."""
        ...


def masked_update(sampler: Sampler, state: Any, idx: jax.Array,
                  priority: jax.Array, valid: jax.Array) -> Any:
    """Out-of-band (deferred) priority write for any registry sampler.

    Rows with ``valid[i] == False`` are rewritten with their *current*
    priority — a no-op write — so a stale deferred update (the slot was
    recycled between sample and feedback) never clobbers fresh state.

    ``idx`` may contain duplicates (priority sampling draws with
    replacement): every occurrence of a row is rewritten with the value
    of that row's last VALID occurrence (its current priority if none is
    valid), so all duplicate scatter writes carry identical values and
    the scatter's winner is irrelevant — sequential last-write-wins
    semantics on every backend, without requiring the protocol's
    distinct-indices contract.
    """
    import jax.numpy as jnp

    prios = sampler.priorities(state)
    rank = jnp.arange(1, idx.shape[0] + 1, dtype=jnp.int32)
    last_valid = jnp.zeros(prios.shape[0], jnp.int32).at[idx].max(
        jnp.where(valid, rank, 0))
    winner = last_valid[idx]  # per position: rank of its row's winner
    value = jnp.where(
        winner > 0,
        priority.astype(jnp.float32)[jnp.maximum(winner - 1, 0)],
        prios[idx])
    return sampler.update(state, idx, value)


def abstract_state(sampler: Sampler) -> Any:
    """Abstract (ShapeDtypeStruct) pytree of ``sampler.init()``.

    Sampler states are pure pytrees, so this is the checkpoint-restore
    target for ANY registry kind — the serialization layer
    (:mod:`repro.train.replay_checkpoint`) needs no per-sampler code.
    """
    return jax.eval_shape(sampler.init)


_REGISTRY: dict[str, Callable[..., Sampler]] = {}


def register_sampler(name: str, *aliases: str):
    """Decorator: register ``builder(capacity, **kw) -> Sampler`` under
    ``name`` (plus aliases).  Re-registration replaces — last wins — so
    downstream code can override a builder without forking this module."""

    def deco(builder: Callable[..., Sampler]):
        for n in (name, *aliases):
            _REGISTRY[n] = builder
        return builder

    return deco


def available_samplers() -> list[str]:
    return sorted(_REGISTRY)


def make_sampler(kind: str, capacity: int, **kw) -> Sampler:
    """Build a sampler by registry name.

    Unknown hyper-parameters in ``kw`` are ignored by builders that don't
    consume them (see module docstring), so one call site can serve every
    registered kind.
    """
    try:
        builder = _REGISTRY[kind]
    except KeyError:
        raise ValueError(
            f"unknown sampler kind: {kind!r} "
            f"(available: {available_samplers()})") from None
    return builder(capacity, **kw)


# --- built-in builders -------------------------------------------------------
# Local imports inside the builders keep this module import-light and break
# the core.amper -> core.samplers -> core.amper cycle.


@register_sampler("uniform")
def _build_uniform(capacity: int, **_unused) -> Sampler:
    from repro.core.amper import UniformSampler

    return UniformSampler(capacity)


@register_sampler("per-sumtree")
def _build_sumtree(capacity: int, **_unused) -> Sampler:
    from repro.core.per import SumTreePER

    return SumTreePER(capacity)


@register_sampler("per-cumsum", "per")
def _build_cumsum(capacity: int, **_unused) -> Sampler:
    from repro.core.per import CumsumPER

    return CumsumPER(capacity)


def _amper_config(capacity: int, *, m: int = 20,
                  lam_fr: float = 2.0, csp_ratio: float = 0.15,
                  lam: float | None = None, v_max: float = 1.0,
                  csp_capacity: int | None = None,
                  min_csp: int = 64, knn_mode: str = "bisect",
                  fr_mode: str = "broadcast", exact_radius: bool = False,
                  frac_bits: int | None = None, **_unused):
    """The one place the unified kwargs vocabulary becomes an AmperConfig."""
    from repro.core.amper import AmperConfig
    import repro.core.quantize as qz

    return AmperConfig(
        capacity=capacity, m=m, lam_fr=lam_fr,
        lam=csp_ratio / 2.0 if lam is None else lam,
        v_max=v_max,
        csp_capacity=(csp_capacity if csp_capacity is not None
                      else max(int(capacity * csp_ratio), min_csp)),
        frac_bits=qz.DEFAULT_FRAC_BITS if frac_bits is None else frac_bits,
        knn_mode=knn_mode, fr_mode=fr_mode, exact_radius=exact_radius)


def _build_amper(variant: str, capacity: int, **kw) -> Sampler:
    from repro.core.amper import AmperSampler

    return AmperSampler(_amper_config(capacity, **kw), variant=variant)


@register_sampler("amper-fr")
def _build_amper_fr(capacity: int, **kw) -> Sampler:
    return _build_amper("fr", capacity, **kw)


@register_sampler("amper-k")
def _build_amper_k(capacity: int, **kw) -> Sampler:
    return _build_amper("k", capacity, **kw)


def _default_mesh():
    """1-D mesh over every visible device (the zero-config sharded case)."""
    from repro.launch.mesh import make_replay_mesh

    return make_replay_mesh()


@register_sampler("amper-fr-sharded")
def _build_amper_fr_sharded(capacity: int, *, mesh=None,
                            axis_names=("pod", "data"),
                            local_csp_capacity: int | None = None,
                            **kw) -> Sampler:
    from repro.core.sharded import ShardedAmperSampler

    return ShardedAmperSampler(
        _amper_config(capacity, **kw), mesh if mesh is not None else _default_mesh(),
        axis_names=axis_names, local_csp_capacity=local_csp_capacity)


@register_sampler("per-sharded")
def _build_per_sharded(capacity: int, *, mesh=None,
                       axis_names=("pod", "data"), **_unused) -> Sampler:
    from repro.core.sharded import ShardedPERSampler

    return ShardedPERSampler(
        capacity, mesh if mesh is not None else _default_mesh(),
        axis_names=axis_names)
