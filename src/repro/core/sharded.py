"""Sharded replay: AMPER/PER priority sampling over a ``jax.sharding.Mesh``.

At production scale the replay/priority table does not fit one device
(e.g. 2^30 sequence priorities = 4 GiB of int32 plus the experiences
themselves), and the sampling step must not funnel the table through one
host.  AMPER's structure makes the distributed version embarrassingly
cheap — this is the paper's insight transferring to the *mesh* level:

  * the m ternary-match queries are pure map operations -> run locally on
    each shard, zero communication;
  * stream compaction is local;
  * the only global state is the per-shard match COUNT (one int32 per
    shard -> all_gather of 4 bytes * shards);
  * batch selection maps each uniform draw to (shard, offset) via the
    gathered count prefix-sum; each element is owned by exactly one shard
    and materialised with a psum.

Total communication per sampled batch: one all-gather of shard counts and
one psum of the b selected indices — O(shards + b) scalars, versus the
sum-tree's O(b log n) serialised dependent lookups.  A sum tree cannot be
sharded this way at all: every descent touches the root.

Contrast baseline: hierarchical cumsum PER on the same sharded table.  It
needs the global prefix-sum of all n priorities — implemented as local
cumsum + all_gather of shard totals, the best-known vector form.

Two access levels:

* :func:`sharded_sample_fr` / :func:`sharded_sample_per` — free-standing
  jit-able sampling functions (the raw sampling law, used by the
  benchmarks and the low-level tests).

* :class:`ShardedAmperSampler` / :class:`ShardedPERSampler` — full
  five-method :class:`repro.core.samplers.Sampler` implementations whose
  state lives sharded on the mesh (``with_sharding_constraint`` keeps the
  priority table distributed through init and the scatter updates).  They
  are registered as ``"amper-fr-sharded"`` / ``"per-sharded"`` in
  :mod:`repro.core.samplers`, so the replay buffer, the DQN agent and the
  benchmarks construct them through the one ``make_sampler`` seam.

The per-shard match path honours every ``AmperConfig.fr_mode`` including
``"kernel"``: the fused Pallas :func:`repro.kernels.ops.multi_query_match`
kernel runs on each shard's local slice (one HBM pass per shard; interpret
mode off-TPU), i.e. the paper's TCAM search executes inside the sampling
pipeline, sharded.  ``fr_mode="fused"`` additionally replaces each shard's
``nonzero``-compaction + gather pick with the streaming
:func:`repro.kernels.ops.rank_select` kernel — same draws, same owners,
bit-identical indices, one pass instead of a materialised index buffer.
"""
from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.core.quantize as qz
from repro.core.amper import (AmperConfig, AmperSampler, AmperState,
                              fr_intervals, fr_queries, fr_radii,
                              group_representatives)


def _flat_axis_index(axis_names: Sequence[str]) -> jax.Array:
    """Row-major linear index of this shard over possibly-multiple mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def _n_shards(axis_names: Sequence[str]) -> jax.Array:
    n = jnp.int32(1)
    for name in axis_names:
        n = n * jax.lax.axis_size(name)
    return n


def resolve_axes(mesh: Mesh, axis_names: Sequence[str]) -> tuple[str, ...]:
    """The subset of ``axis_names`` present on ``mesh`` (order preserved)."""
    axes = tuple(a for a in axis_names if a in mesh.axis_names)
    if not axes:
        raise ValueError(
            f"none of the sharding axes {tuple(axis_names)} exist on mesh "
            f"axes {mesh.axis_names}")
    return axes


def _mesh_shards(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(functools.reduce(operator.mul,
                                (mesh.shape[a] for a in axes), 1))


def _local_match_fr(pq_local: jax.Array, valid_local: jax.Array, v_rep: jax.Array,
                    cfg: AmperConfig) -> jax.Array:
    """m-query ternary match on this shard's slice (no communication)."""
    if cfg.fr_mode == "interval":
        from repro.core.amper import _interval_membership
        lo, hi = fr_intervals(v_rep, cfg)
        return _interval_membership(pq_local, lo, hi) & valid_local
    if cfg.fr_mode == "window":
        from repro.core.amper import _window_membership
        lo, hi = fr_intervals(v_rep, cfg)
        return _window_membership(pq_local, lo, hi, cfg) & valid_local
    if cfg.fr_mode in ("kernel", "fused"):
        # Fused Pallas kernel: all m range queries in ONE pass over this
        # shard's slice of HBM (interpret mode off-TPU).  A prefix query
        # with don't-care mask M is exactly the range [q&~M, (q&~M)|M],
        # so membership is bit-identical to the broadcast mode.
        from repro.kernels import ops as kops
        lo, hi = fr_intervals(v_rep, cfg)
        sel, _counts = kops.multi_query_match(pq_local, valid_local, lo, hi)
        return sel
    if cfg.exact_radius:
        vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
        radius = fr_radii(v_rep, cfg)
        match = jnp.abs(pq_local[None, :] - vq[:, None]) <= radius[:, None]
    else:
        vq, mask = fr_queries(v_rep, cfg)
        match = qz.ternary_match(pq_local[None, :], vq[:, None], mask[:, None])
    return jnp.any(match, axis=0) & valid_local


def _fr_sample_body(cfg: AmperConfig, batch: int, axis_names: tuple[str, ...],
                    local_cap: int):
    """The per-shard AMPER-fr sampling program (shared by the free function
    and :class:`ShardedAmperSampler`)."""

    def body(pq_local, valid_local, key):
        n_local = pq_local.shape[0]
        kq, kpick = jax.random.split(key)
        kpick, kfb = jax.random.split(kpick)  # fallback gets its OWN key
        with jax.named_scope("csp_build"):
            v_rep = group_representatives(kq, cfg)  # identical on all shards
            if cfg.fr_mode == "fused":
                # Fused pick: the rank-select kernel turns each owned
                # draw straight into its member index in one pass over
                # the shard's slice — no compacted index buffer.
                # Membership (and hence counts, owners, offsets) reuses
                # the multi-query kernel, so the whole draw is
                # bit-identical to the reference modes: rank r in index
                # order IS nonzero(selected)[r].
                from repro.kernels import ops as kops
                selected = _local_match_fr(pq_local, valid_local, v_rep, cfg)
                loc_count = jnp.minimum(
                    jnp.sum(selected.astype(jnp.int32)), local_cap)

                def pick_local(offset):
                    lo, hi = fr_intervals(v_rep, cfg)
                    idx, _cnt = kops.rank_select(pq_local, valid_local,
                                                 lo, hi, offset)
                    return idx
            else:
                selected = _local_match_fr(pq_local, valid_local, v_rep, cfg)
                (loc_idx,) = jnp.nonzero(selected, size=local_cap,
                                         fill_value=0)
                loc_count = jnp.minimum(
                    jnp.sum(selected.astype(jnp.int32)), local_cap)

                def pick_local(offset):
                    return loc_idx[jnp.clip(offset, 0, local_cap - 1)]

        with jax.named_scope("csp_pick"):
            counts = jax.lax.all_gather(loc_count, axis_names, tiled=False)
            counts = counts.reshape(-1)  # (n_shards,)
            cum = jnp.cumsum(counts)
            total = cum[-1]

            # Identical draws on every shard (same key): u in [0, total).
            u = jax.random.randint(kpick, (batch,), 0,
                                   jnp.maximum(total, 1))
            owner = jnp.searchsorted(cum, u,
                                     side="right").astype(jnp.int32)
            start = cum - counts  # exclusive prefix
            offset = u - start[jnp.clip(owner, 0, counts.shape[0] - 1)]

            me = _flat_axis_index(axis_names)
            mine = owner == me
            local_pick = pick_local(offset).astype(jnp.int32)
            contrib = jnp.where(mine, local_pick + me * n_local, 0)
            picked = jax.lax.psum(contrib, axis_names)

            # Fallback: empty CSP -> uniform over the global table.
            fb = jax.random.randint(kfb, (batch,), 0,
                                    n_local * _n_shards(axis_names))
            return jnp.where(total > 0, picked, fb).astype(jnp.int32)

    return body


def _local_csp_capacity(mesh: Mesh, axes: Sequence[str], cfg: AmperConfig,
                        override: int | None) -> int:
    if override is not None:
        return override
    return max(cfg.csp_capacity // max(_mesh_shards(mesh, axes), 1), 1)


def sharded_sample_fr(mesh: Mesh, cfg: AmperConfig, batch: int,
                      axis_names: Sequence[str] = ("pod", "data"),
                      local_csp_capacity: int | None = None):
    """Build a jit-able sharded AMPER-fr sampler over ``mesh``.

    Returns fn(pq, valid, key) -> int32[batch] global indices, where pq and
    valid are sharded over ``axis_names`` on their leading dim.
    """
    axes = resolve_axes(mesh, axis_names)
    local_cap = _local_csp_capacity(mesh, axes, cfg, local_csp_capacity)
    spec = P(axes)
    return jax.shard_map(
        _fr_sample_body(cfg, batch, axes, local_cap), mesh=mesh,
        in_specs=(spec, spec, P()),
        out_specs=P(),
        check_vma=False,
    )


def _per_sample_body(batch: int, axis_names: tuple[str, ...]):
    """Per-shard hierarchical-cumsum PER sampling program."""

    def body(p_local, key):
        n_local = p_local.shape[0]
        local_cum = jnp.cumsum(p_local)
        local_total = local_cum[-1]
        totals = jax.lax.all_gather(local_total, axis_names, tiled=False).reshape(-1)
        cum_tot = jnp.cumsum(totals)
        grand = jnp.maximum(cum_tot[-1], 1e-12)

        u = jax.random.uniform(key, (batch,)) * grand
        owner = jnp.searchsorted(cum_tot, u, side="right").astype(jnp.int32)
        start = cum_tot - totals
        me = _flat_axis_index(axis_names)
        mine = owner == me
        local_u = u - start[jnp.clip(owner, 0, totals.shape[0] - 1)]
        loc = jnp.searchsorted(local_cum, local_u, side="right")
        loc = jnp.clip(loc, 0, n_local - 1).astype(jnp.int32)
        contrib = jnp.where(mine, loc + me * n_local, 0)
        return jax.lax.psum(contrib, axis_names).astype(jnp.int32)

    return body


def sharded_sample_per(mesh: Mesh, batch: int,
                       axis_names: Sequence[str] = ("pod", "data")):
    """Contrast baseline: hierarchical cumsum PER on the same sharded table.

    Local prefix-sum + all_gather of shard totals + global draw -> each
    shard binary-searches the draws that land in its range.
    """
    axes = resolve_axes(mesh, axis_names)
    spec = P(axes)
    return jax.shard_map(_per_sample_body(batch, axes), mesh=mesh,
                         in_specs=(spec, P()), out_specs=P(),
                         check_vma=False)


def repartition(sampler, state):
    """Move a sampler state onto ``sampler``'s mesh placement.

    The elastic-restore primitive: a state that lives dense on host, on
    one device, or partitioned over a DIFFERENT shard count is device_put
    leaf-by-leaf with the target sampler's capacity-dim ``NamedSharding``
    — values (and therefore CSP membership / sampling law) are unchanged,
    only the partitioning moves.  Works for any sampler exposing
    ``.sharding``; for unsharded samplers it is the identity.
    """
    sh = getattr(sampler, "sharding", None)
    if sh is None:
        return state
    return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sh), state)


# --- mesh-native Sampler implementations -------------------------------------


class ShardedAmperSampler(AmperSampler):
    """AMPER-fr with the priority table sharded over a mesh.

    Implements the five-method :class:`repro.core.samplers.Sampler`
    protocol; state arrays carry a ``NamedSharding`` over ``axis_names``
    on their leading (capacity) dim, maintained through :meth:`init` and
    the :meth:`update` scatter by ``with_sharding_constraint``.  Sampling
    runs the O(shards + batch)-communication law of
    :func:`sharded_sample_fr`; :meth:`priorities` / :meth:`total` are the
    dense views the replay buffer's importance weights need (XLA keeps
    them distributed — the table is never funnelled through one host).

    Registry name: ``"amper-fr-sharded"``.
    """

    def __init__(self, cfg: AmperConfig, mesh: Mesh,
                 axis_names: Sequence[str] = ("pod", "data"),
                 local_csp_capacity: int | None = None):
        super().__init__(cfg, variant="fr")
        self.mesh = mesh
        self.axis_names = resolve_axes(mesh, axis_names)
        self.n_shards = _mesh_shards(mesh, self.axis_names)
        if cfg.capacity % self.n_shards:
            raise ValueError(
                f"capacity {cfg.capacity} not divisible by the "
                f"{self.n_shards} shards of mesh axes {self.axis_names}")
        self.spec = P(self.axis_names)
        self.sharding = NamedSharding(mesh, self.spec)
        self.local_csp_capacity = _local_csp_capacity(
            mesh, self.axis_names, cfg, local_csp_capacity)
        self._sample_fns: dict[int, callable] = {}

    def _shard(self, x: jax.Array) -> jax.Array:
        return jax.lax.with_sharding_constraint(x, self.sharding)

    def init(self) -> AmperState:
        st = super().init()
        return AmperState(pq=self._shard(st.pq), valid=self._shard(st.valid))

    def update(self, state: AmperState, idx: jax.Array,
               priority: jax.Array) -> AmperState:
        st = super().update(state, idx, priority)
        return AmperState(pq=self._shard(st.pq), valid=self._shard(st.valid))

    def _sample_fn(self, batch: int):
        fn = self._sample_fns.get(batch)
        if fn is None:
            fn = jax.shard_map(
                _fr_sample_body(self.cfg, batch, self.axis_names,
                                self.local_csp_capacity),
                mesh=self.mesh,
                in_specs=(self.spec, self.spec, P()), out_specs=P(),
                check_vma=False)
            self._sample_fns[batch] = fn
        return fn

    def sample(self, state: AmperState, key: jax.Array, batch: int,
               stratified: bool = True) -> jax.Array:
        del stratified  # CSP sampling is uniform by construction
        return self._sample_fn(batch)(state.pq, state.valid, key)

    def membership(self, state: AmperState, key: jax.Array) -> jax.Array:
        """Global bool[capacity] CSP membership for ``key`` (test/analysis
        hook; bit-identical to ``build_csp_fr(...).selected`` single-device)."""

        def body(pq_local, valid_local, k):
            kq, _ = jax.random.split(k)
            v_rep = group_representatives(kq, self.cfg)
            return _local_match_fr(pq_local, valid_local, v_rep, self.cfg)

        fn = jax.shard_map(body, mesh=self.mesh,
                           in_specs=(self.spec, self.spec, P()),
                           out_specs=self.spec, check_vma=False)
        return fn(state.pq, state.valid, key)


class ShardedPERState(NamedTuple):
    priorities: jax.Array  # float32[capacity], sharded on the leading dim


class ShardedPERSampler:
    """Hierarchical-cumsum PER with the priority table sharded over a mesh.

    The contrast baseline to :class:`ShardedAmperSampler` at mesh scale:
    sampling needs the global prefix structure, realised as local cumsum +
    all_gather of shard totals (O(n/shards) local work, O(shards) comms).
    Same five-method protocol; registry name ``"per-sharded"``.  Draws are
    non-stratified (each shard consumes the identical global uniforms).
    """

    def __init__(self, capacity: int, mesh: Mesh,
                 axis_names: Sequence[str] = ("pod", "data")):
        self.capacity = capacity
        self.mesh = mesh
        self.axis_names = resolve_axes(mesh, axis_names)
        self.n_shards = _mesh_shards(mesh, self.axis_names)
        if capacity % self.n_shards:
            raise ValueError(
                f"capacity {capacity} not divisible by the "
                f"{self.n_shards} shards of mesh axes {self.axis_names}")
        self.spec = P(self.axis_names)
        self.sharding = NamedSharding(mesh, self.spec)
        self._sample_fns: dict[int, callable] = {}

    def _shard(self, x: jax.Array) -> jax.Array:
        return jax.lax.with_sharding_constraint(x, self.sharding)

    def init(self) -> ShardedPERState:
        return ShardedPERState(
            priorities=self._shard(jnp.zeros(self.capacity, jnp.float32)))

    def total(self, state: ShardedPERState) -> jax.Array:
        return jnp.sum(state.priorities)

    def priorities(self, state: ShardedPERState) -> jax.Array:
        return state.priorities

    def update(self, state: ShardedPERState, idx: jax.Array,
               priority: jax.Array) -> ShardedPERState:
        return ShardedPERState(priorities=self._shard(
            state.priorities.at[idx].set(priority.astype(jnp.float32))))

    def _sample_fn(self, batch: int):
        fn = self._sample_fns.get(batch)
        if fn is None:
            fn = jax.shard_map(_per_sample_body(batch, self.axis_names),
                               mesh=self.mesh, in_specs=(self.spec, P()),
                               out_specs=P(), check_vma=False)
            self._sample_fns[batch] = fn
        return fn

    def sample(self, state: ShardedPERState, key: jax.Array, batch: int,
               stratified: bool = True) -> jax.Array:
        del stratified  # sharded law draws global (non-stratified) uniforms
        return self._sample_fn(batch)(state.priorities, key)
