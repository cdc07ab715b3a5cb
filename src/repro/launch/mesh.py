"""Mesh builders: the one owner of mesh construction.

Every mesh in the repo comes from :func:`make_mesh`, which makes all axes
``Auto``.  ``jax.make_mesh`` defaults to ``Explicit`` axes, and
``with_sharding_constraint`` — how the replay buffer and the sharded
samplers keep their tables partitioned — only accepts ``Auto`` ones.

The builders are FUNCTIONS (not module constants) so that importing this
module never touches jax device state — required because the dry-run
forces 512 host devices via XLA_FLAGS before first jax init, while
tests/benches must keep seeing the devices they configured.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Sequence[jax.Device] | None = None
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None,
                    model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests, examples)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def make_replay_mesh(n_shards: int | None = None) -> jax.sharding.Mesh:
    """1-D ``("data",)`` mesh for the sharded replay subsystem.

    ``n_shards`` defaults to every visible device; an explicit smaller
    value builds the mesh over a device prefix, which is how the sharded
    benchmarks sweep shard counts inside one process (XLA_FLAGS must have
    forced enough host devices before first jax init).
    """
    devices = jax.devices()
    n = n_shards or len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} shards but only "
                         f"{len(devices)} devices exist")
    return make_mesh((n,), ("data",), devices=devices[:n])
