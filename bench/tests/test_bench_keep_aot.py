"""Ahead-of-time compiles of the check's row copies (``drive.Keep.view``)
for a TPU v5e, at the size of Ape-X's ring of 84x84 uint8 frames: 2,000,000
rows, batch 512, 8 batches to a slab, 4 frames of history, n = 3, 256
environments to a step.  Nothing runs: a described chip compiles, it does
not execute.

A plain row gather ``ring[slots]`` on the chip first copies the whole ring
into another layout (about 6 GB of temporaries for a 500,000-row shard),
and a row loop over a ring sharded across four chips gathers the whole ring
to every chip.  The view reads rows in the ring's own layout order, shard
by shard, so that a pick never asks for a ring-sized temporary or an
all-gather; these tests hold it to that.

The topology is described inside a module fixture, never at import, so
each pytest worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bench import drive
from repro.core.replay_buffer import FrameStore, ReplayBuffer
from repro.core.samplers import make_sampler

FRAME = (84, 84)
ENVS, BATCH, SLAB = 256, 512, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


def _chip_perm(shape, sharding):
    """The axis order (major to minor) in which the described chip lays out
    an array that a program makes, as the ring is made."""
    c = jax.jit(lambda: jnp.zeros(shape, jnp.uint8),
                out_shardings=sharding).lower().compile()
    return tuple(c.output_formats.layout.major_to_minor)


def _compiled_view(cap: int, ring, rep):
    """Keep.view's program for a ring of ``cap`` rows placed by ``ring``
    (its row arrays) and ``rep`` (scalars, the slab's indices), with the
    frames in the layout the chip gives them."""
    fs = FrameStore(history_len=4, frame_shape=FRAME, stride=ENVS, n_step=3)
    rb = ReplayBuffer(cap, make_sampler("amper-fr", cap, v_max=8.0),
                      frame_store=fs, num_envs=ENVS)
    ex = {"frame": jnp.zeros(FRAME, jnp.uint8), "action": jnp.int32(0),
          "reward": jnp.float32(0), "done": jnp.float32(0),
          "terminated": jnp.float32(0)}
    shapes = jax.eval_shape(rb.init, ex)
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=ring if x.ndim else rep), shapes)
    replay = types.SimpleNamespace(
        frame_store=fs, capacity=cap,
        storage_sharding=None if isinstance(ring, SingleDeviceSharding)
        else ring)
    idx = jax.ShapeDtypeStruct((SLAB, BATCH), jnp.int32, sharding=rep)
    perm = _chip_perm((cap,) + FRAME, ring)
    return drive.Keep(replay)._view.lower(
        state, idx, (("frame", perm),)).compile()


def test_view_of_a_shard_asks_no_ring_sized_temporary(topo):
    one = SingleDeviceSharding(topo.devices[0])
    cap = 500_000   # one chip's shard of the 2,000,000-row ring
    c = _compiled_view(cap, one, one)
    ring_bytes = cap * FRAME[0] * FRAME[1]
    assert c.memory_analysis().temp_size_in_bytes < ring_bytes // 2


def test_view_of_a_sharded_ring_gathers_no_ring(topo):
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    cap = 2_000_000
    c = _compiled_view(cap, NamedSharding(mesh, P(("data",))),
                       NamedSharding(mesh, P()))
    shard_bytes = cap // 4 * FRAME[0] * FRAME[1]
    assert c.memory_analysis().temp_size_in_bytes < shard_bytes // 2
    assert "all-gather" not in c.as_text()
