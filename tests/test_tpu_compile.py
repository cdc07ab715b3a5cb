"""Ahead-of-time compiles of the replay kernels for a TPU v5e.

Interpret mode (how every other test runs the Pallas kernels) accepts
programs the chip's compiler refuses: scalar stores to VMEM, float iota,
blocks off the (8, 128) tiling.  These tests lower each kernel of the
replay path through Mosaic for a described v5e chip, at a deployment
table size of 1M rows (``rank_select`` at the 500k slice one shard of a
2M table holds), and check that the compiled program holds the kernel.
Nothing runs: a described chip compiles, it does not execute.

The replay ring's insert is compiled too, for ``breakout-amper-1m``'s
ring of 1M MinAtar frames: a scatter over a ring of frames makes the
chip's compiler copy the whole ring into another layout and back.

The topology is described inside a module fixture, never at import, so
each pytest worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import functools
import json
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.rl.dqn import DQNConfig, make_dqn

N = 1_000_000
M = 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_tcam_match_compiles_for_v5e(one_chip):
    _assert_kernel(lambda p, q, k: ops.tcam_match(p, q, k, interpret=False),
                   _spec(one_chip, (N,), jnp.int32),
                   _spec(one_chip, (), jnp.int32),
                   _spec(one_chip, (), jnp.int32))


def test_multi_query_match_compiles_for_v5e(one_chip):
    _assert_kernel(
        lambda p, v, lo, hi: ops.multi_query_match(p, v, lo, hi,
                                                   interpret=False),
        _spec(one_chip, (N,), jnp.int32), _spec(one_chip, (N,), jnp.bool_),
        _spec(one_chip, (M,), jnp.int32), _spec(one_chip, (M,), jnp.int32))


def test_rank_select_compiles_for_v5e(one_chip):
    n = N // 2
    _assert_kernel(
        lambda p, v, lo, hi, r: ops.rank_select(p, v, lo, hi, r,
                                                interpret=False),
        _spec(one_chip, (n,), jnp.int32), _spec(one_chip, (n,), jnp.bool_),
        _spec(one_chip, (M,), jnp.int32), _spec(one_chip, (M,), jnp.int32),
        _spec(one_chip, (512,), jnp.int32))


def test_amper_sample_compiles_for_v5e(one_chip):
    def draw(p, v, lo, hi, shift, key_data):
        return ops.amper_sample(p, v, lo, hi, shift,
                                jax.random.wrap_key_data(key_data),
                                batch=32, csp_capacity=150_000,
                                interpret=False)

    _assert_kernel(draw,
                   _spec(one_chip, (N,), jnp.int32),
                   _spec(one_chip, (N,), jnp.bool_),
                   _spec(one_chip, (M,), jnp.int32),
                   _spec(one_chip, (M,), jnp.int32),
                   _spec(one_chip, (), jnp.int32),
                   _spec(one_chip, (2,), jnp.uint32))


@pytest.mark.parametrize("rows", [16, 512])
def test_ring_insert_writes_in_the_ring_layout_for_v5e(one_chip, rows):
    """``add_block`` on ``breakout-amper-1m``'s ring of 1M 10x10 frames,
    one 16-env step (the draw cell's insert) and a 32-step chunk (the
    actor's): no scatter over the frame ring, and no temporary of half the
    ring's size, which a copy of the ring into another layout needs."""
    conf = json.loads((Path(__file__).parents[1] / "bench" / "configs"
                       / "breakout-amper-1m.json").read_text())
    dqn = make_dqn(DQNConfig(**conf["dqn"]))
    ex = dqn.example_transition
    state = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(dqn.replay.init, ex))
    envs = dqn.cfg.num_envs
    block = jax.tree.map(
        lambda x: _spec(one_chip, (rows // envs, envs) + jnp.shape(x),
                        jnp.asarray(x).dtype), ex)
    c = jax.jit(functools.partial(dqn.replay.add_block, aggregated=True)
                ).lower(state, block).compile()
    ring = state.storage["frame"]
    ring_type = re.escape(f"u8[{','.join(map(str, ring.shape))}]")
    assert not re.search(rf"= {ring_type}\{{[^}}]*\}} scatter\(",
                         c.as_text())
    ring_bytes = ring.size * ring.dtype.itemsize
    assert c.memory_analysis().temp_size_in_bytes < ring_bytes // 2
