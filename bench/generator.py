"""Seeded replay contents: the prefilled ring and the write pools.

One general generator for every traffic mix.  It reads the ``prefill``
and pool parameters of a traffic file and builds, on the device and in
one jitted call each:

* the prefilled replay state: ``capacity`` rows written through the
  buffer's own ``add_block`` in ``num_envs``-wide steps (the layout an
  actor writes), with episode ends drawn from a seeded length
  distribution, then priorities set through ``update_priorities`` from
  seeded heavy-tailed |delta| (log-normal);
* pools of insert steps and of |delta| rows that closed loops
  cycle through.

The last prefilled step ends every env's episode, so a stream that the
system starts after the prefill never chains its frame stacks into the
prefill's rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _episode_done(key, steps: int, envs: int, lo: int, hi: int):
    """float32[steps, envs]: 1 where an episode of length U[lo, hi] ends."""
    k = math.ceil(steps / lo) + 1
    lengths = jax.random.randint(key, (k, envs), lo, hi + 1)
    ends = jnp.cumsum(lengths, axis=0) - 1
    # One flat scatter ([step, env] -> step * envs + env); ends past the
    # last step fall out of range and are dropped.
    flat = jnp.where(ends < steps, ends * envs + jnp.arange(envs), -1)
    return jnp.zeros(steps * envs, jnp.float32).at[flat.reshape(-1)].set(
        1.0, mode="drop").reshape(steps, envs)


def _leaf(key, name: str, example, steps: int, envs: int, p: dict,
          n_actions: int, done):
    """One transition leaf of shape [steps, envs, *example.shape]."""
    x = jnp.asarray(example)
    shape = (steps, envs) + x.shape
    if name in ("done", "terminated"):
        return done.astype(x.dtype)
    if name == "reward":
        return jax.random.bernoulli(key, p["reward_rate"],
                                    shape).astype(x.dtype)
    if name == "action":
        return jax.random.randint(key, shape, 0, n_actions).astype(x.dtype)
    if x.dtype == jnp.uint8:
        # Sparse frames: a share of pixels lit with random intensities,
        # drawn as bytes so no wider temporary of the ring's size exists.
        k_on, k_v = jax.random.split(key)
        n = math.prod(shape)
        cut = jnp.uint8(round(p["pixel_density"] * 256))
        on = jax.random.bits(k_on, (n,), jnp.uint8) < cut
        val = jnp.maximum(jax.random.bits(k_v, (n,), jnp.uint8), 1)
        return jnp.where(on, val, jnp.uint8(0)).reshape(shape)
    return jax.random.normal(key, shape, x.dtype)


def steps_block(key, example: dict, steps: int, envs: int, p: dict,
                n_actions: int, *, done=None, end_last: bool = False) -> dict:
    """A ``[steps, envs, ...]`` transition block laid out as an actor
    writes it (``done`` given, or drawn from the episode lengths)."""
    k_done, k_leaf = jax.random.split(key)
    if done is None:
        lo, hi = p["episode_len"]
        done = _episode_done(k_done, steps, envs, lo, hi)
    if end_last:
        done = done.at[steps - 1].set(1.0)
    keys = jax.random.split(k_leaf, len(example))
    return {name: _leaf(k, name, example[name], steps, envs, p, n_actions,
                        done)
            for k, name in zip(keys, sorted(example))}


def abs_td(key, shape, p: dict):
    """Heavy-tailed |delta|: exp(N(mu, sigma))."""
    mu, sigma = p["abs_td_lognormal"]
    return jnp.exp(mu + sigma * jax.random.normal(key, shape))


def make_fill(replay, example: dict, num_envs: int, n_actions: int,
              p: dict):
    """Jitted ``(empty state, key) -> ReplayState`` holding ``capacity``
    rows: every row written, ``chunk_steps`` steps at a time, then
    prioritised."""
    cap = replay.capacity
    chunk = p["chunk_steps"]
    if cap % (num_envs * chunk):
        raise ValueError(f"capacity {cap} is not a whole number of "
                         f"{chunk}-step chunks of {num_envs} envs")
    steps = cap // num_envs

    def fill(state, key):
        k_done, k_block, k_td = jax.random.split(key, 3)
        lo, hi = p["episode_len"]
        done = _episode_done(k_done, steps, num_envs, lo, hi)
        done = done.at[steps - 1].set(1.0)

        def body(c, state):
            d = jax.lax.dynamic_slice_in_dim(done, c * chunk, chunk)
            block = steps_block(jax.random.fold_in(k_block, c), example,
                                chunk, num_envs, p, n_actions, done=d)
            return replay.add_block(state, block, aggregated=True)

        # Chunks keep the block's temporaries small: a [rows, 10, 10]
        # uint8 operand is padded to the chip's (32, 128) byte tiles.
        state = jax.lax.fori_loop(0, steps // chunk, body, state)
        td = abs_td(k_td, (cap,), p)
        return replay.update_priorities(
            state, jnp.arange(cap, dtype=jnp.int32), td)

    return jax.jit(fill, donate_argnums=0)


def make_prefill(replay, example: dict, num_envs: int, n_actions: int,
                 p: dict):
    """``key -> ReplayState``: the empty state, then ``make_fill``'s
    program.  The empty state comes in as an argument: with its write
    position a constant inside the same program, the TPU compiler (jaxlib
    0.9.0) fails a check in its scatter emitter on the ring write."""
    empty = jax.jit(lambda: replay.init(example))
    fill = make_fill(replay, example, num_envs, n_actions, p)
    return lambda key: fill(empty(), key)


def make_pools(example: dict, num_envs: int, n_actions: int, batch: int,
               p: dict, insert_steps: int, td_rows: int):
    """Jitted ``key -> (insert block [steps, envs, ...], |delta| [rows,
    batch])`` for closed loops."""

    def pools(key):
        k_block, k_td = jax.random.split(key)
        block = steps_block(k_block, example, insert_steps, num_envs, p,
                            n_actions)
        return block, abs_td(k_td, (td_rows, batch), p)

    return jax.jit(pools)
