"""Golden-trajectory regression fixtures for the environment dynamics.

``tests/golden/envs.json`` holds checked-in obs/reward/done sequences
for every registered env at fixed seeds and a fixed action pattern
(generated from the transcribed-from-gym dynamics).  Any refactor
of the physics — integrator, constants, termination, auto-reset — that
drifts a trajectory fails here instead of silently shifting learning
curves three benchmarks downstream.

The fixture stores the PRE-reset observation stream (``step``'s second
return), i.e. the values the TD target consumes, so auto-reset behavior
is pinned too (via the ``done`` flags).  Fixtures predating the
terminated/truncated split carry no ``terminated`` stream; newer ones
(the pixel envs) pin it as well.

Regenerate (only when the PRNG stream itself changes, never to absorb a
physics change) with ``PYTHONPATH=src python tests/test_env_golden.py``:
it replays each fixture's own action pattern and rewrites every stream.
Before regenerating, check that the old fixture still passes under the
old stream, which shows the dynamics did not move.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl import envs as envs_mod

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "envs.json")

with open(GOLDEN) as f:
    _FIXTURES = json.load(f)


@pytest.mark.parametrize("name", sorted(_FIXTURES))
def test_env_matches_golden_trajectory(name):
    env = envs_mod.make_env(name)
    fx = _FIXTURES[name]
    state = env.reset(jax.random.key(0))
    np.testing.assert_allclose(np.asarray(env.obs(state)),
                               np.asarray(fx["reset_obs"]),
                               rtol=1e-6, atol=1e-6)
    for t, a in enumerate(fx["actions"]):
        state, obs, r, d, term = env.step(
            state, jnp.int32(a), jax.random.fold_in(jax.random.key(1), t))
        np.testing.assert_allclose(
            np.asarray(obs), np.asarray(fx["obs"][t]), rtol=1e-5, atol=1e-6,
            err_msg=f"{name} obs drift at step {t}")
        assert float(r) == pytest.approx(fx["reward"][t], abs=1e-6), (name, t)
        assert bool(d) == fx["done"][t], (name, t)
        if "terminated" in fx:
            assert bool(term) == fx["terminated"][t], (name, t)


def test_golden_covers_every_registered_env():
    """Adding an env without a golden trajectory is a test gap — this
    fails until the fixture is regenerated (see module docstring)."""
    assert set(_FIXTURES) == set(envs_mod.available_envs())


@pytest.mark.parametrize("name", envs_mod.available_envs())
def test_env_contract_shapes_and_dtypes(name):
    """Registry-wide contract: reset/step/obs agree on shape AND dtype.

    Pins the CartPole regression where ``step`` returned the raw state
    vector instead of routing through ``obs()`` — indistinguishable for
    identity observations, wrong for every env where obs != state.
    """
    env = envs_mod.make_env(name)
    obs_shape = tuple(env.obs_shape)
    state = env.reset(jax.random.key(0))
    o_reset = env.obs(state)
    assert tuple(o_reset.shape) == obs_shape, name
    state2, o_step, r, d, term = env.step(
        state, jnp.int32(0), jax.random.key(1))
    assert tuple(o_step.shape) == obs_shape, name
    assert o_step.dtype == o_reset.dtype, name
    o_next = env.obs(state2)
    assert tuple(o_next.shape) == obs_shape, name
    assert o_next.dtype == o_reset.dtype, name
    assert r.dtype == jnp.float32, name
    assert d.dtype == jnp.bool_ and term.dtype == jnp.bool_, name
    # terminated implies done, never the reverse (truncation).
    assert bool(d) or not bool(term), name


@pytest.mark.parametrize("name", envs_mod.available_envs())
def test_time_limit_truncation_is_not_termination(name):
    """Step each env with its episode clock forged to one tick below the
    cap: the step must end the episode (``done``) WITHOUT flagging a
    terminal (``terminated``) — from a reset state, one noop step cannot
    reach any env's real terminal condition."""
    env = envs_mod.make_env(name)
    state = env.reset(jax.random.key(0))
    state = state._replace(t=jnp.int32(env.max_steps - 1))
    state2, obs, r, d, term = env.step(state, jnp.int32(0),
                                       jax.random.key(2))
    assert bool(d) and not bool(term), name
    assert int(state2.t) == 0, name  # auto-reset started a fresh episode


def test_mountaincar_dynamics():
    env = envs_mod.make_env("mountaincar")
    s = env.reset(jax.random.key(0))
    assert s.x.shape == (2,)
    assert -0.6 <= float(s.x[0]) <= -0.4 and float(s.x[1]) == 0.0
    s2, obs, r, done, term = env.step(s, jnp.int32(2), jax.random.key(1))
    assert float(r) == -1.0 and not bool(done) and not bool(term)
    # pushing right from rest increases velocity minus gravity pull
    s3, _, _, _, _ = env.step(s, jnp.int32(0), jax.random.key(1))
    assert float(s2.x[1]) > float(s3.x[1])


def test_mountaincar_terminates_at_goal():
    env = envs_mod.make_env("mountaincar")
    s = env.reset(jax.random.key(0))
    s = s._replace(x=jnp.array([0.49, 0.07]))
    _, _, _, done, term = env.step(s, jnp.int32(2), jax.random.key(1))
    assert bool(done) and bool(term)


def test_mountaincar_velocity_and_position_bounds():
    env = envs_mod.make_env("mountaincar")
    s = env.reset(jax.random.key(3))
    for t in range(50):  # slam left: clamp at MIN_POS with vel reset to 0
        s, obs, _, _, _ = env.step(s, jnp.int32(0),
                                   jax.random.fold_in(jax.random.key(4), t))
        assert env.MIN_POS <= float(obs[0]) <= env.MAX_POS
        assert abs(float(obs[1])) <= env.MAX_SPEED + 1e-9


def test_breakout_brick_hit_scores_and_ball_bounces():
    env = envs_mod.make_env("breakout")
    s = env.reset(jax.random.key(0))
    # Place the ball just below the brick wall moving up into it.
    x = s.x.at[0].set(4.0).at[1].set(5.0).at[2].set(-1.0).at[3].set(1.0)
    s = s._replace(x=x)
    s2, obs, r, d, term = env.step(s, jnp.int32(0), jax.random.key(1))
    assert float(r) == 1.0 and not bool(d)
    assert float(jnp.sum(s2.x[5:])) == 29.0          # one brick cleared
    assert float(s2.x[2]) == 1.0                     # dy flipped downward


def test_breakout_missed_ball_terminates():
    env = envs_mod.make_env("breakout")
    s = env.reset(jax.random.key(0))
    # Ball one row above the bottom, paddle far away.
    x = s.x.at[0].set(8.0).at[1].set(1.0).at[2].set(1.0).at[3].set(1.0) \
        .at[4].set(9.0)
    s = s._replace(x=x)
    _, _, r, d, term = env.step(s, jnp.int32(0), jax.random.key(1))
    assert bool(d) and bool(term) and float(r) == 0.0


def test_freeway_scores_at_top_and_never_terminates():
    env = envs_mod.make_env("freeway")
    s = env.reset(jax.random.key(0))
    s = s._replace(x=s.x.at[0].set(1.0))  # one step below the goal row
    s2, obs, r, d, term = env.step(s, jnp.int32(1), jax.random.key(1))
    assert float(r) == 1.0 and not bool(term)
    assert float(s2.x[0]) == 9.0          # crossing restarts at the bottom


def _regenerate(path: str = GOLDEN) -> None:
    """Rewrite the fixture under the current PRNG stream (same actions)."""
    out = {}
    for name, fx in _FIXTURES.items():
        env = envs_mod.make_env(name)
        state = env.reset(jax.random.key(0))
        rec = {"reset_obs": np.asarray(env.obs(state)).tolist(),
               "actions": fx["actions"], "obs": [], "reward": [],
               "done": []}
        if "terminated" in fx:
            rec["terminated"] = []
        for t, a in enumerate(fx["actions"]):
            state, obs, r, d, term = env.step(
                state, jnp.int32(a), jax.random.fold_in(jax.random.key(1), t))
            rec["obs"].append(np.asarray(obs).tolist())
            rec["reward"].append(float(r))
            rec["done"].append(bool(d))
            if "terminated" in rec:
                rec["terminated"].append(bool(term))
        out[name] = rec
    with open(path, "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    _regenerate()
