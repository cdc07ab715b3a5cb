"""Vectorized actor pipeline: batched ring writes, VectorEnv, train_many,
and the unified sampler registry."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.replay_buffer import FrameStore, ReplayBuffer
from repro.core.samplers import Sampler, available_samplers, make_sampler
from repro.rl.dqn import DQNConfig, make_dqn
from repro.rl.envs import CartPole, VectorEnv

ALL_KINDS = ("uniform", "per-sumtree", "per-cumsum", "amper-fr", "amper-k")


# --- sampler registry --------------------------------------------------------

def test_registry_lists_all_builtins():
    assert set(ALL_KINDS) <= set(available_samplers())


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_registry_builds_protocol_instances(kind):
    s = make_sampler(kind, 128, v_max=4.0, min_csp=16)
    assert isinstance(s, Sampler)
    st = s.update(s.init(), jnp.arange(8), jnp.full(8, 0.5))
    idx = s.sample(st, jax.random.key(0), 16)
    assert idx.shape == (16,) and bool(jnp.all((idx >= 0) & (idx < 128)))
    assert s.priorities(st).shape == (128,)


def test_registry_unknown_kind_raises():
    with pytest.raises(ValueError, match="unknown sampler"):
        make_sampler("sorcery", 64)


def test_registry_ignores_foreign_hyperparams():
    # one unified kwargs dict must serve every kind
    s = make_sampler("per-sumtree", 64, m=20, lam_fr=2.0, csp_ratio=0.15,
                     v_max=8.0, min_csp=32)
    assert isinstance(s, Sampler)


# --- batched ring writes -----------------------------------------------------

def _tr(b, val=0.0):
    return {"obs": jnp.full((b, 3), val), "reward": jnp.arange(b, dtype=jnp.float32)}


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_add_batch_wraparound(kind):
    """B writes crossing `capacity` land storage AND priorities in the
    right ring slots for every sampler."""
    cap, b = 8, 5
    rb = ReplayBuffer(cap, make_sampler(kind, cap, v_max=4.0, min_csp=4))
    state = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
    state = rb.add_batch(state, _tr(b, val=1.0))          # slots 0..4
    assert int(state.pos) == 5 and int(state.size) == 5
    state = rb.add_batch(state, _tr(b, val=2.0))          # slots 5,6,7,0,1
    assert int(state.pos) == (5 + b) % cap
    assert int(state.size) == cap
    obs = np.asarray(state.storage["obs"][:, 0])
    np.testing.assert_array_equal(obs, [2, 2, 1, 1, 1, 2, 2, 2])
    rew = np.asarray(state.storage["reward"])
    np.testing.assert_array_equal(rew, [3, 4, 2, 3, 4, 0, 1, 2])
    # every live slot carries the max-priority write
    prios = np.asarray(rb.sampler.priorities(state.sampler_state))
    assert (prios > 0).all(), prios


def test_add_batch_priorities_in_right_slots():
    """After a wrapping write + a targeted priority update, the updated
    slots (and only they) change."""
    cap = 8
    rb = ReplayBuffer(cap, make_sampler("per-cumsum", cap))
    state = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
    state = rb.add_batch(state, _tr(6))
    state = rb.add_batch(state, _tr(4))    # wraps: slots 6,7,0,1
    state = rb.update_priorities(state, jnp.array([7, 1]), jnp.array([5.0, 9.0]))
    prios = np.asarray(rb.sampler.priorities(state.sampler_state))
    alpha_p = lambda td: (abs(td) + rb.eps) ** rb.alpha
    np.testing.assert_allclose(prios[7], alpha_p(5.0), rtol=1e-5)
    np.testing.assert_allclose(prios[1], alpha_p(9.0), rtol=1e-5)
    np.testing.assert_allclose(prios[[2, 3, 4, 5, 6, 0]], 1.0, rtol=1e-5)


def test_add_batch_matches_sequential_adds():
    cap, b = 16, 5
    rb = ReplayBuffer(cap, make_sampler("per-sumtree", cap))
    s_seq = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
    batch = _tr(b)
    for i in range(b):
        s_seq = rb.add(s_seq, jax.tree.map(lambda x: x[i], batch))
    s_bat = rb.add_batch(rb.init({"obs": jnp.zeros(3),
                                  "reward": jnp.float32(0)}), batch)
    for a, c in zip(jax.tree.leaves(s_seq), jax.tree.leaves(s_bat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-6)


def test_add_batch_larger_than_capacity_rejected():
    rb = ReplayBuffer(4, make_sampler("uniform", 4))
    state = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
    with pytest.raises(ValueError, match="exceeds capacity"):
        rb.add_batch(state, _tr(5))


# --- ring write: arc update vs the scatter it replaced -----------------------

RING_CAP = 8
N_STEP = 3


class _ScatterRing(ReplayBuffer):
    """The buffer with its ring rows written by one scatter over the arc,
    as before the dynamic-update-slice: the reference."""

    def _write_rows(self, rings, rows, pos, idx):
        b = idx.shape[0]
        return jax.tree.map(
            lambda buf, x: buf.at[(pos + jnp.arange(b)) % self.capacity]
            .set(x), rings, rows)


def _ring_example(ring):
    if ring == "frame":
        return {"frame": jnp.zeros((3, 3), jnp.uint8),
                "action": jnp.int32(0), "reward": jnp.float32(0),
                "done": jnp.float32(0)}
    return {"obs": jnp.zeros(3), "action": jnp.int32(0),
            "reward": jnp.float32(0), "next_obs": jnp.zeros(3),
            "done": jnp.float32(0)}


def _random_like(key, x, lead=()):
    x = jnp.asarray(x)
    shape = lead + x.shape
    if x.dtype == jnp.uint8:
        return jax.random.randint(key, shape, 0, 256).astype(jnp.uint8)
    if x.dtype == jnp.int32:
        return jax.random.randint(key, shape, -2**31, 2**31 - 1, jnp.int32)
    return jax.random.normal(key, shape, x.dtype)


def _random_tree(key, tree, lead=()):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten(
        [_random_like(k, x, lead) for k, x in zip(keys, leaves)])


@functools.lru_cache(maxsize=None)
def _ring_writers(ring, path, b):
    """(jitted new write, jitted reference write, full ring state, rows)
    for one case; the state's ``pos`` is set by the test, so one compile
    serves every position."""
    ex = _ring_example(ring)
    kw = {}
    if ring == "frame":
        kw["frame_store"] = FrameStore(history_len=2, frame_shape=(3, 3))
    if path == "nstep":
        kw.update(n_step=N_STEP, num_envs=b)
    rbs = [cls(RING_CAP, make_sampler("amper-fr", RING_CAP, v_max=4.0,
                                      min_csp=4), **kw)
           for cls in (ReplayBuffer, _ScatterRing)]
    k_ring, k_rows, k_nstep, k_misc = jax.random.split(jax.random.key(b), 4)
    state = rbs[0].init(ex)
    state = state._replace(
        storage=_random_tree(k_ring, ex, (RING_CAP,)),
        size=jnp.int32(RING_CAP),
        write_stamp=_random_like(k_misc, jnp.int32(0), (RING_CAP,)),
        write_gen=_random_like(jax.random.fold_in(k_misc, 1),
                               jnp.int32(0), (RING_CAP,)),
        total_adds=jnp.int32(2**31 - 2), add_gen=jnp.int32(5))
    if path == "nstep":
        nst = state.nstep
        state = state._replace(nstep=nst._replace(
            ring=_random_tree(k_nstep, ex, (N_STEP, b)),
            count=jnp.int32(N_STEP)))
    if path == "block":
        # b rows as a [T, B] block: two timesteps where b splits.
        t = 2 if b % 2 == 0 else 1
        rows = _random_tree(k_rows, ex, (t, b // t))
        writes = [jax.jit(functools.partial(rb.add_block, aggregated=True))
                  for rb in rbs]
    elif path == "vmap":
        # Two rings written at once, as train_many's seeds are.
        rows = _random_tree(k_rows, ex, (b,))
        state = jax.tree.map(lambda x: jnp.stack([x, x]), state)
        writes = [jax.jit(jax.vmap(rb.add_batch, in_axes=(0, None)))
                  for rb in rbs]
    else:
        rows = _random_tree(k_rows, ex, (b,))
        writes = [jax.jit(rb.add_batch) for rb in rbs]
    return writes[0], writes[1], state, rows


_RING_CASES = [(ring, path, b, pos)
               for ring in ("frame", "flat")
               for path in ("block", "batch", "nstep", "vmap")
               if not (ring == "frame" and path == "nstep")
               for b in (1, 3, RING_CAP)
               for pos in sorted({0, RING_CAP // 2, RING_CAP - b,
                                  (RING_CAP - b + 1) % RING_CAP,
                                  RING_CAP - 1})]


@pytest.mark.parametrize("ring,path,b,pos", _RING_CASES)
def test_ring_write_matches_arc_scatter(ring, path, b, pos):
    """Every leaf of the state after a write equals, bit for bit, the
    state the scatter over the ring arc gives: arcs that fit, arcs that
    cross the ring's end, and whole-ring writes, through add_block,
    add_batch, the n-step add_batch path and add_batch under vmap (two
    rings at different positions), on uint8 frame rings and flat float
    rings."""
    write, reference, state, rows = _ring_writers(ring, path, b)
    state = state._replace(pos=jnp.int32(pos) if path != "vmap" else
                           jnp.int32([pos, (pos + 3) % RING_CAP]))
    got, want = write(state, rows), reference(state, rows)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.array_equal(np.asarray(got.storage["reward"]),
                              np.asarray(state.storage["reward"]))


# --- VectorEnv ---------------------------------------------------------------

def test_vector_env_num_envs_1_matches_scalar():
    env = CartPole()
    venv = VectorEnv(env, 1)
    k_reset, k_step = jax.random.split(jax.random.key(0))
    vs = venv.reset(k_reset)
    ss = env.reset(jax.random.split(k_reset, 1)[0])
    np.testing.assert_allclose(np.asarray(venv.obs(vs)[0]),
                               np.asarray(env.obs(ss)))
    for t in range(50):
        k = jax.random.fold_in(k_step, t)
        a = jnp.int32(t % 2)
        vs, vobs, vr, vd, vterm = venv.step(vs, a[None], k)
        ss, sobs, sr, sd, sterm = env.step(ss, a, jax.random.split(k, 1)[0])
        np.testing.assert_allclose(np.asarray(vobs[0]), np.asarray(sobs),
                                   rtol=1e-6)
        assert bool(vd[0]) == bool(sd)
        assert bool(vterm[0]) == bool(sterm)
        np.testing.assert_allclose(np.asarray(venv.obs(vs)[0]),
                                   np.asarray(env.obs(ss)), rtol=1e-6)


def test_vector_env_independent_episodes():
    venv = VectorEnv(CartPole(), 8)
    state = venv.reset(jax.random.key(1))
    obs = venv.obs(state)
    assert obs.shape == (8, 4)
    # distinct reset keys -> distinct initial states
    assert len(np.unique(np.asarray(obs[:, 0]))) > 1
    state, next_obs, r, d, term = venv.step(
        state, jnp.zeros(8, jnp.int32), jax.random.key(2))
    assert next_obs.shape == (8, 4) and r.shape == (8,) and d.shape == (8,)


# --- batched agent + multi-seed sweep ---------------------------------------

def test_batched_agent_collects_b_frames_per_step():
    cfg = DQNConfig(num_envs=4, replay_size=64, learn_start=10**6)
    dqn = make_dqn(cfg)
    state, _ = dqn.train(jax.random.key(0), 5)
    assert int(state.buffer.size) == 20           # 5 iterations * 4 envs
    assert int(state.buffer.pos) == 20
    assert state.obs.shape == (4, 4)
    assert state.episode_return.shape == (4,)


def test_train_many_smoke():
    cfg = DQNConfig(num_envs=2, replay_size=256, learn_start=20,
                    eps_decay_steps=100)
    dqn = make_dqn(cfg)
    keys = jax.vmap(jax.random.key)(jnp.arange(3, dtype=jnp.uint32))
    states, metrics = dqn.train_many(keys, 60)
    # one leading seed axis everywhere, finite results, seeds differ
    assert metrics["return_mean"].shape == (3, 60)
    assert bool(jnp.all(jnp.isfinite(metrics["return_mean"])))
    p0 = jax.tree.leaves(states.params)[0]
    assert p0.shape[0] == 3
    assert not np.allclose(np.asarray(p0[0]), np.asarray(p0[1]))
    scores = dqn.evaluate_many(states, keys, 2)
    assert scores.shape == (3,) and bool(jnp.all(jnp.isfinite(scores)))
