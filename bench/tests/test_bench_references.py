"""Each plain reference against the program at a small size (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drive import Keep
from bench.reference import frames as ref_frames
from bench.reference import sampler_amper_fr as amper
from bench.reference import sampler_amper_fr_sharded as amper_sh
from bench.reference import sampler_per_sumtree as sumtree
from bench.reference import td_loss
from bench.reference import writeback


def _prios(n, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    p = jnp.exp(-1.5 + jax.random.normal(k1, (n,)))
    return jnp.where(jax.random.uniform(k2, (n,)) > 0.1, p, 0.0)


@pytest.mark.parametrize("n,batch", [(4096, 32), (8192, 256)])
def test_amper_fr_law_matches_sampler(n, batch):
    from repro.core.samplers import make_sampler

    s = make_sampler("amper-fr", n, m=20, lam_fr=2.0, csp_ratio=0.15,
                     v_max=8.0, min_csp=batch)
    st = s.update(s.init(), jnp.arange(n), _prios(n))
    pq, valid = np.asarray(st.pq), np.asarray(st.valid)
    for i in range(4):
        key = jax.random.key(100 + i)
        got = np.asarray(s.sample(st, key, batch))
        want = amper.draw(pq, valid, key, batch, m=20, lam_fr=2.0,
                          v_max=8.0, frac_bits=24,
                          csp_capacity=s.cfg.csp_capacity)
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(
        amper.priorities(pq, valid, v_max=8.0, frac_bits=24),
        np.asarray(s.priorities(st)), rtol=1e-6)


def test_sharded_law_matches_sharded_sampler():
    from repro.core.samplers import make_sampler
    from repro.launch.mesh import make_replay_mesh

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    n, batch, shards = 4096, 64, 2
    mesh = make_replay_mesh(shards)
    s = make_sampler("amper-fr-sharded", n, m=20, lam_fr=2.0,
                     csp_ratio=0.15, v_max=8.0, min_csp=batch, mesh=mesh)
    st = s.update(s.init(), jnp.arange(n), _prios(n, 1))
    pq, valid = np.asarray(st.pq), np.asarray(st.valid)
    for i in range(3):
        key = jax.random.key(7 + i)
        got = np.asarray(jax.jit(lambda st, k: s.sample(st, k, batch))(
            st, key))
        want = amper_sh.draw(pq, valid, key, batch, m=20, lam_fr=2.0,
                             v_max=8.0, frac_bits=24,
                             csp_capacity=s.cfg.csp_capacity, shards=shards)
        np.testing.assert_array_equal(got, want)


def test_sumtree_draws_sit_in_their_intervals():
    from repro.core.per import SumTreePER

    n, batch = 5000, 256
    s = SumTreePER(n)
    p = _prios(n, 2)
    st = s.update(s.init(), jnp.arange(n), p)
    leaves = np.asarray(s.priorities(st), np.float64)
    for i in range(3):
        key = jax.random.key(i)
        idx = np.asarray(s.sample(st, key, batch))
        assert sumtree.mass_gap(leaves, key, idx) < 1e-6
        # A draw shifted by one row misses its target.
        assert sumtree.mass_gap(leaves, key, (idx + 1) % n) > 1e-6
        # float64 sums and the tree's float32 ones part at a boundary now
        # and then.
        assert np.mean(sumtree.draw(leaves, key, batch) == idx) > 0.98


def test_weights_match_buffer_formula():
    from repro.core.per import importance_from_selected

    p = np.asarray(_prios(1000, 3))
    idx = np.arange(0, 1000, 7)
    got = np.asarray(importance_from_selected(
        jnp.asarray(p)[idx], jnp.sum(jnp.asarray(p)), jnp.int32(900), 0.4))
    np.testing.assert_allclose(amper.weights(p, idx, 900, 0.4), got,
                               rtol=1e-5)


def _frame_buffer(n_step):
    from repro.core.replay_buffer import FrameStore, ReplayBuffer
    from repro.core.samplers import make_sampler
    from bench import generator

    cap, envs = 512, 4
    fs = FrameStore(history_len=4, frame_shape=(10, 10), stride=envs,
                    n_step=n_step, gamma=0.9)
    rb = ReplayBuffer(cap, make_sampler("per-sumtree", cap), frame_store=fs,
                      num_envs=envs)
    ex = {"frame": jnp.zeros((10, 10), jnp.uint8), "action": jnp.int32(0),
          "reward": jnp.float32(0), "done": jnp.float32(0),
          "terminated": jnp.float32(0)}
    p = {"episode_len": [3, 12], "reward_rate": 0.3, "pixel_density": 0.2}
    st = rb.init(ex)
    for i in range(2):   # 800 rows: the second block wraps the ring
        st = rb.add_block(st, generator.steps_block(
            jax.random.key(5 + i), ex, 100, envs, p, 3))
    return rb, st, fs


@pytest.mark.parametrize("n_step", [1, 3])
def test_frame_stacks_match_materialize(n_step):
    rb, st, fs = _frame_buffer(n_step)
    idx = jnp.arange(0, 512, 3)
    got = jax.jit(rb.materialize)(st, idx)
    # The reference reads only the rows the harness keeps around idx.
    view = Keep(rb).view(st, idx)
    want = ref_frames.materialize(
        view["rows"], np.asarray(idx), int(view["size"]),
        capacity=rb.capacity, history_len=4, stride=fs.stride,
        n_step=n_step, gamma=0.9, scale=fs.scale)
    assert ref_frames.gap({k: np.asarray(v) for k, v in got.items()},
                          want) < 1e-6
    # Some stacks are cut by episode ends and the write head.
    assert np.any(want["obs"][..., 0] == 0) and np.any(want["terminated"])


def test_td_loss_reference_follows_dqn_learn():
    from repro.rl.dqn import DQNConfig, make_dqn

    cfg = DQNConfig(env="breakout", sampler="per-sumtree", batch=8,
                    replay_size=512, num_envs=4, lr=1e-3)
    dqn = make_dqn(cfg)
    key = jax.random.key(3)
    st = dqn.init(key)
    p0 = td_loss.init(key, 4, (10, 10), cfg.hidden, 3)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(st.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    k = jax.random.split(jax.random.key(4), 5)
    s, b = 3, 8
    batch = {"obs": jax.random.uniform(k[0], (s, b, 10, 10, 4)),
             "next_obs": jax.random.uniform(k[1], (s, b, 10, 10, 4)),
             "action": jax.random.randint(k[2], (s, b), 0, 3),
             "reward": jax.random.bernoulli(k[3], 0.3, (s, b)).astype(
                 jnp.float32),
             "terminated": jnp.zeros((s, b))}
    w = jax.random.uniform(k[4], (s, b))
    from repro.runtime.learner import make_slab_learner

    params, m, v, td, loss = jax.jit(make_slab_learner(dqn))(
        st.params, st.params, st.opt_m, st.opt_v, jnp.int32(0), batch, w)
    loss_r, td_r, par_r, g0 = td_loss.follow(p0, batch, w,
                                             gamma_n=cfg.gamma, lr=cfg.lr)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(loss_r),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(td), np.asarray(td_r),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(par_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("stamped", [False, True])
def test_writeback_law_matches_update_priorities(stamped):
    from repro.core.replay_buffer import ReplayBuffer
    from repro.core.samplers import make_sampler

    cap = 256
    rb = ReplayBuffer(cap, make_sampler("per-sumtree", cap), alpha=0.6)
    st = rb.add_batch(rb.init({"x": jnp.float32(0)}),
                      {"x": jnp.zeros(cap)})
    idx = jnp.asarray(np.r_[np.arange(40), np.arange(10)], jnp.int32)
    td = jax.random.normal(jax.random.key(1), (50,))
    stamp = rb.stamps(st, idx) if stamped else None
    if stamped:  # rows 0-4 recycled since the draw
        st = st._replace(write_stamp=st.write_stamp.at[:5].add(cap))
    after = rb.update_priorities(st, idx, td, stamp=stamp)
    before = np.asarray(rb.sampler.priorities(st.sampler_state), np.float64)
    live = None
    if stamped:
        live = ((np.asarray(st.write_stamp)[idx] == np.asarray(stamp)[:, 0])
                & (np.asarray(st.write_gen)[idx] == np.asarray(stamp)[:, 1]))
    want, allowed = writeback.new_priorities(
        before, np.asarray(idx), np.abs(np.asarray(td)), alpha=0.6,
        eps=rb.eps, live=live)
    got = np.asarray(rb.sampler.priorities(after.sampler_state))
    assert writeback.gap(got, want, allowed, want.max()) < 1e-6
    if stamped:
        np.testing.assert_array_equal(got[:5], before[:5])
