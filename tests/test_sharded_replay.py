"""Sharded replay subsystem: sampling-law equivalence + mesh integration.

Differential/statistical harness for the mesh-native samplers (cf. Panahi
et al.: silently-divergent sampling distributions corrupt learning
results, so the sharded fronts must provably draw by the same law as
their single-device counterparts):

* every ``fr_mode`` (broadcast / interval / window / kernel / fused)
  produces bit-identical CSP membership, including invalid rows and
  saturated top-code priorities;
* ``fr_mode="fused"`` (the single-dispatch Pallas draw) returns
  bit-identical sampled indices AND importance weights vs "broadcast",
  on single-device and 2/8-shard meshes;
* ``ShardedAmperSampler`` membership == single-device ``build_csp_fr``
  exactly, on 1/2/8-shard meshes;
* ``ShardedPERSampler`` agrees with the PER law P(i) = p_i / sum p by
  chi-square, on 1/2/8-shard meshes;
* the empty-CSP fallback path draws uniformly with its own key (the
  correlated-key regression);
* registry + replay buffer + DQN integration on the mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.quantize as qz
from repro.core.amper import FR_MODES, AmperConfig, build_csp_fr
from repro.core.replay_buffer import ReplayBuffer
from repro.core.samplers import Sampler, available_samplers, make_sampler
from repro.launch.mesh import make_replay_mesh


def _mesh_of(n_shards):
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices")
    return make_replay_mesh(n_shards)


def _random_table(seed, n, v_max=1.0, saturate=True, invalidate=True):
    """Priorities incl. v_max-clipped (top-code) rows + invalid rows."""
    k = jax.random.key(seed)
    hi = v_max * (1.25 if saturate else 1.0)
    p = jax.random.uniform(jax.random.fold_in(k, 1), (n,), minval=0.0,
                           maxval=hi)
    valid = (jax.random.bernoulli(jax.random.fold_in(k, 2), 0.85, (n,))
             if invalidate else jnp.ones(n, bool))
    return qz.quantize(p, v_max), valid, p


# --- fr_mode equivalence -----------------------------------------------------


@pytest.mark.parametrize("seed,m,lam_fr", [
    (0, 8, 2.0), (1, 20, 2.0), (2, 2, 3.5), (3, 13, 0.3), (4, 20, 1.0),
])
def test_fr_modes_bit_identical(seed, m, lam_fr):
    """All fr_mode variants select the exact same CSP membership on
    randomized tables with invalid rows and saturated priorities."""
    n = 2048
    pq, valid, _ = _random_table(seed, n)
    key = jax.random.key(100 + seed)
    sel = {}
    for mode in FR_MODES:
        cfg = AmperConfig(capacity=n, m=m, lam_fr=lam_fr, v_max=1.0,
                          csp_capacity=n, fr_mode=mode)
        sel[mode] = np.asarray(build_csp_fr(pq, valid, key, cfg).selected)
    for mode in FR_MODES[1:]:
        np.testing.assert_array_equal(sel[mode], sel["broadcast"],
                                      err_msg=f"fr_mode={mode}")


def test_fr_mode_kernel_through_registry():
    """`make_sampler(..., fr_mode="kernel")` puts the fused Pallas search
    on the sampling hot path and still draws valid prioritized batches."""
    n = 4096
    _, _, p = _random_table(7, n, saturate=False, invalidate=False)
    s = make_sampler("amper-fr", n, v_max=1.0, fr_mode="kernel")
    st = s.update(s.init(), jnp.arange(n), p)
    idx = jax.jit(lambda k: s.sample(st, k, 4096))(jax.random.key(0))
    assert bool(jnp.all((idx >= 0) & (idx < n)))
    assert float(p[idx].mean()) > float(p.mean()) + 0.03


# --- sharded AMPER == single device ------------------------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 8])
@pytest.mark.parametrize("fr_mode", ["broadcast", "kernel", "fused"])
def test_sharded_amper_membership_exact(n_shards, fr_mode):
    """Sharded CSP membership is bit-identical to single-device
    build_csp_fr under the same key, for any shard count."""
    mesh = _mesh_of(n_shards)
    n = 2048
    pq, valid, p = _random_table(11, n)
    s = make_sampler("amper-fr-sharded", n, v_max=1.0, m=8,
                     fr_mode=fr_mode, mesh=mesh)
    st = s.update(s.init(), jnp.arange(n), jnp.where(valid, p, 0.0))
    # the sampler quantizes on update; compare against ITS stored table so
    # the reference sees identical inputs
    key = jax.random.key(21)
    got = np.asarray(s.membership(st, key))
    cfg = s.cfg
    expect = np.asarray(
        build_csp_fr(st.pq, st.valid, key, cfg._replace(fr_mode="broadcast")
                     ).selected)
    np.testing.assert_array_equal(got, expect)


@pytest.mark.parametrize("n_shards", [2, 8])
def test_sharded_amper_draws_within_membership(n_shards):
    """Every drawn index is a CSP member (same key): the count-prefix
    owner/offset selection never fabricates indices."""
    mesh = _mesh_of(n_shards)
    n = 1024
    pq, valid, p = _random_table(13, n)
    s = make_sampler("amper-fr-sharded", n, v_max=1.0, m=8, mesh=mesh)
    st = s.update(s.init(), jnp.arange(n), jnp.where(valid, p, 0.0))
    key = jax.random.key(5)
    members = np.asarray(s.membership(st, key))
    idx = np.asarray(s.sample(st, key, 512))
    assert members[idx].all(), "sampled a non-member row"


# --- fused draw: bit-identical indices AND weights (acceptance) ---------------


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_sharded_fused_sample_bit_identical(n_shards):
    """fr_mode='fused' (rank_select pick) draws the exact indices of the
    reference nonzero-compaction pick on real 1/2/8-shard meshes."""
    mesh = _mesh_of(n_shards)
    n = 2048
    pq, valid, p = _random_table(17, n)
    idx = {}
    for mode in ("broadcast", "fused"):
        s = make_sampler("amper-fr-sharded", n, v_max=1.0, m=8,
                         fr_mode=mode, mesh=mesh)
        st = s.update(s.init(), jnp.arange(n), jnp.where(valid, p, 0.0))
        idx[mode] = np.asarray(s.sample(st, jax.random.key(23), 256))
    np.testing.assert_array_equal(idx["fused"], idx["broadcast"])


def test_fused_replay_weights_bit_identical():
    """Acceptance: single-device replay buffer with fr_mode='fused' returns
    bit-identical sampled indices AND importance weights vs 'broadcast'
    (shared weight formula, importance_from_selected)."""
    cap, b = 4096, 512
    out = {}
    for mode in ("broadcast", "fused"):
        s = make_sampler("amper-fr", cap, v_max=4.0, fr_mode=mode)
        rb = ReplayBuffer(cap, s)
        state = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
        for i in range(3):
            state = rb.add_batch(
                state, {"obs": jnp.full((b, 3), float(i)),
                        "reward": jnp.arange(b, dtype=jnp.float32)})
        idx, _, w = rb.sample(state, jax.random.key(3), 64)
        out[mode] = (np.asarray(idx), np.asarray(w))
    np.testing.assert_array_equal(out["fused"][0], out["broadcast"][0])
    np.testing.assert_array_equal(out["fused"][1], out["broadcast"][1])


@pytest.mark.parametrize("n_shards", [2, 8])
def test_sharded_fused_replay_weights_bit_identical(n_shards):
    """Acceptance: same bitwise idx+weights guarantee through the sharded
    replay buffer on 2/8-shard meshes."""
    mesh = _mesh_of(n_shards)
    cap, b = 1024, 128
    out = {}
    for mode in ("broadcast", "fused"):
        s = make_sampler("amper-fr-sharded", cap, v_max=4.0,
                         fr_mode=mode, mesh=mesh)
        rb = ReplayBuffer(cap, s)
        state = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
        for i in range(3):
            state = rb.add_batch(
                state, {"obs": jnp.full((b, 3), float(i)),
                        "reward": jnp.arange(b, dtype=jnp.float32)})
        idx, _, w = rb.sample(state, jax.random.key(29), 64)
        out[mode] = (np.asarray(idx), np.asarray(w))
    np.testing.assert_array_equal(out["fused"][0], out["broadcast"][0])
    np.testing.assert_array_equal(out["fused"][1], out["broadcast"][1])


# --- sharded PER == single device (distribution) -----------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_sharded_per_chi_square(n_shards):
    """Empirical sharded-PER draw distribution agrees with the law
    P(i) = p_i / sum p (chi-square, generous threshold)."""
    mesh = _mesh_of(n_shards)
    n = 64
    p = jax.random.uniform(jax.random.key(3), (n,)) + 0.1
    s = make_sampler("per-sharded", n, mesh=mesh)
    st = s.update(s.init(), jnp.arange(n), p)
    draws = 1 << 14
    fn = jax.jit(lambda k: s.sample(st, k, draws))
    counts = np.zeros(n)
    n_reps = 4
    for r in range(n_reps):
        idx = np.asarray(fn(jax.random.key(50 + r)))
        counts += np.bincount(idx, minlength=n)
    total_draws = draws * n_reps
    expect = np.asarray(p / p.sum()) * total_draws
    chi2 = float(((counts - expect) ** 2 / expect).sum())
    df = n - 1
    # mean df, std sqrt(2 df); 6 sigma keeps the flake rate negligible
    assert chi2 < df + 6 * np.sqrt(2 * df), (chi2, df)


def test_sharded_per_matches_cumsum_counterpart():
    """Sharded and single-device PER empirical distributions agree with
    each other (two-sample comparison, same table)."""
    mesh = _mesh_of(8)
    n = 64
    p = jax.random.uniform(jax.random.key(4), (n,)) + 0.1
    sh = make_sampler("per-sharded", n, mesh=mesh)
    cs = make_sampler("per-cumsum", n)
    st_sh = sh.update(sh.init(), jnp.arange(n), p)
    st_cs = cs.update(cs.init(), jnp.arange(n), p)
    draws = 1 << 14
    c_sh = np.bincount(np.asarray(sh.sample(st_sh, jax.random.key(1), draws)),
                       minlength=n)
    c_cs = np.bincount(np.asarray(
        cs.sample(st_cs, jax.random.key(2), draws, stratified=False)),
        minlength=n)
    # both ~multinomial(draws, p/sum p): totals per row within noise
    diff = (c_sh - c_cs) / draws
    assert float(np.abs(diff).max()) < 0.02, diff


# --- empty-CSP fallback (correlated-key regression) --------------------------


def test_empty_csp_fallback_on_mesh():
    """All-invalid table -> uniform fallback draws: in range, well spread
    over every shard's segment."""
    mesh = _mesh_of(8)
    n = 1024
    s = make_sampler("amper-fr-sharded", n, v_max=1.0, mesh=mesh)
    st = s.init()  # nothing valid anywhere
    idx = np.asarray(s.sample(st, jax.random.key(0), 1024))
    assert ((idx >= 0) & (idx < n)).all()
    # every shard's 128-row segment receives draws
    seg_counts = np.bincount(idx // (n // 8), minlength=8)
    assert (seg_counts > 0).all(), seg_counts
    assert len(np.unique(idx)) > 512
    # distinct keys -> distinct fallback batches
    idx2 = np.asarray(s.sample(st, jax.random.key(1), 1024))
    assert not np.array_equal(idx, idx2)


def test_fallback_key_not_reused_for_pick():
    """Regression for the correlated-key bug (old sharded_sample_fr fed
    the SAME subkey to the CSP pick draw and the fallback draw): the
    fallback must not equal a draw from the pick subkey."""
    mesh = _mesh_of(8)
    n = 1024
    s = make_sampler("amper-fr-sharded", n, v_max=1.0, mesh=mesh)
    st = s.init()
    key = jax.random.key(9)
    idx = np.asarray(s.sample(st, key, 256))
    _, kpick = jax.random.split(key)
    buggy_fb = np.asarray(jax.random.randint(kpick, (256,), 0, n))
    assert not np.array_equal(idx, buggy_fb), \
        "fallback reproduced the pick-subkey draw — key reuse is back"


# --- registry / replay buffer / DQN integration ------------------------------


def test_registry_lists_sharded_kinds():
    assert {"amper-fr-sharded", "per-sharded"} <= set(available_samplers())


@pytest.mark.parametrize("kind", ["amper-fr-sharded", "per-sharded"])
def test_sharded_sampler_satisfies_protocol(kind, mesh):
    s = make_sampler(kind, 128, v_max=4.0, min_csp=16, mesh=mesh)
    assert isinstance(s, Sampler)
    st = s.update(s.init(), jnp.arange(8), jnp.full(8, 0.5))
    idx = s.sample(st, jax.random.key(0), 16)
    assert idx.shape == (16,) and bool(jnp.all((idx >= 0) & (idx < 128)))
    assert s.priorities(st).shape == (128,)
    assert float(s.total(st)) > 0


@pytest.mark.parametrize("kind", ["amper-fr-sharded", "per-sharded"])
def test_capacity_must_divide_shards(kind, mesh):
    with pytest.raises(ValueError, match="not divisible"):
        make_sampler(kind, 130, mesh=mesh)


@pytest.mark.parametrize("kind", ["amper-fr-sharded", "per-sharded"])
def test_replay_buffer_sharded_wraparound(kind, mesh):
    """Ring-arc writes + priority updates respect the shard layout; the
    storage stays partitioned like the priority table."""
    cap, b = 16, 10
    s = make_sampler(kind, cap, v_max=4.0, min_csp=4, mesh=mesh)
    rb = ReplayBuffer(cap, s)
    state = rb.init({"obs": jnp.zeros(3), "reward": jnp.float32(0)})
    assert state.storage["obs"].sharding == s.sharding
    tr = lambda val: {"obs": jnp.full((b, 3), val),
                      "reward": jnp.arange(b, dtype=jnp.float32)}
    state = rb.add_batch(state, tr(1.0))              # slots 0..9
    state = rb.add_batch(state, tr(2.0))              # slots 10..15, 0..3
    assert int(state.pos) == (2 * b) % cap and int(state.size) == cap
    obs = np.asarray(state.storage["obs"][:, 0])
    np.testing.assert_array_equal(
        obs, [2, 2, 2, 2, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 2, 2])
    assert state.storage["obs"].sharding == s.sharding
    prios = np.asarray(rb.sampler.priorities(state.sampler_state))
    assert (prios > 0).all()
    state = rb.update_priorities(state, jnp.array([3, 12]),
                                 jnp.array([5.0, 9.0]))
    p2 = np.asarray(rb.sampler.priorities(state.sampler_state))
    assert p2[3] != prios[3] and p2[12] != prios[12]
    idx, batch, w = rb.sample(state, jax.random.key(0), 8)
    assert batch["obs"].shape == (8, 3) and w.shape == (8,)


@pytest.mark.slow
def test_dqn_sharded_trains_cartpole(mesh):
    """Acceptance: amper-fr-sharded trains CartPole end-to-end on the
    8-device mesh, within tolerance of the single-device amper-fr run."""
    from repro.rl.dqn import DQNConfig, make_dqn

    scores = {}
    for sampler in ("amper-fr", "amper-fr-sharded"):
        cfg = DQNConfig(env="cartpole", sampler=sampler, replay_size=2000,
                        eps_decay_steps=3000, learn_start=200)
        dqn = make_dqn(cfg)
        state, _ = dqn.train(jax.random.key(0), 6000)
        scores[sampler] = float(dqn.evaluate(state, jax.random.key(9), 10))
    assert scores["amper-fr-sharded"] > 80, scores
    assert scores["amper-fr-sharded"] > 0.5 * scores["amper-fr"], scores


@pytest.mark.slow
def test_dqn_per_sharded_smoke(mesh):
    """per-sharded runs the same pipeline (importance weights included)."""
    from repro.rl.dqn import DQNConfig, make_dqn

    cfg = DQNConfig(env="cartpole", sampler="per-sharded", replay_size=2000,
                    eps_decay_steps=500, learn_start=100)
    dqn = make_dqn(cfg)
    state, metrics = dqn.train(jax.random.key(0), 1000)
    assert bool(jnp.all(jnp.isfinite(metrics["return_mean"])))
    assert float(dqn.evaluate(state, jax.random.key(1), 3)) > 0
