"""Async actor–learner runtime: PRNG stream discipline, strict-sync
equivalence with the scan trainer, deferred-feedback exactness and
staleness, block enqueue, stamped out-of-band priority updates, and the
environment registry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.replay_buffer import ReplayBuffer
from repro.core.samplers import make_sampler, masked_update
from repro.rl import envs as envs_mod
from repro.rl.dqn import DQNConfig, make_dqn
from repro.runtime import ReplayService, prng


# --- environment registry ----------------------------------------------------

def test_env_registry_builds_by_name():
    assert {"cartpole", "acrobot",
            "mountaincar"} <= set(envs_mod.available_envs())
    env = envs_mod.make_env("cartpole")
    assert env.obs_dim == 4 and env.n_actions == 2
    assert envs_mod.make_env("acrobot").obs_dim == 6
    assert envs_mod.make_env("mountaincar").obs_dim == 2


def test_env_registry_unknown_raises():
    with pytest.raises(ValueError, match="unknown env"):
        envs_mod.make_env("pong")


def test_env_registry_backcompat_alias():
    assert envs_mod.ENVS["cartpole"] is envs_mod.CartPole


# --- PRNG stream discipline --------------------------------------------------

def test_no_key_reuse_across_actors_and_prefetch():
    """Regression: every key any runtime thread consumes is distinct —
    across actors, across chunks within an actor, across prefetch draws,
    and across the actor/prefetch stream boundary."""
    key = jax.random.key(0)
    seen = set()

    def fingerprint(k):
        return tuple(np.asarray(jax.random.key_data(k)).ravel().tolist())

    for actor_id in range(4):
        k_reset, k_roll = prng.actor_keys(key, actor_id)
        for k in (k_reset, *(prng.chunk_key(k_roll, c) for c in range(3))):
            fp = fingerprint(k)
            assert fp not in seen, (actor_id, fp)
            seen.add(fp)
    for draw in range(6):
        fp = fingerprint(prng.sample_key(key, draw))
        assert fp not in seen, ("prefetch", draw)
        seen.add(fp)


# --- block enqueue + stamped out-of-band priority updates --------------------

def _block(t, b, obs_dim=3):
    n = t * b
    return {
        "obs": jnp.arange(n * obs_dim, dtype=jnp.float32).reshape(t, b, obs_dim),
        "reward": jnp.arange(n, dtype=jnp.float32).reshape(t, b),
    }


def test_add_block_matches_sequential_add_batch():
    rb = ReplayBuffer(32, make_sampler("per-cumsum", 32))
    example = {"obs": jnp.zeros(3), "reward": jnp.float32(0)}
    block = _block(t=3, b=4)
    s_blk = rb.add_block(rb.init(example), block)
    s_seq = rb.init(example)
    for t in range(3):
        s_seq = rb.add_batch(s_seq, jax.tree.map(lambda x: x[t], block))
    for a, b_ in zip(jax.tree.leaves(s_blk), jax.tree.leaves(s_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_))


def test_write_stamps_track_global_add_counter():
    rb = ReplayBuffer(8, make_sampler("uniform", 8))
    state = rb.init({"x": jnp.float32(0)})
    assert int(state.total_adds) == 0
    assert (np.asarray(state.write_stamp) == -1).all()
    state = rb.add_batch(state, {"x": jnp.zeros(6)})
    state = rb.add_batch(state, {"x": jnp.zeros(4)})   # wraps: 6,7,0,1
    np.testing.assert_array_equal(
        np.asarray(state.write_stamp), [8, 9, 2, 3, 4, 5, 6, 7])
    assert int(state.total_adds) == 10


def test_stamped_update_drops_recycled_slots():
    """A deferred priority update whose slot was overwritten since the
    sample must not clobber the newcomer's (max-priority) entry."""
    rb = ReplayBuffer(8, make_sampler("per-cumsum", 8))
    state = rb.init({"x": jnp.float32(0)})
    state = rb.add_batch(state, {"x": jnp.zeros(6)})
    idx = jnp.array([0, 5])
    stamp = rb.stamps(state, idx)     # sample-time (counter, gen) pairs
    np.testing.assert_array_equal(np.asarray(stamp), [[0, 0], [5, 0]])
    state = rb.add_batch(state, {"x": jnp.zeros(4)})    # recycles slot 0
    state = rb.update_priorities(
        state, idx, jnp.array([5.0, 9.0]), stamp=stamp)
    prios = np.asarray(rb.sampler.priorities(state.sampler_state))
    alpha_p = lambda td: (abs(td) + rb.eps) ** rb.alpha
    # slot 5 still holds its sampled transition -> updated
    np.testing.assert_allclose(prios[5], alpha_p(9.0), rtol=1e-5)
    # slot 0 was recycled -> keeps the newcomer's max-priority write
    np.testing.assert_allclose(prios[0], 1.0, rtol=1e-5)
    # max_priority tracks only the valid rows
    np.testing.assert_allclose(
        float(state.max_priority), max(1.0, alpha_p(9.0)), rtol=1e-5)


def test_add_counter_rollover_bumps_generation():
    """Drive real add_batch calls across the signed-int32 boundary: the
    generation word increments exactly at the rollover, per-row stamps
    keep their wrapping values, and the (counter, gen) pair stays
    monotone in lexicographic order."""
    rb = ReplayBuffer(8, make_sampler("uniform", 8))
    state = rb.init({"x": jnp.float32(0)})
    state = state._replace(total_adds=jnp.int32(2**31 - 3))
    state = rb.add_batch(state, {"x": jnp.zeros(6)})    # 3 pre, 3 post wrap
    assert int(state.add_gen) == 1
    np.testing.assert_array_equal(
        np.asarray(state.write_stamp[:6]),
        np.array([2**31 - 3, 2**31 - 2, 2**31 - 1,
                  -(2**31), -(2**31) + 1, -(2**31) + 2], np.int64))
    np.testing.assert_array_equal(np.asarray(state.write_gen[:6]),
                                  [0, 0, 0, 1, 1, 1])
    assert int(state.total_adds) == -(2**31) + 3        # wrapped counter


def test_stamp_equality_is_wrap_safe_across_generations():
    """A slot recycled an exact multiple of 2^32 adds after the sample
    repeats its int32 counter word; only the generation word tells the
    writes apart.  The single-word comparison this replaces would
    false-accept the stale feedback and clobber the newcomer."""
    rb = ReplayBuffer(8, make_sampler("per-cumsum", 8))
    state = rb.init({"x": jnp.float32(0)})
    state = rb.add_batch(state, {"x": jnp.zeros(6)})
    idx = jnp.array([0, 5])
    stale = rb.stamps(state, idx)                       # gen-0 stamps
    # Forge the 2^32-adds-later recycling: same counter words, bumped
    # generation on slot 0 (as a full lap of _write_arc would produce).
    state = state._replace(
        write_gen=state.write_gen.at[0].set(1), add_gen=jnp.int32(1))
    state = rb.update_priorities(
        state, idx, jnp.array([5.0, 9.0]), stamp=stale)
    prios = np.asarray(rb.sampler.priorities(state.sampler_state))
    alpha_p = lambda td: (abs(td) + rb.eps) ** rb.alpha
    # slot 5 kept its generation -> the update lands
    np.testing.assert_allclose(prios[5], alpha_p(9.0), rtol=1e-5)
    # slot 0's counter matches but its generation moved on -> dropped
    np.testing.assert_allclose(prios[0], 1.0, rtol=1e-5)


def test_masked_update_is_noop_where_invalid():
    s = make_sampler("per-sumtree", 16)
    st = s.update(s.init(), jnp.arange(4), jnp.array([1.0, 2.0, 3.0, 4.0]))
    st2 = masked_update(s, st, jnp.array([1, 2]), jnp.array([9.0, 9.0]),
                        jnp.array([True, False]))
    prios = np.asarray(s.priorities(st2))
    np.testing.assert_allclose(prios[:4], [1.0, 9.0, 3.0, 4.0], rtol=1e-6)


@pytest.mark.parametrize("kind", ["per-cumsum", "per-sumtree", "uniform"])
def test_masked_update_duplicates_last_occurrence_wins(kind):
    """Priority draws are with replacement, so deferred feedback can hit
    the same row several times in one apply; sequential last-write-wins
    semantics must hold regardless of the backend's scatter winner."""
    s = make_sampler(kind, 8)
    st = s.update(s.init(), jnp.arange(8), jnp.full(8, 1.0))
    idx = jnp.array([3, 5, 3, 3, 5])
    pri = jnp.array([10.0, 20.0, 30.0, 40.0, 50.0])
    valid = jnp.array([True, True, True, True, True])
    prios = np.asarray(s.priorities(masked_update(s, st, idx, pri, valid)))
    np.testing.assert_allclose(prios[3], 40.0, rtol=1e-6)   # last write to 3
    np.testing.assert_allclose(prios[5], 50.0, rtol=1e-6)   # last write to 5
    # a trailing invalid duplicate must not clobber a valid earlier write
    prios2 = np.asarray(s.priorities(masked_update(
        s, st, jnp.array([3, 3]), jnp.array([10.0, 99.0]),
        jnp.array([True, False]))))
    np.testing.assert_allclose(prios2[3], 10.0, rtol=1e-6)


# --- n-step accumulator ------------------------------------------------------

def _nstep_reference(trs, n, gamma):
    """Hand-rolled n-step aggregation over a [T] list of per-env dicts:
    for each window start t (t + n <= T), the discounted return truncated
    at the first done, the bootstrap obs, and the any-done flag."""
    out = []
    for t in range(len(trs) - n + 1):
        w = trs[t:t + n]
        reward, cont = 0.0, 1.0
        h = n - 1
        for k in range(n):
            reward += (gamma ** k) * cont * w[k]["reward"]
            if w[k]["done"] > 0.5:
                h = k
                cont = 0.0
                break
        done = 1.0 if cont == 0.0 else 0.0
        out.append({"obs": w[0]["obs"], "action": w[0]["action"],
                    "reward": reward, "next_obs": w[h]["next_obs"],
                    "done": done})
    return out


def test_nstep_accumulator_matches_reference():
    from repro.core.replay_buffer import NStepAccumulator

    n, gamma, T, E = 3, 0.9, 12, 2
    rng = np.random.default_rng(0)
    acc = NStepAccumulator(n, gamma)
    ex = {"obs": jnp.zeros(2), "action": jnp.int32(0),
          "reward": jnp.float32(0), "next_obs": jnp.zeros(2),
          "done": jnp.float32(0)}
    st = acc.init(ex, E)
    stream = []          # per timestep: [E] transition batch
    for t in range(T):
        stream.append({
            "obs": rng.normal(size=(E, 2)).astype(np.float32),
            "action": rng.integers(0, 2, E).astype(np.int32),
            "reward": rng.normal(size=E).astype(np.float32),
            "next_obs": rng.normal(size=(E, 2)).astype(np.float32),
            "done": (rng.random(E) < 0.3).astype(np.float32)})
    emitted = []
    for t in range(T):
        st, out, valid = acc.push(st, jax.tree.map(jnp.asarray, stream[t]))
        assert bool(valid) == (t >= n - 1)
        if valid:
            emitted.append(jax.tree.map(np.asarray, out))
    for e in range(E):
        per_env = [{k: v[e] for k, v in tr.items()} for tr in stream]
        ref = _nstep_reference(per_env, n, gamma)
        assert len(ref) == len(emitted)
        for i, r in enumerate(ref):
            for k in ("obs", "action", "reward", "next_obs", "done"):
                np.testing.assert_allclose(
                    np.asarray(emitted[i][k])[e], r[k], rtol=1e-5,
                    atol=1e-6, err_msg=f"env {e} window {i} field {k}")


def test_nstep_add_block_matches_sequential_add_batch():
    """Raw-block ingestion must scan the accumulator exactly like T
    sequential vectorized add_batch calls (and skip warm-up rows)."""
    rb = ReplayBuffer(64, make_sampler("per-cumsum", 64), n_step=3,
                      gamma=0.95, num_envs=4)
    ex = {"obs": jnp.zeros(3), "reward": jnp.float32(0),
          "next_obs": jnp.zeros(3), "action": jnp.int32(0),
          "done": jnp.float32(0)}
    key = jax.random.key(0)
    block = {
        "obs": jax.random.normal(jax.random.fold_in(key, 0), (6, 4, 3)),
        "reward": jax.random.normal(jax.random.fold_in(key, 1), (6, 4)),
        "next_obs": jax.random.normal(jax.random.fold_in(key, 2), (6, 4, 3)),
        "action": jnp.zeros((6, 4), jnp.int32),
        "done": (jax.random.uniform(jax.random.fold_in(key, 3),
                                    (6, 4)) < 0.2).astype(jnp.float32)}
    s_blk = rb.add_block(rb.init(ex), block)
    # Both sides compiled: XLA:CPU contracts the n-step return's
    # multiply into its reduction as an FMA inside a fused program, which
    # op-by-op dispatch does not, so eager add_batch calls would differ
    # from the scanned block by one ulp.
    add_batch = jax.jit(rb.add_batch)
    s_seq = rb.init(ex)
    for t in range(6):
        s_seq = add_batch(s_seq, jax.tree.map(lambda x: x[t], block))
    assert int(s_blk.size) == 4 * 4  # 2 warm-up steps emitted nothing
    for a, b_ in zip(jax.tree.leaves(s_blk), jax.tree.leaves(s_seq)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_nstep_add_batch_rejects_wrong_width():
    rb = ReplayBuffer(32, make_sampler("uniform", 32), n_step=2, num_envs=4)
    ex = {"obs": jnp.zeros(2), "reward": jnp.float32(0),
          "next_obs": jnp.zeros(2), "action": jnp.int32(0),
          "done": jnp.float32(0)}
    st = rb.init(ex)
    with pytest.raises(ValueError, match="num_envs"):
        rb.add_batch(st, jax.tree.map(
            lambda x: jnp.zeros((3,) + jnp.shape(x), jnp.asarray(x).dtype),
            ex))


# --- strict-sync equivalence -------------------------------------------------

def test_sync_requires_single_actor():
    with pytest.raises(ValueError, match="sync mode"):
        ReplayService(DQNConfig(), sync=True, num_actors=2)


@pytest.mark.parametrize("agent,n_step", [("dqn", 1), ("double", 3),
                                          ("dueling", 2)])
def test_sync_service_matches_scan_trainer(agent, n_step):
    """`ReplayService(sync=True, num_actors=1)` reproduces the lax.scan
    trainer's CartPole learning curve (and final params) within float
    tolerance — the strict synchronous mode is the scan trainer, across
    the whole agent family including n-step replay (acceptance pin)."""
    cfg = DQNConfig(agent=agent, n_step=n_step, num_envs=1, replay_size=512,
                    batch=32, learn_start=100, eps_decay_steps=500,
                    target_sync=50)
    key = jax.random.key(0)
    n = 300
    dqn = make_dqn(cfg)
    state, metrics = dqn.train(key, n)
    res = ReplayService(cfg, sync=True, num_actors=1).run(key, n)
    np.testing.assert_allclose(
        np.asarray(metrics["return_mean"]), res.metrics["return_curve"],
        rtol=1e-4, atol=1e-4)
    assert res.metrics["learner_steps"] == n - cfg.learn_start
    # Sync mode is the scan trainer's iteration run step by step: its
    # params equal the jitted agent step looped on the host, bitwise.
    step = jax.jit(dqn.agent_step)
    looped = dqn.init(key)
    for k in jax.random.split(jax.random.fold_in(key, 1), n):
        looped, _ = step(looped, k)
    for a, b in zip(jax.tree.leaves(looped.params),
                    jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Against the scan trainer itself the params are compared a few
    # learner steps in.  XLA fuses the scan's loop body and the
    # standalone step differently, and the two fusions round differently
    # (the first learner step already differs by ulps).  The priority
    # draw is discontinuous in the stored priorities, so after hundreds
    # of steps those ulps become different sampled rows.
    k_steps = cfg.learn_start + 5
    state_k, _ = dqn.train(key, k_steps)
    res_k = ReplayService(cfg, sync=True, num_actors=1).run(key, k_steps)
    for a, b in zip(jax.tree.leaves(state_k.params),
                    jax.tree.leaves(res_k.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --- async mode: deferred feedback contract ----------------------------------

@pytest.mark.parametrize("sampler,agent,n_step",
                         [("per-sumtree", "dqn", 1),
                          ("amper-fr", "dqn", 1),
                          ("amper-fr", "double", 3)])
def test_async_feedback_exactly_once_in_order(sampler, agent, n_step):
    """Every learner batch's deferred priority update is applied exactly
    once, in learner-step order, with non-negative measured staleness —
    including with per-actor n-step aggregation in the rollout path."""
    cfg = DQNConfig(sampler=sampler, agent=agent, n_step=n_step,
                    num_envs=2, replay_size=256, batch=16,
                    learn_start=8, eps_decay_steps=200, target_sync=50,
                    v_max=8.0)
    svc = ReplayService(cfg, num_actors=2, chunk_len=4, slab=2,
                        queue_size=4, max_replay_ratio=64,
                        feedback_log=True)
    res = svc.run(jax.random.key(1), 20)
    m = res.metrics
    assert m["learner_steps"] == 20
    assert m["feedback_seqs"] == list(range(20)), m["feedback_seqs"]
    assert m["staleness"]["count"] == 20
    assert 0 <= m["staleness"]["mean"] <= m["staleness"]["max"]
    assert m["frames"] > 0 and int(res.buffer.size) > 0
    # evaluate accepts the bare params the runtime returns
    score = float(svc.dqn.evaluate(res.params, jax.random.key(2), 2))
    assert np.isfinite(score)
    for leaf in jax.tree.leaves(res.params):
        assert bool(jnp.all(jnp.isfinite(leaf)))


# --- metrics / durability satellites -----------------------------------------


def test_check_meta_missing_key_is_loud():
    """A checkpoint written before a topology field existed must be
    rejected, not silently accepted (.get(k, want) would pass it)."""
    ok = {"mode": "async", "num_actors": 2}
    ReplayService._check_meta(ok, "async", num_actors=2)
    with pytest.raises(ValueError, match="mode"):
        ReplayService._check_meta({"mode": "sync"}, "async")
    with pytest.raises(ValueError, match="num_actors"):
        ReplayService._check_meta({"mode": "async"}, "async", num_actors=2)
    with pytest.raises(ValueError, match="num_actors=3"):
        ReplayService._check_meta({"mode": "async", "num_actors": 3},
                                  "async", num_actors=2)


def test_prefetch_beta_not_published_for_a_draw_that_never_happened():
    """last_beta is the β of the latest *completed* slab draw: a draw
    that raises must leave it untouched (it was being set before the
    sample call, so metrics could report a β no slab ever used)."""
    import queue
    import threading
    from types import SimpleNamespace

    from repro.runtime.pipeline import PrefetchPipeline

    state = SimpleNamespace(size=jnp.int32(64))

    def failing_sample(st, key, beta):
        raise RuntimeError("sampler exploded")

    stop = threading.Event()
    p = PrefetchPipeline(failing_sample, lambda: (state, 0),
                         out_q=queue.Queue(2), stop=stop,
                         base_key=jax.random.key(0), slab=2, min_size=1,
                         beta_fn=lambda v: 0.7)
    p.start()
    p.join(timeout=10.0)
    assert not p.is_alive()
    assert isinstance(p.error, RuntimeError)
    assert p.last_beta is None  # no completed draw -> no published beta
    assert p.draws == 0
    stop.set()
