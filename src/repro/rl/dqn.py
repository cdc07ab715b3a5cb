"""DQN agent *family* with pluggable experience replay (the paper's test
vehicle, grown to the variants the PER literature reports on).

Architecture follows the paper's setup (Sec. 2.4 / 4.1.2) — epsilon-greedy
exploration, hard target sync, replay memory with uniform / PER /
AMPER-k / AMPER-fr sampling — but the agent layer is composable along
three orthogonal axes, all selected from :class:`DQNConfig` with zero
call-site changes:

* **Q-head** (``repro.models.qhead``): the 3-layer MLP of the paper, or
  the dueling value/advantage decomposition (Wang et al. 2016).  Pixel
  envs (``len(obs_shape) > 1``) promote these to their conv
  counterparts automatically.
* **Target rule**: vanilla ``max_a Q_target`` or Double-DQN's
  argmax-decoupled ``Q_target(s', argmax_a Q_online(s', a))``
  (van Hasselt et al. 2016) — the setup Schaul et al. report PER on.
* **n-step returns** (``n_step=N``): the replay stack itself aggregates
  the 1-step stream into truncated n-step transitions (the accumulator
  lives in :class:`~repro.core.replay_buffer.ReplayState`, so it rides
  through checkpoints), and the learner bootstraps with ``gamma**N``.

``agent="dqn" | "double" | "dueling" | "double-dueling"`` composes the
first two axes.  The ENTIRE loop — environment, replay, sampling, TD
update — is one lax.scan, so a full CartPole run takes seconds on CPU.

Observation contract: agents are built from the env's ``obs_shape``
(``(obs_dim,)`` for the classic-control envs, ``(H, W)`` for the pixel
envs).  Pixel envs switch the replay buffer to frame-deduplicated uint8
storage (:class:`~repro.core.replay_buffer.FrameStore`): each step
stores ONE raw frame, the buffer materializes ``history_len``-stacked
float batches at sample time, and the actor maintains the same uint8
stack as its policy input — both sides convert with the identical
``frame * scale`` expression, so materialized training observations are
bit-identical to what the policy saw.

TD targets bootstrap on ``terminated``, not ``done``: an episode cut by
the env's time limit (``done`` without ``terminated``) is not a real
terminal state, and zeroing its bootstrap would bias Q toward the
truncation horizon on every step-capped env.  The frame path stores no
pre-reset observation, so there ``terminated`` collapses to ``done``
(see the replay-buffer module docstring).

The actor side is batched: ``cfg.num_envs`` independent environments
step in lockstep (``VectorEnv``), every iteration writes a B-transition
arc into the replay ring (`ReplayBuffer.add_batch`) and the samplers
absorb the B priority writes as one batched scatter.  ``num_envs=1``
reproduces the scalar pipeline exactly.  ``train_many`` vmaps the whole
training run over a batch of seeds for sweep-style evaluation.

Scheduling note: ``learn_start`` / ``train_every`` / ``target_sync`` /
``eps_decay_steps`` count scan ITERATIONS, not frames — with B envs each
iteration collects B frames, so one gradient step amortises over B
transitions (the standard vectorized-actor replay ratio).

PER uses importance-sampling weights; AMPER samples uniformly from its
CSP (per the paper) so its weights are 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.per import beta_schedule
from repro.core.replay_buffer import FrameStore, ReplayBuffer
from repro.core.samplers import make_sampler
from repro.models.qhead import make_qhead, mlp_apply, mlp_init  # noqa: F401
from repro.rl import envs as envs_mod
from repro.train import checkpoint as ckpt_mod

RETURN_RING = 64  # completed-episode returns kept for the train metric

# agent name -> (Q-head kind, use Double-DQN targets); pixel envs promote
# the head kind to its conv counterpart.
AGENTS = {
    "dqn": ("mlp", False),
    "double": ("mlp", True),
    "dueling": ("dueling", False),
    "double-dueling": ("dueling", True),
}

_CONV_PROMOTION = {"mlp": "conv", "dueling": "conv-dueling"}


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    env: str = "cartpole"
    sampler: str = "per-sumtree"   # any repro.core.samplers registry name
    agent: str = "dqn"             # dqn | double | dueling | double-dueling
    n_step: int = 1                # n-step return horizon (1 = classic)
    num_envs: int = 1
    replay_size: int = 2000
    batch: int = 64
    hidden: int = 128
    history_len: int = 4           # frames per stacked pixel observation
    gamma: float = 0.99
    lr: float = 1e-3
    eps_start: float = 1.0
    eps_end: float = 0.05
    eps_decay_steps: int = 5000
    target_sync: int = 100
    learn_start: int = 200
    train_every: int = 1
    alpha: float = 0.6
    beta: float = 0.4
    # IS-exponent annealing (Schaul et al.: β→1 over training).  beta_end
    # None keeps the constant-β behaviour; beta_anneal_steps None uses
    # eps_decay_steps as the horizon.  Steps are scan iterations for the
    # sync trainers and learner steps for the async runtime.
    beta_end: float | None = None
    beta_anneal_steps: int | None = None
    # AMPER hyper-parameters (paper defaults: m=20, CSP ratio 0.15)
    amper_m: int = 20
    amper_lam_fr: float = 2.0
    amper_csp_ratio: float = 0.15
    v_max: float = 8.0


class AgentState(NamedTuple):
    params: Any
    target_params: Any
    opt_m: Any
    opt_v: Any
    buffer: Any
    env_state: Any               # VectorEnv state, leaves lead with [num_envs]
    obs: jax.Array               # policy input: float32[num_envs, obs_dim],
    #                              or uint8[num_envs, H, W, history_len] for
    #                              pixel envs (the actor's frame stack)
    step: jax.Array
    episode_return: jax.Array    # float32[num_envs] running returns
    last_returns: jax.Array      # ring buffer of completed episode returns
    n_episodes: jax.Array


class DQN(NamedTuple):
    """Everything `make_dqn` builds, by name (no positional unpacking).

    ``act`` / ``learn`` are the pieces the async runtime
    (:mod:`repro.runtime`) composes into overlapped pipeline stages;
    ``agent_step`` is the same two pieces fused into one synchronous
    iteration, and ``train`` wraps that in a lax.scan.
    """

    init: Callable
    agent_step: Callable
    train: Callable          # (key, n_steps) -> (AgentState, metrics)
    train_ckpt: Callable     # (key, n_steps, manager) -> checkpointed train
    train_many: Callable     # (keys [S], n_steps) -> batched states/metrics
    evaluate: Callable       # (params/AgentState, key, n_episodes) -> return
    evaluate_many: Callable  # (batched states, keys [S], n_episodes) -> [S]
    act: Callable            # (params, env_state, obs, step, key)
    #                          -> (env_state, next_obs, transitions)
    learn: Callable          # (params, target, m, v, step, batch, weights)
    #                          -> (params, m, v, td, loss)
    cfg: DQNConfig
    env: Any                 # scalar env instance
    venv: Any                # VectorEnv over cfg.num_envs copies
    replay: Any              # the ReplayBuffer (sampler attached)
    beta_at: Callable        # (step) -> IS exponent under cfg's schedule
    q_apply: Callable        # (params, obs) -> Q-values (the head's apply)
    example_transition: Any  # zero transition pytree (schema of the ring)
    init_obs: Callable       # (venv env_state) -> initial policy input
    #                          (raw obs, or the seeded frame stack)


def make_dqn(cfg: DQNConfig) -> DQN:
    env = envs_mod.make_env(cfg.env)
    venv = envs_mod.VectorEnv(env, cfg.num_envs)
    obs_shape = venv.obs_shape
    pixel = len(obs_shape) > 1
    try:
        head_kind, double = AGENTS[cfg.agent]
    except KeyError:
        raise ValueError(f"unknown agent: {cfg.agent!r} "
                         f"(available: {sorted(AGENTS)})") from None
    if cfg.n_step < 1:
        raise ValueError(f"n_step must be >= 1, got {cfg.n_step}")
    if pixel:
        head_kind = _CONV_PROMOTION[head_kind]
        net_shape = obs_shape + (cfg.history_len,)
    else:
        net_shape = obs_shape
    qhead = make_qhead(head_kind, net_shape, cfg.hidden, env.n_actions)
    q_apply = qhead.apply
    # n-step targets bootstrap the un-terminated window with gamma^n.
    gamma_n = cfg.gamma ** cfg.n_step
    # The completed-return ring must fit one iteration's worst case of
    # num_envs simultaneous finishes, else slots collide within a scatter.
    ring = max(RETURN_RING, cfg.num_envs)
    sampler = make_sampler(
        cfg.sampler, cfg.replay_size,
        m=cfg.amper_m, lam_fr=cfg.amper_lam_fr,
        csp_ratio=cfg.amper_csp_ratio, v_max=cfg.v_max,
        min_csp=cfg.batch, knn_mode="bisect")
    is_per = cfg.sampler.startswith("per")
    frame_store = (FrameStore(history_len=cfg.history_len,
                              frame_shape=obs_shape, stride=cfg.num_envs,
                              n_step=cfg.n_step, gamma=cfg.gamma)
                   if pixel else None)
    rb = ReplayBuffer(cfg.replay_size, sampler, alpha=cfg.alpha,
                      beta=cfg.beta,
                      n_step=1 if pixel else cfg.n_step,
                      gamma=cfg.gamma, num_envs=cfg.num_envs,
                      frame_store=frame_store)
    if pixel:
        # One uint8 frame per transition; obs/next_obs stacks are
        # materialized by the buffer at sample time.
        example_transition = {
            "frame": jnp.zeros(obs_shape, jnp.uint8),
            "action": jnp.int32(0), "reward": jnp.float32(0),
            "done": jnp.float32(0), "terminated": jnp.float32(0)}
    else:
        example_transition = {
            "obs": jnp.zeros(obs_shape), "action": jnp.int32(0),
            "reward": jnp.float32(0), "next_obs": jnp.zeros(obs_shape),
            "done": jnp.float32(0), "terminated": jnp.float32(0)}

    def stack_init(frames):
        """Seed a history stack from one uint8 frame batch: zeros except
        the newest plane — the same padding the frame store materializes
        for an episode's first observation."""
        z = jnp.zeros(frames.shape + (cfg.history_len,), jnp.uint8)
        return z.at[..., -1].set(frames)

    def stack_push(stack, frames, done):
        """Shift one frame in; restart from zero-padding where ``done``."""
        shifted = jnp.concatenate([stack[..., 1:], frames[..., None]],
                                  axis=-1)
        d = jnp.reshape(done, jnp.shape(done)
                        + (1,) * (shifted.ndim - jnp.ndim(done)))
        return jnp.where(d, stack_init(frames), shifted)

    if pixel:
        def q_in(obs):
            # The one uint8 -> float expression shared with
            # ReplayBuffer.materialize (bit-identical policy inputs).
            return obs.astype(jnp.float32) * frame_store.scale

        def init_obs(env_state):
            return stack_init(venv.obs(env_state))
    else:
        def q_in(obs):
            return obs

        def init_obs(env_state):
            return venv.obs(env_state)

    def init(key) -> AgentState:
        k1, k2 = jax.random.split(key)
        params = qhead.init(k1)
        tr = example_transition
        env_state = venv.reset(k2)
        return AgentState(
            params=params, target_params=params,
            opt_m=jax.tree.map(jnp.zeros_like, params),
            opt_v=jax.tree.map(jnp.zeros_like, params),
            buffer=rb.init(tr), env_state=env_state,
            obs=init_obs(env_state), step=jnp.int32(0),
            episode_return=jnp.zeros(cfg.num_envs),
            last_returns=jnp.zeros(ring), n_episodes=jnp.int32(0))

    def td_loss(params, target_params, batch, weights):
        q = q_apply(params, batch["obs"])
        qa = jnp.take_along_axis(q, batch["action"][:, None], 1)[:, 0]
        qn = q_apply(target_params, batch["next_obs"])
        if double:
            # Double DQN: the online net picks the action, the target net
            # evaluates it — decoupling selection from overestimation.
            a_star = jnp.argmax(q_apply(params, batch["next_obs"]), axis=-1)
            boot = jnp.take_along_axis(qn, a_star[:, None], 1)[:, 0]
            boot = jax.lax.stop_gradient(boot)
        else:
            boot = qn.max(-1)
        # Bootstrap through time-limit truncation: only a true MDP
        # terminal (`terminated`) zeroes the tail — a `done` from the
        # step cap is an artifact of the horizon, not of the value.
        target = batch["reward"] + gamma_n * (1 - batch["terminated"]) * boot
        td = qa - jax.lax.stop_gradient(target)
        return jnp.mean(weights * td * td), td

    def adam(params, grads, m, v, step):
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        c = step.astype(jnp.float32) + 1
        lr = cfg.lr * jnp.sqrt(1 - 0.999 ** c) / (1 - 0.9 ** c)
        params = jax.tree.map(
            lambda p, mm, vv: p - lr * mm / (jnp.sqrt(vv) + eps), params, m, v)
        return params, m, v

    def beta_at(step):
        """IS exponent at ``step`` (traced-scalar safe).  Constant unless
        the config opts into annealing via ``beta_end``."""
        if cfg.beta_end is None:
            return cfg.beta
        horizon = (cfg.beta_anneal_steps if cfg.beta_anneal_steps is not None
                   else cfg.eps_decay_steps)
        return beta_schedule(cfg.beta, cfg.beta_end, step, horizon)

    def act(params, env_state, obs, step, key):
        """One vectorized epsilon-greedy env step (the actor piece).

        Returns ``(env_state, next_obs, transitions)`` where ``next_obs``
        is the post-auto-reset policy input for the next step (a float
        observation, or the shifted uint8 frame stack for pixel envs) and
        ``transitions`` is the B-row pytree to store — float envs keep
        the pre-reset ``next_obs`` the TD target needs; pixel envs store
        only the current raw frame (the buffer rebuilds both stacks).
        """
        k_coin, k_rand, k_env = jax.random.split(key, 3)
        eps = jnp.clip(
            cfg.eps_start + (cfg.eps_end - cfg.eps_start)
            * step / cfg.eps_decay_steps, cfg.eps_end, cfg.eps_start)
        q = q_apply(params, q_in(obs))                   # [B, n_actions]
        greedy = jnp.argmax(q, axis=-1)
        explore = jax.random.uniform(k_coin, (cfg.num_envs,)) < eps
        randa = jax.random.randint(k_rand, (cfg.num_envs,), 0, env.n_actions)
        action = jnp.where(explore, randa, greedy).astype(jnp.int32)
        env_state, next_obs, reward, done, terminated = venv.step(
            env_state, action, k_env)
        if pixel:
            transitions = {
                "frame": obs[..., -1], "action": action, "reward": reward,
                "done": done.astype(jnp.float32),
                "terminated": terminated.astype(jnp.float32)}
            return env_state, stack_push(obs, venv.obs(env_state),
                                         done), transitions
        transitions = {
            "obs": obs, "action": action, "reward": reward,
            "next_obs": next_obs, "done": done.astype(jnp.float32),
            "terminated": terminated.astype(jnp.float32)}
        return env_state, venv.obs(env_state), transitions

    def learn(params, target_params, opt_m, opt_v, step, batch, weights):
        """One TD gradient step on a sampled batch (the learner piece)."""
        w = weights if is_per else jnp.ones_like(weights)
        with jax.named_scope("td_loss"):
            (loss, td), grads = jax.value_and_grad(
                td_loss, has_aux=True)(params, target_params, batch, w)
        with jax.named_scope("adam"):
            params, m, v = adam(params, grads, opt_m, opt_v, step)
        return params, m, v, td, loss

    def agent_step(state: AgentState, key) -> tuple[AgentState, dict]:
        k_act, k_sample = jax.random.split(key)
        env_state, obs_next, transitions = act(
            state.params, state.env_state, state.obs, state.step, k_act)
        reward = transitions["reward"]
        done = transitions["done"] > 0.5
        buffer = rb.add_batch(state.buffer, transitions)

        # Per-env episode accounting: each env that finished this step
        # claims the next free slot of the shared completed-return ring
        # (exclusive cumsum orders simultaneous finishes; non-finished envs
        # aim out of range and are dropped by the scatter).
        ep_ret = state.episode_return + reward
        d = done.astype(jnp.int32)
        slot = (state.n_episodes + jnp.cumsum(d) - d) % ring
        last_returns = state.last_returns.at[
            jnp.where(done, slot, ring)].set(ep_ret, mode="drop")
        n_episodes = state.n_episodes + jnp.sum(d)
        episode_return = jnp.where(done, 0.0, ep_ret)

        def do_train(args):
            params, m, v, buffer = args
            idx, batch, w = rb.sample(buffer, k_sample, cfg.batch,
                                      beta=beta_at(state.step))
            params, m, v, td, _ = learn(
                params, state.target_params, m, v, state.step, batch, w)
            buffer = rb.update_priorities(buffer, idx, td)
            return params, m, v, buffer

        should = (state.step >= cfg.learn_start) & (
            state.step % cfg.train_every == 0)
        params, m, v, buffer = jax.lax.cond(
            should, do_train, lambda a: a,
            (state.params, state.opt_m, state.opt_v, buffer))
        target_params = jax.tree.map(
            lambda t, p: jnp.where(state.step % cfg.target_sync == 0, p, t),
            state.target_params, params)

        new = AgentState(params=params, target_params=target_params,
                         opt_m=m, opt_v=v, buffer=buffer,
                         env_state=env_state, obs=obs_next,
                         step=state.step + 1,
                         episode_return=episode_return,
                         last_returns=last_returns, n_episodes=n_episodes)
        metrics = {"return_mean": jnp.where(
            n_episodes > 0,
            last_returns.sum() / jnp.minimum(n_episodes, ring), 0.0),
            # The IS exponent this step's draw actually used — surfaces
            # the annealed schedule instead of the frozen constructor β.
            "beta": jnp.float32(beta_at(state.step))}
        return new, metrics

    def _train(key, n_steps: int):
        state = init(key)
        keys = jax.random.split(jax.random.fold_in(key, 1), n_steps)
        state, metrics = jax.lax.scan(agent_step, state, keys)
        return state, metrics

    train = jax.jit(_train, static_argnames="n_steps")
    # Multi-seed sweep: one compiled program, seeds run data-parallel.
    train_many = jax.jit(jax.vmap(_train, in_axes=(0, None)),
                         static_argnames="n_steps")

    scan_segment = jax.jit(
        lambda state, keys: jax.lax.scan(agent_step, state, keys))

    def train_ckpt(key, n_steps: int, manager: ckpt_mod.CheckpointManager):
        """The scan trainer with periodic checkpoint + exact resume.

        The per-step key array is derived once for the WHOLE run
        (``split(fold_in(key, 1), n_steps)``, exactly as ``train``) and
        the scan runs in ``save_interval`` segments with an atomic
        checkpoint of the full :class:`AgentState` — params, optimizer
        moments, replay buffer, sampler state, env state, and episode
        accounting — between segments.  A killed run resumed from the
        latest checkpoint reaches the same final state as an
        uninterrupted ``train_ckpt`` run, bit for bit (pinned by
        ``tests/test_resume.py``); against the single-scan ``train`` the
        match is float-tolerance only, because XLA compiles the segmented
        and fused programs with different reassociation.

        Because the key derivation depends on ``n_steps``, resuming with
        a different ``n_steps`` would silently change every step key; the
        manifest records it and a mismatch raises.

        Returns ``(state, metrics, done_steps)`` where ``metrics`` covers
        only the steps run by THIS invocation and ``done_steps < n_steps``
        iff the manager was preempted mid-run (a final checkpoint is
        flushed first).
        """
        keys = jax.random.split(jax.random.fold_in(key, 1), n_steps)
        state = None
        start = 0
        latest = manager.latest_step()
        if latest is not None:
            saved = ckpt_mod.load_meta(manager.directory, latest)
            if saved.get("n_steps", n_steps) != n_steps:
                raise ValueError(
                    f"resume with n_steps={n_steps} but checkpoint was "
                    f"written by an n_steps={saved['n_steps']} run; the "
                    f"step-key derivation depends on n_steps, so this "
                    f"would not be an exact resume")
            target = jax.eval_shape(init, jax.random.key(0))
            state = ckpt_mod.restore(manager.directory, latest, target)
            start = latest
        if state is None:  # no checkpoint: only now pay for a fresh init
            state = init(key)
        parts = []
        t = start
        while t < n_steps:
            seg = min(n_steps - t, manager.save_interval)
            state, m = scan_segment(state, keys[t:t + seg])
            parts.append(m)
            t += seg
            if manager.should_save(t) or t == n_steps:
                manager.save(t, state, meta={"n_steps": n_steps, "step": t})
            if manager.preempted and t < n_steps:
                break
        if not parts:  # resumed a run that had already completed
            return state, {"return_mean": jnp.zeros((0,)),
                           "beta": jnp.zeros((0,))}, t
        metrics = jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)
        return state, metrics, t

    def evaluate(state, key, n_episodes: int = 10) -> jax.Array:
        """Greedy-policy average return (the paper's 'test score').

        Accepts a full :class:`AgentState` or bare network params (what
        the async runtime's :class:`~repro.runtime.service.RunResult`
        carries).
        """
        params = state.params if hasattr(state, "params") else state

        def one_ep(key):
            k0, key = jax.random.split(key)
            env_state = env.reset(k0)
            if pixel:
                obs0 = stack_init(env.obs(env_state))
            else:
                obs0 = env.obs(env_state)

            def body(carry):
                env_state, obs, ret, done, key = carry
                key, k = jax.random.split(key)
                action = jnp.argmax(
                    q_apply(params, q_in(obs))).astype(jnp.int32)
                env_state, obs2, r, d, _term = env.step(env_state, action, k)
                if pixel:
                    nxt = stack_push(obs, env.obs(env_state), d)
                else:
                    nxt = env.obs(env_state)
                return (env_state, nxt, ret + r * (1 - done),
                        jnp.maximum(done, d.astype(jnp.float32)), key)

            def cond(carry):
                return carry[3] < 1

            out = jax.lax.while_loop(
                cond, body,
                (env_state, obs0, jnp.float32(0), jnp.float32(0), key))
            return out[2]

        return jax.vmap(one_ep)(jax.random.split(key, n_episodes)).mean()

    def evaluate_many(states, keys, n_episodes: int = 10) -> jax.Array:
        """Per-seed test scores for a `train_many` output batch."""
        return jax.vmap(lambda s, k: evaluate(s, k, n_episodes))(states, keys)

    return DQN(init=init, agent_step=agent_step, train=train,
               train_ckpt=train_ckpt, train_many=train_many,
               evaluate=evaluate, evaluate_many=evaluate_many, act=act,
               learn=learn, cfg=cfg, env=env, venv=venv, replay=rb,
               beta_at=beta_at, q_apply=q_apply,
               example_transition=example_transition, init_obs=init_obs)
