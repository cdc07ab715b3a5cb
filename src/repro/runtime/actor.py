"""Host-thread actor pool driving jitted ``VectorEnv`` rollout chunks.

Each actor owns an independent ``VectorEnv`` state (its own reset key,
its own episode accounting) and repeatedly runs one jitted rollout chunk
— ``chunk_len`` vectorized epsilon-greedy steps composed from the DQN's
``act`` piece inside a ``lax.scan`` — then enqueues the resulting
``[chunk_len, num_envs]`` transition block for the replay service.  The
Python thread only dispatches the chunk and moves the result between
queues; all math happens inside XLA, which releases the GIL, so actors
overlap with the learner and the prefetch pipeline.

Exploration schedule note: each actor drives ``eps`` with its *local*
step counter, so with A actors the schedule advances per actor-iteration
rather than per global frame — the standard per-worker schedule of
distributed DQN variants.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span
from repro.runtime import prng


class TransitionBlock(NamedTuple):
    """One rollout chunk handed from an actor to the replay service.

    With n-step replay the rows are already aggregated by the actor's
    own :class:`~repro.core.replay_buffer.NStepAccumulator` (each actor
    is an independent env stream, so the buffer's shared accumulator
    cannot serve them); the leading dim is then the number of *emitted*
    n-step rows — ``chunk_len`` once warm, fewer for the chunk that
    spans the warm-up, and ``transitions`` is None when the whole chunk
    fell inside it.  ``frames`` always counts raw env frames.
    """

    transitions: Any            # pytree, leaves [emitted, num_envs, ...]
    frames: int                 # chunk_len * num_envs
    actor_id: int
    chunk_id: int
    completed_returns: np.ndarray  # episodes that finished in this chunk


def put_with_stop(q: queue.Queue, item, stop: threading.Event,
                  timeout: float = 0.05) -> bool:
    """Blocking put that aborts (returns False) once ``stop`` is set."""
    while not stop.is_set():
        try:
            q.put(item, timeout=timeout)
            return True
        except queue.Full:
            continue
    return False


class PauseGate:
    """Cooperative quiesce point for the pipeline threads (optional).

    An orchestrator calls :meth:`pause`; each worker thread parks at its
    next :meth:`wait_if_paused` call (registering itself, so
    :meth:`wait_parked` can await full quiescence) and stays parked until
    :meth:`resume`.  Parking happens only at loop boundaries — after a
    worker's in-flight queue put has completed — so a fully-parked
    pipeline has every produced item already in a queue where a
    non-parking drainer can consume it.

    The replay service's checkpoints no longer use this: snapshots are
    copy-on-write (``service._CowSnapshotter`` captures immutable state
    references without pausing anything), so the service constructs its
    pool and prefetcher with ``gate=None``.  The gate remains available
    as a general quiesce utility for callers that do need a full stop
    (e.g. debugging a live pipeline).
    """

    def __init__(self):
        from repro.analysis.locks import make_condition

        self._cond = make_condition("runtime.pause_gate")
        self._paused = False
        self._parked = 0

    @property
    def paused(self) -> bool:
        return self._paused

    def pause(self) -> None:
        with self._cond:
            self._paused = True

    def resume(self) -> None:
        with self._cond:
            self._paused = False
            self._cond.notify_all()

    def wait_if_paused(self, stop: threading.Event) -> None:
        """Worker side: park here while the gate is paused."""
        if not self._paused:
            return
        with self._cond:
            self._parked += 1
            self._cond.notify_all()
            try:
                while self._paused and not stop.is_set():
                    self._cond.wait(timeout=0.05)
            finally:
                self._parked -= 1
                self._cond.notify_all()

    def wait_parked(self, n: int, stop: threading.Event,
                    timeout: float = 60.0) -> bool:
        """Orchestrator side: block until ``n`` workers are parked."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._parked < n:
                if stop.is_set() or time.monotonic() > deadline:
                    return False
                self._cond.wait(timeout=0.05)
        return True


def make_rollout(dqn, chunk_len: int) -> Callable:
    """Build the jittable chunk function
    ``(params, env_state, obs, step0, ep_ret, nstep, key) ->
    (env_state, obs, ep_ret, nstep, transitions, valid, finished)``
    where ``transitions`` leaves lead with ``[chunk_len, num_envs]``,
    ``valid`` is ``bool[chunk_len]`` (always True for 1-step; for n-step
    it gates rows emitted before the accumulator warmed up — envs run in
    lockstep, so validity is per-timestep, not per-env) and ``finished``
    is ``float32[chunk_len, num_envs]`` holding completed episode
    returns (NaN where no episode ended).  ``nstep`` threads the actor's
    own per-stream accumulator state (None when ``cfg.n_step == 1``)."""
    act = dqn.act
    acc = dqn.replay.accumulator   # None for n_step == 1

    def rollout(params, env_state, obs, step0, ep_ret, nstep, key):
        def body(carry, i):
            env_state, obs, ep_ret, ns = carry
            env_state, obs, tr = act(
                params, env_state, obs, step0 + i, jax.random.fold_in(key, i))
            ret = ep_ret + tr["reward"]
            done = tr["done"] > 0.5
            finished = jnp.where(done, ret, jnp.nan)
            if acc is not None:
                ns, out, valid = acc.push(ns, tr)
            else:
                out, valid = tr, jnp.bool_(True)
            return ((env_state, obs, jnp.where(done, 0.0, ret), ns),
                    (out, valid, finished))

        carry, (transitions, valid, finished) = jax.lax.scan(
            body, (env_state, obs, ep_ret, nstep),
            jnp.arange(chunk_len, dtype=jnp.int32))
        env_state, obs, ep_ret, nstep = carry
        return env_state, obs, ep_ret, nstep, transitions, valid, finished

    return rollout


class Actor(threading.Thread):
    """One host thread: params snapshot -> rollout chunk -> block queue."""

    def __init__(self, actor_id: int, dqn, rollout: Callable,
                 params_fn: Callable[[], Any], out_q: queue.Queue,
                 stop: threading.Event, base_key: jax.Array, chunk_len: int,
                 budget_fn: Callable[[], bool] | None = None,
                 gate: PauseGate | None = None,
                 resume_state: dict | None = None):
        super().__init__(name=f"replay-actor-{actor_id}", daemon=True)
        self.actor_id = actor_id
        self._dqn = dqn
        self._rollout = rollout
        self._params_fn = params_fn
        self._out_q = out_q
        self._stop_evt = stop
        self._base_key = base_key
        self._chunk_len = chunk_len
        self._budget_fn = budget_fn
        self._gate = gate
        self._resume_state = resume_state
        self.chunks_done = (0 if resume_state is None
                            else int(resume_state["chunk"]))
        self.error: BaseException | None = None
        # Exact-resume snapshot slot: REPLACED (never mutated) with a
        # fresh dict after every completed chunk's enqueue, so a reader
        # on any thread — the COW snapshotter captures it live, without
        # parking this actor — always sees a self-consistent
        # chunk-boundary state.  The PRNG stream is captured by the two
        # integers: chunk c's rollout key is fold_in(roll_key, c) and
        # never depends on wall history.
        self.run_state: dict | None = None

    def run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # surfaced by the service after join
            self.error = e
            self._stop_evt.set()

    def _publish_run_state(self, env_state, obs, ep_ret, nstep, step, chunk):
        self.run_state = {"env_state": env_state, "obs": obs,
                          "ep_ret": ep_ret, "nstep": nstep,
                          "step": step, "chunk": chunk}

    def _loop(self) -> None:
        dqn, chunk_len = self._dqn, self._chunk_len
        k_reset, k_roll = prng.actor_keys(self._base_key, self.actor_id)
        if self._resume_state is None:
            env_state = dqn.venv.reset(k_reset)
            obs = dqn.init_obs(env_state)  # raw obs, or seeded frame stack
            ep_ret = jnp.zeros(dqn.cfg.num_envs)
            # This actor's own n-step window (None for n_step == 1): an
            # independent env stream must not share the buffer's.
            nstep = dqn.replay.nstep_init(dqn.example_transition)
            step, chunk = 0, 0
        else:
            # Exact continuation: env state, episode accounting, the
            # n-step window, and the PRNG stream position (chunk counter)
            # come from the snapshot; chunk_key(k_roll, chunk) resumes
            # the same key stream an uninterrupted run would have
            # consumed next.
            rs = self._resume_state
            env_state, obs, ep_ret = rs["env_state"], rs["obs"], rs["ep_ret"]
            nstep = rs.get("nstep")
            step, chunk = int(rs["step"]), int(rs["chunk"])
        self._publish_run_state(env_state, obs, ep_ret, nstep, step, chunk)
        while not self._stop_evt.is_set():
            if self._gate is not None:
                with span("actor_wait"):
                    self._gate.wait_if_paused(self._stop_evt)
            # Replay-ratio throttle: don't burn host cores producing frames
            # the learner can't consume (matters on small CPU hosts).
            with span("actor_wait"):
                while (self._budget_fn is not None
                       and not self._budget_fn()
                       and not self._stop_evt.is_set()
                       and not (self._gate is not None
                                and self._gate.paused)):
                    self._stop_evt.wait(0.002)
            if self._gate is not None and self._gate.paused:
                continue  # park at the loop-top gate before rolling out
            if self._stop_evt.is_set():
                return
            with span("rollout"):
                (env_state, obs, ep_ret, nstep, transitions, valid,
                 finished) = self._rollout(
                    self._params_fn(), env_state, obs, jnp.int32(step),
                    ep_ret, nstep, prng.chunk_key(k_roll, chunk))
            with span("host_sync"):
                fin = np.asarray(finished).ravel()
            # n-step warm-up: invalid rows form a prefix (the window only
            # fills once), so drop them host-side — the replay thread
            # writes only real n-step rows.  One extra jit trace for the
            # single shorter chunk that spans the warm-up.
            with span("host_sync"):
                n_valid = int(np.asarray(valid).sum())
            if n_valid == 0:
                transitions = None
            elif n_valid < chunk_len:
                transitions = jax.tree.map(
                    lambda x: x[chunk_len - n_valid:], transitions)
            block = TransitionBlock(
                transitions=transitions,
                frames=chunk_len * dqn.cfg.num_envs,
                actor_id=self.actor_id, chunk_id=chunk,
                completed_returns=fin[~np.isnan(fin)])
            with span("actor_wait"):
                delivered = put_with_stop(self._out_q, ("block", block),
                                          self._stop_evt)
            if not delivered:
                return
            step += chunk_len
            chunk += 1
            self.chunks_done = chunk
            self._publish_run_state(env_state, obs, ep_ret, nstep, step,
                                    chunk)


class ActorPool:
    """A fixed pool of :class:`Actor` threads sharing one block queue."""

    def __init__(self, dqn, rollout: Callable, *, num_actors: int,
                 params_fn: Callable[[], Any], out_q: queue.Queue,
                 stop: threading.Event, base_key: jax.Array, chunk_len: int,
                 budget_fn: Callable[[], bool] | None = None,
                 gate: PauseGate | None = None,
                 resume_states: list | None = None):
        self.actors = [
            Actor(i, dqn, rollout, params_fn, out_q, stop, base_key,
                  chunk_len, budget_fn, gate=gate,
                  resume_state=(resume_states[i] if resume_states else None))
            for i in range(num_actors)
        ]

    @property
    def chunks_done(self) -> int:
        return sum(a.chunks_done for a in self.actors)

    def run_states(self) -> list:
        """Per-actor exact-resume snapshots (valid while parked/joined)."""
        return [a.run_state for a in self.actors]

    def start(self) -> None:
        for a in self.actors:
            a.start()

    def join(self, timeout: float | None = None) -> None:
        for a in self.actors:
            a.join(timeout)

    def raise_errors(self) -> None:
        for a in self.actors:
            if a.error is not None:
                raise RuntimeError(
                    f"actor {a.actor_id} failed") from a.error
