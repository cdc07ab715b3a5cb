"""Functional experience-replay buffer (Fig. 1's ER memory).

Stores an arbitrary transition pytree in a ring buffer with a pluggable
priority sampler (uniform / PER sum-tree / PER cumsum / AMPER-k / AMPER-fr,
or their mesh-sharded counterparts).
Everything is pure and jit-able; the buffer state is a pytree that can be
donated through a training step or sharded across a mesh.

The buffer is mesh-aware through the sampler: when the sampler carries a
``sharding`` (the ``*-sharded`` registry kinds expose a ``NamedSharding``
over the capacity dim), every storage leaf is kept partitioned the same
way, so transitions live on the shard that owns their priority row and the
ring-arc ``add_batch`` scatter respects the shard layout (each shard writes
only the arc slice it owns; no leaf is ever gathered to one device).

New experiences enter with the current maximum priority (the standard PER
convention: ensures every transition is replayed at least once); sampled
transitions get their priority rewritten from the fresh TD error after the
train step — the store / sample / update cycle of Fig. 1.

For the async runtime (:mod:`repro.runtime`) the buffer additionally
tracks a per-slot *write stamp*: the global add counter at the slot's
last write, plus a *generation* word counting signed-int32 rollovers of
that counter, so the pair ``(stamp, gen)`` identifies a write uniquely
for 2^64 adds.  A deferred priority update that arrives after the slot
was recycled by newer experience must not clobber the newcomer's
priority; passing the sample-time stamp pairs
(:meth:`ReplayBuffer.stamps`, shape ``[..., 2]``) to
:meth:`ReplayBuffer.update_priorities` turns it into an out-of-band
write that silently drops exactly those stale rows — including slots
recycled an exact multiple of 2^32 adds apart, which a single int32
stamp would false-accept.

With ``n_step > 1`` the buffer stores *n-step* transitions: a per-env
:class:`NStepAccumulator` (its state rides inside ``ReplayState``, so it
checkpoints with the buffer) converts the incoming 1-step stream into
n-step rows — ``reward`` becomes the discounted n-step return truncated
at the first episode boundary inside the window, ``next_obs`` the
observation the TD target bootstraps from (``gamma**n_step`` at the
learner), and ``done`` whether any step of the window ended.  The
emitted rows keep the 1-step schema, so storage layout, samplers, and
checkpoints are unchanged.  The async runtime feeds its own per-actor
accumulator (each actor is an independent env stream) and hands the
buffer pre-aggregated rows via ``add_block(..., aggregated=True)``.

Frame-deduplicated pixel storage
--------------------------------

Passing a :class:`FrameStore` switches the buffer to pixel-native
storage: each transition stores its observation ONCE as a raw uint8
frame (``frame: uint8[capacity, H, W]``) instead of two float stacks
(``obs`` + ``next_obs``, each ``float32[H, W, history_len]`` — a ~2 *
history_len * 4 blowup).  ``sample`` materializes the float
``history_len``-stacked ``obs``/``next_obs`` batches on the fly by
gathering backward along the ring arc (the tensorpack
``ReplayMemory``/``recent_state`` pattern), masking frames that cross an
episode boundary, the ring write head, or the unwritten warm-up region
to zero — bit-identical to what a naive float buffer would have stored.
The ``(idx, transitions, is_weights)`` contract and every sampler are
unchanged; n-step aggregation happens at *sample time* (the stored
stream stays 1-step), so construct the buffer with ``n_step=1`` and put
the n-step window in ``FrameStore(n_step=...)``.

Frame chaining needs ring adjacency: the transition ``stride`` slots
before slot ``i`` must be the previous timestep of the *same* env
stream.  That holds for a single writer stream of ``stride`` lockstep
envs (the sync runtime, or the async runtime with one actor) and is
validated by stamp-difference checks at gather time, so foreign rows
degrade to masked frames/terminals rather than silent corruption.  One
semantic caveat: a time-limit truncation's pre-reset next observation is
never stored (the next slot already holds the fresh episode's reset
frame), so the frame path treats every ``done`` as terminal — the
truncation-bootstrap distinction lives on the float path.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.per import importance_from_selected
from repro.core.samplers import masked_update


class NStepState(NamedTuple):
    """Per-env-stream window of the last ``n`` 1-step transitions.

    All envs of one stream step in lockstep, so one scalar cursor pair
    serves the whole ``[num_envs]`` batch; ``ring`` leaves lead with
    ``[n, num_envs]``.
    """

    ring: Any         # transition pytree, leaves [n, num_envs, ...]
    count: jax.Array  # int32 pushes so far, saturating at n
    pos: jax.Array    # int32 next ring slot (== oldest entry once full)


class NStepAccumulator:
    """Pure, jittable n-step transition aggregator (per env stream).

    Push one vectorized 1-step transition batch per call; once the
    window holds ``n`` steps, each push also emits the n-step transition
    whose *first* step is the oldest window entry:

      ``reward``     = sum_k gamma^k r_k, truncated at the first ``done``
                       inside the window (steps past it belong to the
                       next episode and must not leak in);
      ``next_obs``   = the pre-reset observation of the truncating step
                       (or of the newest step when no episode ended);
      ``done``       = did any window step end the episode;
      ``terminated`` = should the TD target *not* bootstrap (emitted only
                       when the input rows carry the key).  A time-limit
                       truncation exactly at the window's last step keeps
                       ``terminated = 0``: the emitted reward covers all
                       n steps and ``next_obs`` is the pre-reset
                       observation, so the learner's fixed ``gamma**n``
                       bootstrap is exactly right.  A ``done`` *inside*
                       the window (truncation or not) sets
                       ``terminated = 1`` — the learner's discount is
                       fixed at ``gamma**n``, so a shorter horizon
                       cannot bootstrap at the right scale and the
                       conservative terminal treatment is the unbiased
                       choice among the expressible ones.

    The learner bootstraps the un-terminated case with ``gamma**n``.
    Emission validity is a traced scalar (all envs warm up in lockstep),
    so callers gate the ring write with one ``lax.cond``.
    """

    def __init__(self, n_step: int, gamma: float):
        if n_step < 2:
            raise ValueError(f"NStepAccumulator needs n_step >= 2, got "
                             f"{n_step} (use the buffer directly for 1)")
        self.n = n_step
        self.gamma = gamma

    def init(self, example_transition: Any, num_envs: int) -> NStepState:
        ring = jax.tree.map(
            lambda x: jnp.zeros((self.n, num_envs) + jnp.shape(x),
                                jnp.asarray(x).dtype),
            example_transition)
        return NStepState(ring=ring, count=jnp.int32(0), pos=jnp.int32(0))

    def push(self, state: NStepState, transitions: Any
             ) -> tuple[NStepState, Any, jax.Array]:
        """-> (state, emitted n-step rows [num_envs, ...], valid scalar).

        ``emitted`` holds garbage until ``valid`` (count reached n);
        gate the write on it.
        """
        ring = jax.tree.map(lambda buf, x: buf.at[state.pos].set(x),
                            state.ring, transitions)
        pos = (state.pos + 1) % self.n
        count = jnp.minimum(state.count + 1, self.n)
        new = NStepState(ring=ring, count=count, pos=pos)
        # Window in chronological order: once full, `pos` is the oldest.
        order = (pos + jnp.arange(self.n, dtype=jnp.int32)) % self.n
        w = jax.tree.map(lambda buf: buf[order], ring)
        d = w["done"]                                    # [n, E]
        cont = jnp.cumprod(1.0 - d, axis=0)              # alive after k
        cont_before = jnp.concatenate(
            [jnp.ones_like(cont[:1]), cont[:-1]], axis=0)
        disc = (self.gamma ** jnp.arange(self.n, dtype=jnp.float32))[:, None]
        reward = jnp.sum(disc * cont_before * w["reward"], axis=0)
        done = 1.0 - cont[-1]
        any_done = jnp.any(d > 0.5, axis=0)
        first_done = jnp.argmax(d > 0.5, axis=0)         # 0 when none
        horizon = jnp.where(any_done, first_done, self.n - 1)
        next_obs = jax.vmap(lambda col, h: col[h], in_axes=(1, 0))(
            w["next_obs"], horizon)
        emitted = {"obs": w["obs"][0], "action": w["action"][0],
                   "reward": reward, "next_obs": next_obs, "done": done}
        if "terminated" in w:
            # Bootstrap only when the window either ran done-free or was
            # cut by a truncation exactly at its last step (see class
            # docstring for why mid-window truncations stay terminal).
            emitted["terminated"] = jnp.where(
                any_done,
                jnp.where(first_done == self.n - 1,
                          w["terminated"][self.n - 1], 1.0),
                0.0)
        return new, emitted, count >= self.n


class FrameStore(NamedTuple):
    """Configuration of the frame-deduplicated pixel storage mode.

    history_len: frames stacked into one observation (the conv head's
      channel dim).
    frame_shape: shape of one stored frame, e.g. ``(H, W)``.
    stride: ring distance between consecutive timesteps of one env — the
      writer's lockstep width (``num_envs`` when a vectorized step is
      written as one arc).
    n_step: n-step return aggregated at sample time (the stored stream
      stays 1-step).
    gamma: discount for the sample-time n-step return.
    scale: uint8 -> float conversion factor; actors must use the same
      expression (``frame.astype(float32) * scale``) so materialized
      stacks are bit-identical to what the policy saw.
    """

    history_len: int
    frame_shape: tuple
    stride: int = 1
    n_step: int = 1
    gamma: float = 0.99
    scale: float = 1.0 / 255.0


_FRAME_KEYS = ("frame", "action", "reward", "done")


class ReplayState(NamedTuple):
    storage: Any          # pytree of arrays with leading dim = capacity
    sampler_state: Any    # state of the priority sampler
    pos: jax.Array        # int32 next write slot
    size: jax.Array       # int32 live count
    max_priority: jax.Array  # float32 running max (for new entries)
    write_stamp: jax.Array   # int32[capacity] global add counter at last
    #                          write of each slot (-1 = never written)
    total_adds: jax.Array    # int32 transitions ever written (wraps; see
    #                          add_gen)
    write_gen: jax.Array     # int32[capacity] rollover generation of the
    #                          slot's stamp — (write_stamp, write_gen)
    #                          identifies a write uniquely for 2^64 adds
    add_gen: jax.Array       # int32 rollovers of total_adds so far
    nstep: Any = None        # NStepState when n_step > 1, else None


class ReplayBuffer:
    """Ring buffer + priority sampler.

    Args:
      capacity: number of transitions.
      sampler: object exposing init/update/sample/priorities (see core.amper).
      alpha: PER exponent; priorities stored as (|td| + eps)^alpha.
      beta: importance-sampling exponent for weight computation.
      n_step: store n-step transitions (1 = the classic 1-step buffer).
        With ``n_step > 1``, ``add_batch`` expects exactly ``num_envs``
        rows per call (one lockstep vectorized env step) and routes them
        through the in-state :class:`NStepAccumulator`.
      gamma: discount used for the n-step return (ignored for n_step=1).
      num_envs: env-stream width the accumulator is sized for.
      frame_store: switch to frame-deduplicated uint8 pixel storage (see
        module docstring).  Requires ``n_step == 1`` here — the frame
        path aggregates n-step returns at sample time from
        ``FrameStore.n_step`` — and a storage schema containing at least
        ``frame`` (uint8, ``frame_shape``), ``action``, ``reward`` and
        ``done``.
    """

    def __init__(self, capacity: int, sampler, alpha: float = 0.6,
                 beta: float = 0.4, eps: float = 1e-2, n_step: int = 1,
                 gamma: float = 0.99, num_envs: int = 1,
                 frame_store: FrameStore | None = None):
        self.capacity = capacity
        self.sampler = sampler
        self.alpha = alpha
        self.beta = beta
        self.eps = eps
        self.n_step = n_step
        self.num_envs = num_envs
        self.frame_store = frame_store
        if frame_store is not None:
            if n_step != 1:
                raise ValueError(
                    "frame-store buffers aggregate n-step returns at "
                    "sample time: construct with n_step=1 and set "
                    f"FrameStore(n_step={n_step}) instead")
            if frame_store.history_len < 1 or frame_store.n_step < 1 \
                    or frame_store.stride < 1:
                raise ValueError(f"invalid FrameStore config: {frame_store}")
            span = (frame_store.history_len + frame_store.n_step) \
                * frame_store.stride
            if span >= capacity:
                raise ValueError(
                    f"capacity {capacity} too small for FrameStore "
                    f"window span {span} (stack + n-step would always "
                    "cross the write head)")
        self.accumulator = (NStepAccumulator(n_step, gamma)
                            if n_step > 1 else None)
        # Mesh-native samplers advertise the NamedSharding of their
        # priority table; storage follows it on the capacity dim.
        self.storage_sharding = getattr(sampler, "sharding", None)

    def nstep_init(self, example_transition: Any):
        """Fresh accumulator state for an independent env stream (the
        async runtime gives each actor its own), or None for n_step=1."""
        if self.accumulator is None:
            return None
        return self.accumulator.init(example_transition, self.num_envs)

    def _constrain(self, storage: Any) -> Any:
        if self.storage_sharding is None:
            return storage
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, self.storage_sharding),
            storage)

    def init(self, example_transition: Any) -> ReplayState:
        if self.frame_store is not None:
            missing = [k for k in _FRAME_KEYS
                       if k not in example_transition]
            if missing:
                raise ValueError(
                    f"frame-store schema missing keys {missing}: needs "
                    f"at least {list(_FRAME_KEYS)}")
            frame = jnp.asarray(example_transition["frame"])
            if frame.dtype != jnp.uint8:
                raise ValueError(
                    f"frame leaf must be uint8, got {frame.dtype}")
            if tuple(frame.shape) != tuple(self.frame_store.frame_shape):
                raise ValueError(
                    f"frame leaf shape {tuple(frame.shape)} != "
                    f"FrameStore.frame_shape "
                    f"{tuple(self.frame_store.frame_shape)}")
        storage = self._constrain(jax.tree.map(
            lambda x: jnp.zeros((self.capacity,) + jnp.shape(x), jnp.asarray(x).dtype),
            example_transition,
        ))
        return ReplayState(
            storage=storage,
            sampler_state=self.sampler.init(),
            pos=jnp.int32(0),
            size=jnp.int32(0),
            max_priority=jnp.float32(1.0),
            write_stamp=self._constrain(
                jnp.full((self.capacity,), -1, jnp.int32)),
            total_adds=jnp.int32(0),
            write_gen=self._constrain(
                jnp.zeros((self.capacity,), jnp.int32)),
            add_gen=jnp.int32(0),
            nstep=self.nstep_init(example_transition),
        )

    def add(self, state: ReplayState, transition: Any) -> ReplayState:
        """Store one transition at the ring position with max priority."""
        return self.add_batch(
            state, jax.tree.map(lambda x: jnp.asarray(x)[None], transition))

    def _write_arc(self, state: ReplayState, transitions: Any) -> ReplayState:
        """Raw ring-arc write of B already-final rows (no accumulation)."""
        b = jax.tree.leaves(transitions)[0].shape[0]
        if b > self.capacity:
            raise ValueError(
                f"add_batch of {b} transitions exceeds capacity "
                f"{self.capacity}: ring slots would collide within one write")
        idx = (state.pos + jnp.arange(b, dtype=jnp.int32)) % self.capacity
        sampler_state = self.sampler.update(
            state.sampler_state, idx,
            jnp.broadcast_to(state.max_priority, (b,))
        )
        # int32 arithmetic wraps; the generation words track each signed
        # rollover so (stamp, gen) stays unique across 2^63 adds.
        lo = state.total_adds
        stamps = lo + jnp.arange(b, dtype=jnp.int32)
        row_gen = state.add_gen + (stamps < lo).astype(jnp.int32)
        new_total = lo + jnp.int32(b)
        storage, write_stamp, write_gen = self._constrain(self._write_rows(
            (state.storage, state.write_stamp, state.write_gen),
            (transitions, stamps, row_gen), state.pos, idx))
        return ReplayState(
            storage=storage,
            sampler_state=sampler_state,
            pos=(state.pos + b) % self.capacity,
            size=jnp.minimum(state.size + b, self.capacity),
            max_priority=state.max_priority,
            write_stamp=write_stamp,
            total_adds=new_total,
            write_gen=write_gen,
            add_gen=state.add_gen + (new_total < lo).astype(jnp.int32),
            nstep=state.nstep,
        )

    def _write_rows(self, rings: Any, rows: Any, pos: jax.Array,
                    idx: jax.Array) -> Any:
        """Write ``rows`` (leading dim B) into the arc ``idx`` of ``rings``
        (see :func:`_write_ring_arc`).  A ring sharded on its rows keeps
        the scatter, which GSPMD splits by the shard owning each row."""
        rows = jax.tree.map(
            lambda buf, x: jnp.broadcast_to(
                x, idx.shape + buf.shape[1:]).astype(buf.dtype),
            rings, rows)
        if self.storage_sharding is not None:
            return _scatter_rows(rings, rows, idx)
        return _write_ring_arc(rings, rows, pos, self.capacity)

    def add_batch(self, state: ReplayState, transitions: Any) -> ReplayState:
        """Store B transitions (leading dim B on every leaf) in one shot.

        The write slots are the contiguous ring arc
        ``(pos + arange(B)) % capacity`` — distinct as long as
        B <= capacity, so one batched sampler priority write replaces B
        sequential ones and every sampler's scatter semantics stay
        well-defined across the wraparound.

        With ``n_step > 1`` the rows are one lockstep vectorized env
        step (B must equal ``num_envs``); they enter the in-state
        accumulator and the *emitted* n-step rows are written instead —
        nothing reaches the ring until the window has warmed up.
        """
        if self.accumulator is None:
            return self._write_arc(state, transitions)
        b = jax.tree.leaves(transitions)[0].shape[0]
        if b != self.num_envs:
            raise ValueError(
                f"n_step={self.n_step} add_batch expects one vectorized "
                f"env step of num_envs={self.num_envs} rows, got {b} "
                f"(pre-aggregated rows go through add_block(..., "
                f"aggregated=True))")
        nstate, emitted, valid = self.accumulator.push(
            state.nstep, transitions)
        state = state._replace(nstep=nstate)
        return jax.lax.cond(
            valid, lambda s: self._write_arc(s, emitted), lambda s: s, state)

    def add_block(self, state: ReplayState, block: Any,
                  aggregated: bool = False) -> ReplayState:
        """Store a ``[T, B, ...]`` rollout block in chronological order.

        This is the runtime's block-enqueue entry point: an actor hands
        over a whole chunk of T vectorized steps at once, and the flatten
        preserves time-major order so the ring arc matches T sequential
        ``add_batch`` calls exactly.

        ``aggregated=True`` marks the rows as already n-step (the async
        actors run their own per-stream accumulator), bypassing the
        buffer's accumulator; with ``n_step > 1`` and raw rows the block
        is scanned through ``add_batch`` one timestep at a time instead
        of the single flattened write.
        """
        t, b = jax.tree.leaves(block)[0].shape[:2]
        with jax.named_scope("ring_write"):
            if self.accumulator is not None and not aggregated:
                state, _ = jax.lax.scan(
                    lambda s, tr: (self.add_batch(s, tr), None), state,
                    block)
                return state
            flat = jax.tree.map(
                lambda x: x.reshape((t * b,) + x.shape[2:]), block)
            return self._write_arc(state, flat)

    def _stack_frames(self, state: ReplayState, slot0: jax.Array,
                      ref: jax.Array, base_ok: jax.Array) -> jax.Array:
        """Materialize ``history_len``-stacks ending at ``slot0``.

        Chains backward ``stride`` ring slots per frame; every link must
        (a) carry the stamp exactly ``stride`` adds older than its
        successor — wrap-safe int32 difference, so a slot recycled by the
        write head or belonging to a foreign stream fails the check —
        (b) be a written slot (the ring fills ``[0, size)`` in order),
        and (c) not close an episode (its ``done`` would make the next
        frame a reset observation).  Broken links zero the remaining
        older frames, which is exactly the zero-padding a naive float
        buffer records at episode starts / warm-up.
        """
        fs = self.frame_store
        st, lo = state.storage, state.write_stamp
        nd = len(fs.frame_shape)

        def as_mask(ok):
            return ok.astype(jnp.float32).reshape(ok.shape + (1,) * nd)

        frames = []
        ok = base_ok
        for j in range(fs.history_len):
            slot = (slot0 - j * fs.stride) % self.capacity
            if j > 0:
                ok = (ok
                      & (lo[slot] - ref == jnp.int32(-j * fs.stride))
                      & (slot < state.size)
                      & (st["done"][slot] < 0.5))
            frames.append(st["frame"][slot].astype(jnp.float32)
                          * fs.scale * as_mask(ok))
        return jnp.stack(frames[::-1], axis=-1)   # oldest -> newest

    def materialize(self, state: ReplayState, idx: jax.Array) -> dict:
        """Frame mode: build the float batch a naive buffer would return.

        For each anchor slot: the stacked ``obs`` ending at the anchor's
        frame, the sample-time n-step return, and the stacked
        ``next_obs`` ending ``n_step * stride`` slots later.  Windows
        cut by an episode boundary, the ring write head, or unwritten
        warm-up slots are masked to terminal (``terminated = 1``,
        ``next_obs = 0``) — the TD target then reduces to the observed
        return, which never fabricates data; the write-head exclusions
        touch at most ``(history_len + n_step) * stride / capacity`` of
        the ring.
        """
        fs = self.frame_store
        st, lo = state.storage, state.write_stamp
        anchor = idx.astype(jnp.int32) % self.capacity
        ref = lo[anchor]
        written = anchor < state.size
        obs = self._stack_frames(state, anchor, ref, written)

        # Sample-time n-step return along the forward arc; `enter`
        # carries "window still inside the anchor's episode and backed
        # by in-sequence rows".
        enter = written.astype(jnp.float32)
        reward = jnp.zeros(anchor.shape, jnp.float32)
        for k in range(fs.n_step):
            slot = (anchor + k * fs.stride) % self.capacity
            avail = ((lo[slot] - ref == jnp.int32(k * fs.stride))
                     & (slot < state.size))
            use = enter * avail.astype(jnp.float32)
            reward = reward + use * float(fs.gamma ** k) * st["reward"][slot]
            enter = use * (1.0 - st["done"][slot])
        boot = (anchor + fs.n_step * fs.stride) % self.capacity
        has_boot = ((enter > 0.5)
                    & (lo[boot] - ref == jnp.int32(fs.n_step * fs.stride))
                    & (boot < state.size))
        next_obs = self._stack_frames(state, boot, lo[boot], has_boot)
        term = 1.0 - has_boot.astype(jnp.float32)
        return {"obs": obs, "action": st["action"][anchor],
                "reward": reward, "next_obs": next_obs,
                "done": term, "terminated": term}

    def sample(self, state: ReplayState, key: jax.Array, batch: int,
               beta: float | jax.Array | None = None):
        """Returns (indices, transitions, is_weights).

        ``beta`` overrides the constructor's constant IS exponent for
        this draw — the hook annealed schedules (β→1 over training, per
        Schaul et al.) thread through; may be a traced scalar.

        In frame mode ``transitions`` is the materialized float batch
        (see :meth:`materialize`); the stored uint8 frames never leave
        the buffer.
        """
        idx = self.sampler.sample(state.sampler_state, key, batch)
        if self.frame_store is not None:
            with jax.named_scope("frame_stack"):
                batch_tree = self.materialize(state, idx)
        else:
            batch_tree = jax.tree.map(lambda buf: buf[idx], state.storage)
        with jax.named_scope("is_weights"):
            prios = self.sampler.priorities(state.sampler_state)
            # Shared weight formula (one normalisation constant for the
            # reference and fused paths — see per.importance_from_selected).
            w = importance_from_selected(prios[idx], jnp.sum(prios),
                                         jnp.maximum(state.size, 1),
                                         self.beta if beta is None else beta)
        return idx, batch_tree, w

    def stamps(self, state: ReplayState, idx: jax.Array) -> jax.Array:
        """Write stamp pairs ``int32[..., 2]`` (counter, generation) of
        ``idx`` at sample time (pass back to :meth:`update_priorities`
        for a stale-safe deferred update)."""
        return jnp.stack(
            [state.write_stamp[idx], state.write_gen[idx]], axis=-1)

    def update_priorities(self, state: ReplayState, idx: jax.Array,
                          td_error: jax.Array,
                          stamp: jax.Array | None = None) -> ReplayState:
        """Rewrite priorities from fresh TD errors (Sec. 3.4.3: plain write).

        With ``stamp`` (the ``[..., 2]`` pairs captured by
        :meth:`stamps` when the batch was sampled) this becomes the
        runtime's out-of-band entry point: rows whose slot has been
        overwritten by newer experience since the sample are dropped
        instead of clobbering the newcomer's priority.  Matching both
        words keeps the check exact across int32 rollovers of the add
        counter (a slot recycled exactly 2^32 adds later repeats its
        stamp but not its generation).
        """
        with jax.named_scope("priority_write"):
            p = (jnp.abs(td_error) + self.eps) ** self.alpha
            if stamp is None:
                sampler_state = self.sampler.update(
                    state.sampler_state, idx, p)
                p_max = jnp.max(p)
            else:
                valid = ((state.write_stamp[idx] == stamp[..., 0])
                         & (state.write_gen[idx] == stamp[..., 1]))
                sampler_state = masked_update(
                    self.sampler, state.sampler_state, idx, p, valid)
                p_max = jnp.max(jnp.where(valid, p, 0.0))
        return state._replace(
            sampler_state=sampler_state,
            max_priority=jnp.maximum(state.max_priority, p_max),
        )


def _scatter_rows(rings: Any, rows: Any, idx: jax.Array) -> Any:
    return jax.tree.map(lambda buf, x: buf.at[idx].set(x), rings, rows)


@functools.partial(jax.jit, static_argnums=3)
def _write_ring_arc(rings: Any, rows: Any, pos: jax.Array,
                    capacity: int) -> Any:
    """Write ``rows`` (leading dim B) into the ring arc that starts at
    ``pos`` of every leaf of ``rings``.

    A scatter over a ring of frames lowers, on the TPU, only onto a
    row-major operand, so XLA copies the whole ring into that layout and
    back around every write.  An arc that does not cross the ring's end
    is one contiguous window instead, written by a dynamic-update-slice
    in the ring's own layout.  An arc that wraps is written one row at a
    time: rare (one insert in capacity / B), and neither a scatter nor a
    read of a ring window, either of which brings the relayout back.
    Rings batched under ``vmap`` (``train_many``) keep the scatter: a
    ``pos`` per ring would turn the branch into a select that computes
    both writes and picks between whole rings.  Jitted, so that eager
    callers reuse one compiled write per shape.
    """
    b = jax.tree.leaves(rows)[0].shape[0]

    @jax.custom_batching.custom_vmap
    def write(rings, rows, pos):
        def arc(rings):
            return jax.tree.map(
                lambda buf, x: jax.lax.dynamic_update_slice_in_dim(
                    buf, x, pos, 0), rings, rows)

        def wrap(rings):
            # The slot is recomputed from `pos`: indexing the arc's slot
            # array here puts a ring copy in every iteration on the TPU.
            def row(j, rings):
                return jax.tree.map(
                    lambda buf, x: jax.lax.dynamic_update_slice_in_dim(
                        buf, jax.lax.dynamic_slice_in_dim(x, j, 1, 0),
                        (pos + j) % capacity, 0), rings, rows)

            with jax.named_scope("ring_write_wrap"):
                return jax.lax.fori_loop(0, b, row, rings)

        return jax.lax.cond(pos + b <= capacity, arc, wrap, rings)

    @write.def_vmap
    def write_batched(axis_size, in_batched, rings, rows, pos):
        out = jax.vmap(
            lambda r, x, p: _scatter_rows(
                r, x, (p + jnp.arange(b)) % capacity),
            in_axes=tuple(jax.tree.map(lambda on: 0 if on else None,
                                       in_batched)),
            axis_size=axis_size)(rings, rows, pos)
        return out, jax.tree.map(lambda _: True, out)

    return write(rings, rows, pos)


def dirty_arcs(capacity: int, base_pos: int, n_new: int) -> list[tuple[int, int]]:
    """Half-open ring row ranges written since a base snapshot.

    ``base_pos`` is the write position captured at the base snapshot and
    ``n_new`` the transitions written since (callers derive it from the
    wrapping int32 add counter via a mod-2^32 difference — see
    ``train.replay_checkpoint.replay_dirty``); both come from plain host
    ints read off captured states, so the arc is exact, not an estimate.
    Wrapping the capacity boundary yields two ranges; ``n_new >=
    capacity`` means every row was rewritten and the whole leading dim
    is dirty.  Host-side helper for the incremental checkpoint layer
    (train/replay_checkpoint.py).
    """
    base_pos, n_new = int(base_pos), int(n_new)
    if n_new <= 0:
        return []
    if n_new >= capacity:
        return [(0, capacity)]
    end = base_pos + n_new
    if end <= capacity:
        return [(base_pos, end)]
    return [(base_pos, capacity), (0, end - capacity)]


def rows_to_ranges(rows) -> list[tuple[int, int]]:
    """Collapse a host iterable of touched row indices into sorted,
    merged half-open ranges — the shape the checkpoint layer's ``Rows``
    dirty spec takes."""
    out: list[tuple[int, int]] = []
    for r in sorted({int(r) for r in rows}):
        if out and r == out[-1][1]:
            out[-1] = (out[-1][0], r + 1)
        else:
            out.append((r, r + 1))
    return out
