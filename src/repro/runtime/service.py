"""`ReplayService` — the async actor–learner replay façade.

Wires the pipeline stages into one serving-shaped system:

    actors (threads, jitted rollout chunks)
        └── transition blocks ──> replay thread (ring writes, canonical
                                  buffer state, priority feedback applies)
                                        └── state snapshots ──> prefetch
                                                thread (slab sampling)
                                                    └── batch slabs ──>
    learner (caller thread, fused TD steps)
        └── deferred priority feedback ──> replay thread (stamped,
                                           out-of-band, exactly once)

The canonical replay state is owned by ONE thread (the replay thread);
every other stage sees it only as immutable snapshots, so there are no
locks around JAX state — just bounded queues.  ``sync=True`` degrades
the service to a strict synchronous mode: the exact ``agent_step``
iteration of the scan trainer driven step-by-step, which is the
apples-to-apples baseline the async speedup is measured against (and the
mode the equivalence tests pin to the scan trainer's learning curve).

Durability: pass a :class:`~repro.train.checkpoint.CheckpointManager` to
:meth:`ReplayService.run` and the service checkpoints the WHOLE replay
stack — params, optimizer moments, the canonical ``ReplayState``
(storage, priority tables, write stamps, ``max_priority``, ring
position), per-actor env states and PRNG stream positions, and the
prefetcher's draw counter — and auto-resumes from the latest checkpoint.
Checkpoints are incremental (delta chains over the ring arcs and touched
priority rows actually written since the last save — see
``train/replay_checkpoint.replay_dirty``) and, in async mode,
copy-on-write: nothing pauses.  The replay thread owns the canonical
state as immutable pytrees, so :class:`_CowSnapshotter` captures the
current state *reference* plus host counter watermarks on the learner
thread (microseconds) and serializes on its own thread while actors,
prefetcher, learner and replay thread keep running.  In-flight blocks
and feedback slabs are simply absent from the snapshot; the stamped
exactly-once feedback contract (PR 3) makes that safe on resume.  In
sync mode a killed run resumed from its checkpoint is BIT-IDENTICAL to
an uninterrupted one (pinned by ``tests/test_resume.py``); async resume
is tolerance-level by nature (thread interleaving changes which frames
land first).

Metrics cover the questions the paper's latency story raises at system
scale: learner steps/sec, environment frames/sec, queue depths (is the
sampler or the actor pool the bottleneck?), and priority-feedback
staleness (how many learner steps old is a priority when it lands).
"""
from __future__ import annotations

import collections
import functools
import queue
import threading
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.locks import tracked_queue
from repro.rl.dqn import DQNConfig, make_dqn
from repro.runtime.actor import ActorPool, make_rollout, put_with_stop
from repro.runtime.learner import Feedback, Learner, make_slab_learner
from repro.runtime.pipeline import PrefetchPipeline, make_slab_sampler
from repro.train import checkpoint as ckpt_mod
from repro.train import replay_checkpoint as rck


class RunResult(NamedTuple):
    params: Any          # final network params (dqn.evaluate accepts them)
    target_params: Any
    buffer: Any          # final canonical ReplayState
    metrics: dict


def _hstats(snap: obs.Snapshot, name: str) -> dict:
    """Histogram summary from a snapshot, zeros when absent/empty."""
    data = snap.data.get(name)
    if not data:
        return {"count": 0, "mean": 0.0, "min": 0.0, "max": 0.0,
                "p50": 0.0, "p95": 0.0, "p99": 0.0}
    return obs.hist_stats(data, snap.meta[name]["bounds"])


def _cval(snap: obs.Snapshot, name: str) -> float:
    data = snap.data.get(name)
    if not data:
        return 0.0
    v = data.get("value", 0.0)
    return 0.0 if v != v else float(v)  # NaN (unset gauge) -> 0


class _RunTelemetry:
    """Per-run observability bundle: registry, instruments, exporters.

    Built at ``run()`` entry and installed as the process-global
    registry for the run's duration, so spans recorded by the runtime
    threads and by the checkpoint/core layers all land in one place;
    :meth:`finish` restores the previous registry.  The service always
    runs with an ENABLED registry (the aggregate staleness/queue-depth
    stats were always kept); the user's Telemetry spec adds exporters
    and the replay-health probe on top.  ``RunResult.metrics`` is
    computed from a snapshot diff against the run-start snapshot, so a
    long-lived caller-supplied registry still yields per-run numbers.
    """

    def __init__(self, spec: obs.Telemetry | None):
        # No spec -> aggregate stats only: no exporters and no health
        # probe (probing spends a jitted dispatch per cadence tick,
        # which un-instrumented runs and perf benchmarks must not pay).
        self.spec = (spec if spec is not None
                     else obs.Telemetry(probe_every=0))
        self.registry = (self.spec.registry if self.spec.registry is not None
                         else obs.Registry(enabled=True))
        r = self.registry
        self.frames = r.counter(
            "frames_total", help="environment frames appended to replay")
        self.blocks = r.counter(
            "blocks_total", help="transition blocks absorbed by the core")
        self.fb_enqueued = r.counter(
            "feedback_enqueued_total",
            help="priority-feedback slabs enqueued")
        self.fb_applied = r.counter(
            "feedback_applied_total", help="priority-feedback slabs applied")
        self.staleness = r.histogram(
            "staleness_steps", bounds=obs.INT_BUCKETS,
            help="priority-feedback staleness in learner steps")
        self.work_depth = r.histogram(
            "work_queue_depth", bounds=obs.INT_BUCKETS,
            help="actor->replay queue depth per drained item")
        self.batch_depth = r.histogram(
            "batch_queue_depth", bounds=obs.INT_BUCKETS,
            help="prefetch->learner queue depth per drained item")
        self.snap_pause = r.histogram(
            "snapshot_pause_us", bounds=obs.US_BUCKETS,
            help="pipeline pause per snapshot: COW capture cost in async "
                 "mode, the blocking save in sync mode (microseconds)")
        self.base = r.snapshot()
        self.exporter = (obs.JsonlExporter(self.spec.metrics_out)
                         if self.spec.metrics_out else None)
        self.health: obs.ReplayHealth | None = None
        self._prev = obs.set_registry(r, profile=self.spec.profile)
        self._finished = False

    def probe_hook(self, sampler, batch: int):
        """Build the pipeline's probe callback (None when probing is
        off).  The callback runs on the prefetch thread at cadence: it
        re-derives the draw's CSP facts, refreshes the health gauges,
        and appends a JSONL snapshot line so the log is a timeline."""
        if self.spec.probe_every <= 0:
            return None
        self.health = obs.ReplayHealth(self.registry, sampler, batch,
                                       window=self.spec.window)

        def hook(state, key):
            self.health.update(state.sampler_state, key)
            if self.exporter is not None:
                self.exporter.write_snapshot(self.diff())

        return hook

    def diff(self) -> obs.Snapshot:
        return self.registry.snapshot().diff(self.base)

    def event(self, name: str, **fields) -> None:
        if self.exporter is not None:
            self.exporter.write_event(name, **fields)

    def finish(self, extra: dict | None = None) -> None:
        """Final JSONL snapshot + Prometheus dump, then restore the
        previously installed global registry.  Idempotent."""
        if self._finished:
            return
        self._finished = True
        if self.exporter is not None:
            self.exporter.write_snapshot(self.diff(), extra=extra)
            self.exporter.close()
        if self.spec.prometheus_out:
            obs.write_prometheus(self.registry, self.spec.prometheus_out)
        obs.set_registry(self._prev)


class ReplayService:
    """Asynchronous actor–learner replay service (or its strict-sync twin).

    Args:
      cfg: the DQN config (env, sampler, batch, schedules).
      num_actors: actor threads; each steps ``cfg.num_envs`` envs.
      sync: strict synchronous mode — requires ``num_actors=1`` and
        reproduces the scan trainer's iteration exactly.
      chunk_len: env steps per actor rollout chunk (one dispatch).
      slab: batches per prefetch draw / fused learner call.
      prefetch_depth: batch-slab queue depth (2 = double buffering).
      queue_size: transition-block + feedback queue bound (backpressure).
      min_size: buffer fill before sampling starts; defaults to the scan
        trainer's ``learn_start`` worth of frames.
      max_replay_ratio: optional frames-per-learner-step cap; actors
        pause when generation runs this far ahead of consumption (frees
        host cores for the learner on small machines).
      feedback_log: record the exact per-batch feedback sequence trace in
        ``metrics["feedback_seqs"]`` (O(learner steps) memory — for tests
        and debugging; the aggregate staleness stats are always kept).
      device: optional target device for prefetched batches.
      telemetry: an :class:`repro.obs.Telemetry` spec.  The service
        always keeps registry-backed run metrics (staleness, queue
        depths, snapshot pauses — the compat ``RunResult.metrics`` view
        is computed from them); the spec adds the JSONL/Prometheus
        exporters and the replay-health probe (live Fig. 7 KL gauge,
        CSP occupancy, fallback rate) on top.
    """

    def __init__(self, cfg: DQNConfig, *, num_actors: int = 2,
                 sync: bool = False, chunk_len: int = 32, slab: int = 4,
                 prefetch_depth: int = 2, queue_size: int = 8,
                 min_size: int | None = None,
                 max_replay_ratio: float | None = None,
                 feedback_log: bool = False, device=None,
                 telemetry: obs.Telemetry | None = None):
        if sync and num_actors != 1:
            raise ValueError("sync mode is defined for num_actors=1 "
                             f"(got {num_actors})")
        self.cfg = cfg
        self.sync = sync
        self.num_actors = num_actors
        self.chunk_len = chunk_len
        self.slab = slab
        self.prefetch_depth = prefetch_depth
        self.queue_size = queue_size
        self.device = device
        self.min_size = (min_size if min_size is not None else
                         max(cfg.batch,
                             min(cfg.learn_start * cfg.num_envs,
                                 cfg.replay_size)))
        self.max_replay_ratio = max_replay_ratio
        self.feedback_log = feedback_log
        self.telemetry = telemetry
        self.dqn = make_dqn(cfg)
        rb = self.dqn.replay
        # Frame-deduplicated storage chains stacks through ring adjacency
        # (slot i-stride must be the previous timestep of the same env
        # stream).  Interleaved blocks from multiple actors would break
        # that invariant on every chunk boundary, so pixel runs are
        # single-actor (the actor is still cfg.num_envs-wide).
        if rb.frame_store is not None and num_actors != 1:
            raise ValueError(
                "frame-store replay requires num_actors=1: stack "
                "materialization relies on single-stream ring adjacency "
                f"(got num_actors={num_actors})")
        # One jitted callable per pipeline stage, built once so repeated
        # run() calls (warmup, then measurement) reuse the compile cache.
        self._rollout = jax.jit(make_rollout(self.dqn, chunk_len))
        self._sample = jax.jit(make_slab_sampler(rb, cfg.batch, slab))
        # The slab's batch/weight buffers are consumed exactly once ->
        # donate them (args 5, 6); params/target stay undonated because
        # actors and the target alias them across calls.  The CPU backend
        # cannot reuse donated buffers and warns, so only donate off-CPU.
        donate = () if jax.default_backend() == "cpu" else (5, 6)
        self._learn = jax.jit(make_slab_learner(self.dqn),
                              donate_argnums=donate)
        # Actors pre-aggregate n-step rows in their own accumulators, so
        # the canonical buffer must not run its accumulator again.
        # The transition block is consumed by exactly this one write, so
        # its buffers are donated (off-CPU, as above).  The replay STATE
        # is never donated here or in the feedback apply: the prefetcher
        # snapshots self._bstate by reference and may be mid-draw on the
        # same buffers when the next write lands — donating the table
        # would invalidate the arrays under it.  XLA still updates the
        # priority rows in place inside the dispatch; donation would only
        # save the copy of the *unchanged* leaves, and correctness wins.
        donate_block = () if jax.default_backend() == "cpu" else (1,)
        self._add_block = jax.jit(
            functools.partial(rb.add_block, aggregated=True),
            donate_argnums=donate_block)

        def apply_feedback(state, idx, td, stamp):
            # Flatten [S, batch] row-major: masked_update resolves rows
            # duplicated across batches to their last occurrence, so one
            # scatter reproduces sequential-apply semantics (stamps can't
            # change between rows of a slab).  Stamps are (counter, gen)
            # pairs — keep their trailing word axis through the flatten.
            flat = lambda x: x.reshape(-1)
            return rb.update_priorities(
                state, flat(idx), flat(td), stamp=stamp.reshape(-1, 2))

        # The feedback slab (idx/td/stamp) is consumed exactly once by
        # this apply — donate those buffers; the state stays undonated
        # (prefetcher aliasing, see above).  The dirty-row log for
        # incremental snapshots takes a HOST copy of fb.idx before the
        # apply runs, so donating idx stays safe.
        donate_fb = () if jax.default_backend() == "cpu" else (1, 2, 3)
        self._apply_feedback = jax.jit(apply_feedback,
                                       donate_argnums=donate_fb)
        self._agent_step = jax.jit(self.dqn.agent_step)
        # (fb_applied_at_append, host idx rows) log the replay thread
        # feeds and the COW snapshotter consumes; None when the run has
        # no checkpoint manager (zero cost on the hot path).
        self._fb_rows: collections.deque | None = None
        # (learned, synced) -> cached non-buffer sync dirty tree.
        self._sync_dirty_tpl: dict = {}

    # ------------------------------------------------------------------ #

    def run(self, key: jax.Array, n_steps: int,
            manager: ckpt_mod.CheckpointManager | None = None) -> RunResult:
        """Train for ``n_steps`` — scan-trainer iterations in sync mode,
        learner steps (rounded up to a whole slab) in async mode.

        With a ``manager`` the run checkpoints periodically (and on
        preemption) and AUTO-RESUMES from the manager's latest
        checkpoint; ``n_steps`` is the absolute target, so a resumed run
        executes only the remainder.  The saved snapshot embeds the run
        key, so the resumed process does not need to pass the same
        ``key`` — but sync mode validates ``n_steps`` (the step-key array
        derivation depends on it).
        """
        if manager is not None:
            manager.install_preemption_hook()  # no-op off the main thread
        tel = _RunTelemetry(self.telemetry)
        try:
            if self.sync:
                result = self._run_sync(key, n_steps, manager, tel)
            else:
                result = self._run_async(key, n_steps, manager, tel)
        finally:
            tel.finish(extra={"mode": "sync" if self.sync else "async"})
        return result

    # --- checkpoint snapshot targets ----------------------------------- #

    def _key_data_struct(self):
        kd = jax.random.key_data(jax.random.key(0))
        return jax.ShapeDtypeStruct(kd.shape, kd.dtype)

    def _sync_target(self):
        return {"key_data": self._key_data_struct(),
                "state": jax.eval_shape(self.dqn.init, jax.random.key(0))}

    def _async_target(self):
        a = jax.eval_shape(self.dqn.init, jax.random.key(0))
        actor_t = {"env_state": a.env_state, "obs": a.obs,
                   "ep_ret": jax.ShapeDtypeStruct((self.cfg.num_envs,),
                                                  jnp.float32),
                   # the actor's own n-step window (None when n_step=1);
                   # same abstract shape as the buffer's in-state one
                   "nstep": a.buffer.nstep}
        return {"key_data": self._key_data_struct(),
                "params": a.params, "target_params": a.target_params,
                "opt_m": a.opt_m, "opt_v": a.opt_v, "buffer": a.buffer,
                "actors": [actor_t for _ in range(self.num_actors)]}

    def _restore(self, manager, target, mode: str, **expected):
        """(step, snapshot, meta) from the latest checkpoint, or Nones.

        The meta is validated BEFORE the arrays load, so a topology
        mismatch (actor count, mode, n_steps) reads as what it is rather
        than a leaf-count error.  The buffer subtree is device_put with
        the CURRENT sampler's mesh placement (``replay_shardings``), so a
        snapshot saved on 8 shards resumes on 2 — or on one device —
        transparently.
        """
        step = manager.latest_step()
        if step is None:
            return None, None, None
        meta = ckpt_mod.load_meta(manager.directory, step)
        self._check_meta(meta, mode, **expected)
        snap = ckpt_mod.restore(
            manager.directory, step, target,
            rck.replay_shardings(self.dqn.replay, target))
        return step, snap, meta

    @staticmethod
    def _check_meta(meta: dict, mode: str, **expected) -> None:
        if meta.get("mode") != mode:
            raise ValueError(f"checkpoint was written by a "
                             f"{meta.get('mode')!r}-mode run, cannot "
                             f"resume in {mode!r} mode")
        for k, want in expected.items():
            # An absent key is as much a topology mismatch as a wrong
            # value — .get(k, want) would silently accept a checkpoint
            # written before the field existed.
            if k not in meta:
                raise ValueError(f"checkpoint meta has no {k!r} field "
                                 f"(expected {k}={want}); it was written "
                                 f"by an incompatible service version")
            if meta[k] != want:
                raise ValueError(f"checkpoint {k}={meta[k]} does not match "
                                 f"this service's {k}={want}")

    # --- strict synchronous mode -------------------------------------- #

    def _run_sync(self, key: jax.Array, n_steps: int,
                  manager: ckpt_mod.CheckpointManager | None,
                  tel: _RunTelemetry) -> RunResult:
        cfg = self.cfg
        start = 0
        state = None
        marks = None       # replay watermarks of the last on-disk save
        if manager is not None:
            step, snap, meta = self._restore(manager, self._sync_target(),
                                             "sync", n_steps=n_steps)
            if step is not None:
                key = jax.random.wrap_key_data(snap["key_data"])
                state, start = snap["state"], int(meta["step"])
                # The restored state IS the manager's latest checkpoint,
                # so the next save can be a delta against it.
                marks = rck.replay_marks(state.buffer)
        if state is None:
            state = self.dqn.init(key)
        # Same step-key derivation as the scan trainer's _train.
        keys = jax.random.split(jax.random.fold_in(key, 1), n_steps)
        returns = []
        preempted_at = None
        prev_save_t = start
        t0 = time.perf_counter()
        t_first_learn = None
        t_end = start
        for t in range(start, n_steps):
            if t == max(cfg.learn_start, start):
                jax.block_until_ready(state.params)
                t_first_learn = time.perf_counter()
                compiles0 = obs.compile_count()
            state, m = self._agent_step(state, keys[t])
            returns.append(m["return_mean"])
            t_end = t + 1
            if manager is not None and (manager.should_save(t + 1)
                                        or t + 1 == n_steps):
                dirty = (self._sync_dirty(state, marks, prev_save_t, t + 1)
                         if marks is not None else None)
                # Sync saves block the training loop, so the whole save
                # IS the pipeline pause — record it in the same
                # instrument the async COW capture uses (uniform schema).
                t_save = time.perf_counter()
                manager.save(t + 1,
                             {"key_data": jax.random.key_data(key),
                              "state": state},
                             meta={"mode": "sync", "step": t + 1,
                                   "n_steps": n_steps},
                             dirty=dirty)
                tel.snap_pause.observe(
                    (time.perf_counter() - t_save) * 1e6)
                tel.event("checkpoint", step=t + 1,
                          delta=dirty is not None)
                marks = rck.replay_marks(state.buffer)
                prev_save_t = t + 1
                if manager.preempted and t + 1 < n_steps:
                    preempted_at = t + 1
                    break
        jax.block_until_ready(state.params)
        wall_end = time.perf_counter()
        compiles = (obs.compile_count() - compiles0
                    if t_first_learn is not None else 0)
        learner_steps = sum(
            1 for t in range(start, t_end)
            if t >= cfg.learn_start and t % cfg.train_every == 0)
        learn_wall = (wall_end - t_first_learn if t_first_learn is not None
                      else float("nan"))
        curve = np.asarray(jnp.stack(returns)) if returns else np.zeros(0)
        snap = tel.diff()
        pause = _hstats(snap, "snapshot_pause_us")
        metrics = {
            "mode": "sync",
            "learner_steps": learner_steps,
            "learner_steps_per_sec": (learner_steps / learn_wall
                                      if learner_steps else 0.0),
            "wall_time": wall_end - t0,
            "compiles": compiles,
            "frames": (t_end - start) * cfg.num_envs,
            "frames_per_sec": ((t_end - start) * cfg.num_envs
                               / max(wall_end - t0, 1e-9)),
            "return_mean": float(curve[-1]) if len(curve) else 0.0,
            "return_curve": curve,
            # β the last executed step's draw used — the annealed value,
            # not the frozen constructor default.
            "beta": float(self.dqn.beta_at(max(t_end - 1, 0))),
            # Sync draws apply feedback inline — staleness is zero by
            # construction; the keys exist so both modes share a schema.
            "staleness": {"count": 0, "mean": 0.0, "max": 0,
                          "p50": 0, "p95": 0, "p99": 0},
            "queue_depth": {"work_mean": 0.0, "batch_mean": 0.0},
            "resumed_from": start if start else None,
            "preempted_at": preempted_at,
            # Uniform snapshot/checkpoint schema with async mode: here
            # every save blocks the loop, so count == saved and the
            # pause histogram holds whole save latencies.
            "snapshot": {
                "count": pause["count"],
                "saved": pause["count"],
                "pause_us_mean": pause["mean"],
                "pause_us_max": pause["max"],
                "drain_cycles": 0,
            },
            "checkpoint": self._checkpoint_metrics(snap, manager),
        }
        return RunResult(params=state.params,
                         target_params=state.target_params,
                         buffer=state.buffer, metrics=metrics)

    @staticmethod
    def _checkpoint_metrics(snap: obs.Snapshot, manager) -> dict:
        """Checkpoint overhead view shared by both modes (zeros when the
        run had no manager)."""
        save = _hstats(snap, "span_checkpoint_save_ms")
        return {
            "saves": save["count"],
            "save_ms_mean": save["mean"],
            "save_ms_max": save["max"],
            "full_bytes": _cval(snap, "checkpoint_full_bytes"),
            "delta_bytes": _cval(snap, "checkpoint_delta_bytes"),
            "chain_len": (manager._chain_len if manager is not None else 0),
        }

    def _sync_dirty(self, state, marks: dict, t0: int, t1: int):
        """Dirty tree for the sync snapshot covering steps ``[t0, t1)``.

        The scan step's scheduling is structural — step t learns iff
        ``t >= learn_start and t % train_every == 0`` and target-syncs
        iff ``t % target_sync == 0`` — so whether params / optimizer
        moments / target / priority tables changed in the window is
        decidable host-side without reading a single array.  Storage and
        write stamps are dirty exactly on the ring arc the window's adds
        wrote; priority tables are arc-only when no learning happened
        and full otherwise (the sampled rows live inside the jit).
        Everything small (scalars, env state, episode accounting) is
        always saved.
        """
        cfg = self.cfg
        learned = any(t >= cfg.learn_start and t % cfg.train_every == 0
                      for t in range(t0, t1))
        synced = any(t % cfg.target_sync == 0 for t in range(t0, t1))
        # The non-buffer part of the dirty tree depends only on the two
        # predicates (the state's structure is fixed for the run), so
        # cache it — rebuilding ~6 tree maps per save is measurable at
        # the benchmark's save cadence.
        tpl = self._sync_dirty_tpl.get((learned, synced))
        if tpl is None:
            tpl = jax.tree.map(lambda _: True, state)._replace(
                params=ckpt_mod.dirty_like(state.params, learned),
                target_params=ckpt_mod.dirty_like(state.target_params,
                                                  synced),
                opt_m=ckpt_mod.dirty_like(state.opt_m, learned),
                opt_v=ckpt_mod.dirty_like(state.opt_v, learned))
            self._sync_dirty_tpl[(learned, synced)] = tpl
        bd = rck.replay_dirty(self.dqn.replay, state.buffer, marks)
        if learned:
            bd = bd._replace(sampler_state=ckpt_mod.dirty_like(
                state.buffer.sampler_state, True))
        return {"key_data": True, "state": tpl._replace(buffer=bd)}

    # --- asynchronous mode -------------------------------------------- #

    def _run_async(self, key: jax.Array, n_steps: int,
                   manager: ckpt_mod.CheckpointManager | None,
                   tel: _RunTelemetry) -> RunResult:
        cfg = self.cfg
        start_steps, prefetch_draw, frames0, blocks0 = 0, 0, 0, 0
        actor_resume = None
        snap = None
        resume_marks = None
        if manager is not None:
            step, snap, meta = self._restore(manager, self._async_target(),
                                             "async",
                                             num_actors=self.num_actors)
            if step is not None:
                key = jax.random.wrap_key_data(snap["key_data"])
                start_steps = int(meta["learner_steps"])
                prefetch_draw = int(meta["prefetch_draw"])
                frames0 = int(meta["frames"])
                blocks0 = int(meta["blocks"])
                actor_resume = [
                    {**a, "step": meta["actor_steps"][i],
                     "chunk": meta["actor_chunks"][i]}
                    for i, a in enumerate(snap["actors"])]
        if snap is not None and snap.get("params") is not None:
            params0, target0 = snap["params"], snap["target_params"]
            opt_m0, opt_v0 = snap["opt_m"], snap["opt_v"]
            self._bstate = snap["buffer"]
            # The restored buffer IS the manager's latest on-disk state:
            # the first snapshot of this run can be a delta against it.
            # fb_applied is 0 in THIS run's counter space (fresh log).
            resume_marks = {**rck.replay_marks(self._bstate),
                            "fb_applied": 0}
        else:
            state0 = self.dqn.init(key)
            params0, target0 = state0.params, state0.target_params
            opt_m0, opt_v0 = state0.opt_m, state0.opt_v
            self._bstate = state0.buffer          # canonical replay state
        params_box = [params0]                # actors read, learner swaps
        work_q: queue.Queue = tracked_queue("runtime.work_q", self.queue_size)
        self._work_q = work_q
        batch_q: queue.Queue = tracked_queue(
            "runtime.batch_q", self.prefetch_depth)
        stop = threading.Event()
        self._fb_rows = collections.deque() if manager is not None else None
        # The rec dict is the CONTROL PLANE: counters the COW snapshot
        # consistency contract and the replay-ratio budget read (the
        # publish-state-before-bump ordering in _replay_loop depends on
        # them staying plain same-thread ints).  Pure observability
        # aggregates (staleness, queue depths, snapshot pauses) live in
        # the telemetry registry's lock-free instruments instead.
        rec = {"frames": 0, "blocks": 0,
               "fb_enqueued": 0, "fb_applied": 0,
               "feedback_seqs": [] if self.feedback_log else None,
               "returns": collections.deque(maxlen=256), "error": None}

        def feedback_put(fb):
            ok = put_with_stop(work_q, ("feedback", fb), stop)
            if ok:
                rec["fb_enqueued"] += 1
                tel.fb_enqueued.add()
            return ok

        last_saved = [start_steps]
        snapper: _CowSnapshotter | None = None

        def on_slab(params, target_params, opt_m, opt_v):
            """Checkpoint hook, on the learner (caller) thread.  O(µs):
            the snapshotter only grabs references and counters here; the
            serialization runs on its own thread.  Returns True to stop
            the learner early (preemption)."""
            if manager is None:
                return False
            steps = learner.steps_done
            preempt = manager.preempted
            due = steps - last_saved[0] >= manager.save_interval
            if not (preempt or due):
                return False
            if steps != last_saved[0] and snapper.capture(
                    steps, params, target_params, opt_m, opt_v):
                last_saved[0] = steps
            return preempt and steps < n_steps

        learner = Learner(
            self._learn, in_q=batch_q, feedback_put=feedback_put,
            publish=lambda p: params_box.__setitem__(0, p),
            target_sync=cfg.target_sync, stop=stop,
            start_steps=start_steps, on_slab=on_slab)
        replay_thread = threading.Thread(
            target=self._replay_loop, name="replay-core",
            args=(work_q, batch_q, stop, learner, rec, tel), daemon=True)
        budget_fn = None
        if self.max_replay_ratio is not None:
            ratio, head = self.max_replay_ratio, self.min_size

            def budget_fn():
                return (frames0 + rec["frames"]
                        < head + ratio * max(learner.steps_done, 1))

        # No PauseGate: snapshots are copy-on-write, nothing ever parks.
        pool = ActorPool(
            self.dqn, self._rollout, num_actors=self.num_actors,
            params_fn=lambda: params_box[0], out_q=work_q, stop=stop,
            base_key=key, chunk_len=self.chunk_len, budget_fn=budget_fn,
            resume_states=actor_resume)
        prefetch = PrefetchPipeline(
            self._sample,
            state_fn=lambda: (self._bstate, learner.steps_done),
            out_q=batch_q, stop=stop, base_key=key, slab=self.slab,
            min_size=self.min_size, device=self.device,
            beta_fn=self.dqn.beta_at,
            start_draw=prefetch_draw, start_seq=start_steps,
            probe=tel.probe_hook(self.dqn.replay.sampler,
                                 self.cfg.batch * self.slab),
            probe_every=tel.spec.probe_every)
        if manager is not None:
            snapper = _CowSnapshotter(self, manager, pool, prefetch, key,
                                      rec, frames0, blocks0,
                                      resume_marks=resume_marks, tel=tel)

        def shutdown():
            stop.set()
            pool.join(timeout=10.0)
            prefetch.join(timeout=10.0)
            replay_thread.join(timeout=10.0)
            if snapper is not None:
                snapper.drain()  # finish any in-flight snapshot write

        def raise_worker_errors():
            if rec["error"] is not None:
                raise RuntimeError("replay thread failed") from rec["error"]
            if prefetch.error is not None:
                raise RuntimeError(
                    "prefetch pipeline failed") from prefetch.error
            if snapper is not None and snapper.error is not None:
                raise RuntimeError(
                    "snapshot writer failed") from snapper.error
            pool.raise_errors()

        t0 = time.perf_counter()
        replay_thread.start()
        pool.start()
        prefetch.start()
        try:
            params, target_params = learner.run(
                params0, target0, opt_m0, opt_v0, n_steps)
            jax.block_until_ready(params)
            t_end = time.perf_counter()
            compiles = (obs.compile_count()
                        - learner.compiles_at_first_step)
        except BaseException:
            # Join first, then surface the root cause: a learner failure
            # is often secondary to a worker-thread fault, and raising
            # from it here chains both tracebacks.
            shutdown()
            raise_worker_errors()
            raise
        shutdown()
        raise_worker_errors()
        preempted_at = None
        if manager is not None:
            if manager.preempted and learner.steps_done < n_steps:
                preempted_at = learner.steps_done
            if learner.steps_done != last_saved[0]:
                # Final checkpoint: threads are joined and the replay
                # thread drained every queue before exiting, so the state
                # is already quiescent — no pause protocol needed.
                self._save_snapshot(manager, learner.steps_done, params,
                                    target_params, learner.opt_m,
                                    learner.opt_v, key, pool, prefetch,
                                    rec, frames0, blocks0)

        learn_wall = (t_end - learner.first_step_time
                      if learner.first_step_time else float("nan"))
        wall = t_end - t0
        returns = np.asarray(rec["returns"])
        snap = tel.diff()
        stale = _hstats(snap, "staleness_steps")
        workd = _hstats(snap, "work_queue_depth")
        batchd = _hstats(snap, "batch_queue_depth")
        pause = _hstats(snap, "snapshot_pause_us")
        metrics = {
            "mode": "async",
            "learner_steps": learner.steps_done - start_steps,
            "total_learner_steps": learner.steps_done,
            "learner_steps_per_sec": (
                (learner.steps_done - start_steps) / learn_wall
                if learner.steps_done > start_steps else 0.0),
            "wall_time": wall,
            # Compiles from the learner's first step to the end of the
            # run: the window of learner_steps_per_sec.
            "compiles": compiles,
            "frames": rec["frames"],
            "total_frames": frames0 + rec["frames"],
            # Same zero-wall guard as the sync path: a run that resumes
            # at its target does zero work in epsilon time.
            "frames_per_sec": rec["frames"] / max(wall, 1e-9),
            "blocks": rec["blocks"],
            "return_mean": (float(returns[-64:].mean())
                            if returns.size else 0.0),
            "recent_returns": returns[-64:],
            # β of the prefetcher's latest slab draw (annealed), falling
            # back to the schedule at the last executed learner step
            # (same convention as sync mode) if no draw happened.
            "beta": (prefetch.last_beta if prefetch.last_beta is not None
                     else float(self.dqn.beta_at(
                         max(learner.steps_done - 1, 0)))),
            "feedback_seqs": rec["feedback_seqs"],
            # Feedback slabs (slab x batch priority rows each) applied.
            "feedback_applied": rec["fb_applied"],
            # Compatibility view over the registry's staleness histogram:
            # count/sum are exact, max is exact, and the INT_BUCKETS
            # bounds make the percentiles exact for staleness <= 64.
            "staleness": {
                "count": stale["count"],
                "mean": stale["mean"],
                "max": int(stale["max"]),
                "p50": int(stale["p50"]),
                "p95": int(stale["p95"]),
                "p99": int(stale["p99"]),
            },
            "queue_depth": {
                "work_mean": workd["mean"],
                "batch_mean": batchd["mean"],
            },
            "losses": [float(l) for l in learner.losses],
            "resumed_from": start_steps if start_steps else None,
            "preempted_at": preempted_at,
            # COW snapshot accounting: "pause" is the learner-thread
            # capture cost (reference grab + watermark reads), the only
            # stall a snapshot inflicts on the pipeline.  drain_cycles
            # is the number of full pause→drain quiesce protocols run —
            # structurally zero since the COW rework, kept as a column
            # so the benchmark trajectory records the regime change.
            "snapshot": {
                "count": pause["count"],
                "saved": snapper.saved if snapper is not None else 0,
                "pause_us_mean": pause["mean"],
                "pause_us_max": pause["max"],
                "drain_cycles": 0,
            },
            "checkpoint": self._checkpoint_metrics(snap, manager),
        }
        if tel.health is not None:
            metrics["health"] = {
                "kl_nats": tel.health.monitor.kl(),
                "chi2": tel.health.monitor.chi_square(),
                "csp_occupancy": _cval(snap, "csp_occupancy"),
                "fallback_draws": _cval(snap, "fallback_draws"),
                "probe_draws": _cval(snap, "probe_draws"),
            }
        return RunResult(params=params, target_params=target_params,
                         buffer=self._bstate, metrics=metrics)

    # --- snapshot protocol -------------------------------------------- #

    def _async_dirty(self, bstate, snap: dict, marks: dict, rows):
        """Dirty tree for an async snapshot relative to ``marks``.

        The buffer gets the exact ring-arc + touched-priority-row set;
        every other component (params, optimizer moments, actor states,
        the key) changes every slab or is tiny — always full.
        """
        bd = rck.replay_dirty(self.dqn.replay, bstate, marks,
                              priority_rows=rows)
        return {k: (bd if k == "buffer" else ckpt_mod.dirty_like(v, True))
                for k, v in snap.items()}

    def _save_snapshot(self, manager, steps, params, target_params,
                       opt_m, opt_v, key, pool, prefetch, rec,
                       frames0, blocks0) -> None:
        run_states = pool.run_states()
        snap = {"key_data": jax.random.key_data(key),
                "params": params, "target_params": target_params,
                "opt_m": opt_m, "opt_v": opt_v, "buffer": self._bstate,
                "actors": [{"env_state": rs["env_state"], "obs": rs["obs"],
                            "ep_ret": rs["ep_ret"], "nstep": rs["nstep"]}
                           for rs in run_states]}
        meta = {"mode": "async", "learner_steps": int(steps),
                "num_actors": self.num_actors,
                "prefetch_draw": int(prefetch.draws),
                "frames": int(frames0 + rec["frames"]),
                "blocks": int(blocks0 + rec["blocks"]),
                "actor_steps": [int(rs["step"]) for rs in run_states],
                "actor_chunks": [int(rs["chunk"]) for rs in run_states]}
        manager.save(int(steps), snap, meta=meta)

    def _replay_loop(self, work_q: queue.Queue, batch_q: queue.Queue,
                     stop: threading.Event, learner: Learner,
                     rec: dict, tel: _RunTelemetry) -> None:
        """The one owner of the canonical replay state: applies transition
        blocks and deferred priority feedback in arrival order, publishes
        immutable snapshots for the prefetcher.  Each publish REPLACES
        ``self._bstate`` with a fresh pytree (never mutates), which is
        what lets the COW snapshotter treat any captured reference as a
        consistent checkpoint without pausing this thread."""
        try:
            bstate = self._bstate
            while True:
                try:
                    with obs.span("replay_wait"):
                        tag, item = work_q.get(timeout=0.05)
                except queue.Empty:
                    if stop.is_set() and learner.finished and work_q.empty():
                        return
                    continue
                # Ordering contract with the snapshot drain check: publish
                # the new canonical state BEFORE bumping the applied
                # counters, so "counters say drained" implies the saved
                # self._bstate already contains the counted item.
                if tag == "block":
                    if item.transitions is not None:  # None: all rows fell
                        with obs.span("add_block"):    # in n-step warm-up
                            bstate = self._add_block(bstate,
                                                     item.transitions)
                        self._bstate = bstate
                    rec["frames"] += item.frames
                    rec["blocks"] += 1
                    tel.frames.add(item.frames)
                    tel.blocks.add()
                    rec["returns"].extend(item.completed_returns.tolist())
                else:  # deferred priority feedback (one slab, S batches)
                    fb: Feedback = item
                    if self._fb_rows is not None:
                        # Dirty-row log for incremental snapshots: append
                        # BEFORE the apply/publish (host copy — fb.idx is
                        # donated to the apply below), so any feedback
                        # visible in a captured state has its rows in the
                        # log and the COW dirty set is a superset, never
                        # an under-count.  Stale (stamp-dropped) rows get
                        # logged too; marking them dirty just re-writes
                        # identical bytes.
                        with obs.span("host_sync"):
                            rows = np.asarray(fb.idx).ravel()
                        self._fb_rows.append((rec["fb_applied"], rows))
                    with obs.span("apply_feedback", slab=fb.seq0):
                        bstate = self._apply_feedback(
                            bstate, fb.idx, fb.td, fb.stamp)
                    self._bstate = bstate
                    s = int(fb.idx.shape[0])
                    if rec["feedback_seqs"] is not None:
                        rec["feedback_seqs"].extend(
                            range(fb.seq0, fb.seq0 + s))
                    # The slab's S batches share one staleness value.
                    tel.staleness.observe_n(
                        learner.steps_done - fb.version, s)
                    rec["fb_applied"] += 1
                    tel.fb_applied.add()
                tel.work_depth.observe(work_q.qsize())
                tel.batch_depth.observe(batch_q.qsize())
        except BaseException as e:
            rec["error"] = e
            stop.set()


class _CowSnapshotter:
    """Copy-on-write checkpoint writer for the async runtime.

    The learner-thread half (:meth:`capture`) grabs immutable pytree
    references and host counter watermarks — no pause gate, no drain.
    The replay thread publishes every new canonical state as a *fresh*
    pytree, so a captured reference is a consistent snapshot by
    construction; a dedicated worker thread serializes it to disk while
    actors, prefetcher, learner and replay thread keep running.

    Consistency contract:

    * **state ⊇ counters.**  Capture reads the applied-feedback counter
      BEFORE the state reference, and the replay thread publishes state
      BEFORE bumping the counter — so the dirty rows computed from the
      previous save's counter watermark are a *superset* of what changed
      between the two states; a superset only re-writes identical bytes.
    * **in-flight work is absent, not torn.**  Blocks and feedback slabs
      still in queues at capture are simply not in the snapshot.  On
      resume the stamped exactly-once feedback contract (PR 3) makes the
      missing applies safe: priorities are one slab staler, which async
      resume tolerates by contract (``tests/test_resume.py`` pins the
      sequence-gaplessness of the resumed run, not frame identity).
    * **one save in flight.**  ``capture`` skips (returns False) while
      the worker is still writing, so manager chain bookkeeping and the
      marks/row-log pruning are strictly serialized.
    """

    def __init__(self, service: ReplayService, manager, pool, prefetch,
                 key, rec: dict, frames0: int, blocks0: int,
                 resume_marks: dict | None = None,
                 tel: _RunTelemetry | None = None):
        self._svc = service
        self._manager = manager
        self._pool = pool
        self._prefetch = prefetch
        self._key = key
        self._rec = rec
        self._tel = tel
        self._frames0 = frames0
        self._blocks0 = blocks0
        # Watermarks of the last successful on-disk save (None -> the
        # next save is full).  Only the worker thread writes this after
        # construction.
        self.marks = resume_marks
        self.saved = 0
        self.error: BaseException | None = None
        # The run key never changes — materialize its raw data once so
        # capture() does not dispatch a jax op per snapshot.
        self._key_data = np.asarray(jax.random.key_data(key))
        self._busy = threading.Event()
        self._q: queue.Queue = tracked_queue("runtime.snapshot_q", 1)
        self._thread = threading.Thread(target=self._worker,
                                        name="replay-snapshot", daemon=True)
        self._thread.start()

    def capture(self, steps, params, target_params, opt_m, opt_v) -> bool:
        """Learner-thread half: O(µs) reference grab — no device syncs,
        no tree walks; the dirty-set computation and the ``int()`` reads
        of the captured buffer's scalars happen on the worker thread
        (the captured pytree is frozen, so they read the same values).
        False = skipped (previous snapshot still writing, an error is
        pending, or an actor has not published its first run state yet).
        """
        if self.error is not None or self._busy.is_set():
            return False
        run_states = self._pool.run_states()
        if any(rs is None for rs in run_states):
            return False
        t0 = time.perf_counter()
        rec = self._rec
        a_now = rec["fb_applied"]      # read BEFORE the state reference
        bstate = self._svc._bstate
        snap = {"key_data": self._key_data,
                "params": params, "target_params": target_params,
                "opt_m": opt_m, "opt_v": opt_v, "buffer": bstate,
                "actors": [{"env_state": rs["env_state"], "obs": rs["obs"],
                            "ep_ret": rs["ep_ret"], "nstep": rs["nstep"]}
                           for rs in run_states]}
        meta = {"mode": "async", "learner_steps": int(steps),
                "num_actors": self._svc.num_actors,
                "prefetch_draw": int(self._prefetch.draws),
                "frames": int(self._frames0 + rec["frames"]),
                "blocks": int(self._blocks0 + rec["blocks"]),
                "actor_steps": [int(rs["step"]) for rs in run_states],
                "actor_chunks": [int(rs["chunk"]) for rs in run_states]}
        # Pause accounting covers the capture work itself; the queue put
        # below wakes the worker, whose overlapped serialization shows
        # up in the benchmark's wall-overhead column, not here.
        pause_us = (time.perf_counter() - t0) * 1e6
        if self._tel is not None:
            self._tel.snap_pause.observe(pause_us)
        self._busy.set()
        self._q.put((int(steps), snap, meta, a_now))
        return True

    def _worker(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                return
            steps, snap, meta, a_now = job
            try:
                bstate = snap["buffer"]
                dirty = None
                if self.marks is not None:
                    # Reading the row log here (after capture) can only
                    # see MORE entries than existed at capture — extra
                    # rows widen the dirty set, which is always safe.
                    a_base = self.marks["fb_applied"]
                    rows = [r for seq, arr in list(self._svc._fb_rows)
                            if seq >= a_base for r in arr]
                    dirty = self._svc._async_dirty(bstate, snap,
                                                   self.marks, rows)
                next_marks = {**rck.replay_marks(bstate),
                              "fb_applied": a_now}
                self._manager.save(steps, snap, meta=meta, dirty=dirty)
                self.marks = next_marks
                self.saved += 1
                if self._tel is not None:
                    self._tel.event("checkpoint", step=steps,
                                    delta=dirty is not None)
                # Entries older than the new watermark can never be
                # dirty again — prune (popleft racing the replay
                # thread's append is deque-safe).
                log = self._svc._fb_rows
                while log and log[0][0] < next_marks["fb_applied"]:
                    log.popleft()
            except BaseException as e:
                self.error = e   # surfaced by raise_worker_errors
            finally:
                self._busy.clear()

    def drain(self, timeout: float = 120.0) -> None:
        """Wait out any in-flight save, then stop the worker thread.
        After this returns the manager is safe to use from the caller
        (the final quiescent save)."""
        deadline = time.monotonic() + timeout
        while self._busy.is_set() and time.monotonic() < deadline:
            time.sleep(0.002)
        try:
            self._q.put_nowait(None)
        except queue.Full:
            pass
        self._thread.join(timeout=10.0)
