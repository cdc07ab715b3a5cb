"""Learner loop with a deferred priority-feedback queue.

The learner consumes prefetched :class:`~repro.runtime.pipeline.BatchSlab`s
and applies one fused jitted call per slab — a ``lax.scan`` of S TD
gradient steps built from the DQN's ``learn`` piece — so the per-step
Python dispatch cost is amortized S-fold.  The slab's batch and weight
buffers are donated to that call (they are consumed exactly once).

Priority feedback is *deferred*: instead of writing TD errors back into
the sampler state inline (which would serialize the learner behind the
replay service), each slab's ``(seq0, idx, |td|, stamp, version)`` record
is enqueued and the replay thread applies it out-of-band via the
buffer's stamped ``update_priorities`` — one jitted apply per slab, rows
in learner-step order.  Sequence numbers make the exactly-once /
in-order contract testable; the sample-time version makes staleness
(learner steps between draw and priority write) measurable.

Target-network sync and params publication to the actor pool happen at
slab granularity on the host: ``target_sync`` is rounded up to the next
slab boundary, and every completed slab publishes the fresh params
snapshot (a Python reference swap — actors pick it up at their next
chunk).
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import compile_count, get_registry, span
from repro.runtime.pipeline import BatchSlab


class Feedback(NamedTuple):
    """One slab's deferred priority updates, learner -> replay thread.

    Carries S batches (slab rows in learner-step order) so the replay
    thread applies them in one jitted call; row j corresponds to global
    batch sequence number ``seq0 + j``.
    """

    seq0: int           # global batch sequence number of row 0 (FIFO)
    idx: jax.Array      # int32[S, batch] sampled replay rows
    td: jax.Array       # float32[S, batch] fresh TD errors
    stamp: jax.Array    # int32[S, batch, 2] (counter, gen) write stamps
    #                     captured at sample time
    version: int        # learner steps completed when the slab was drawn


def make_slab_learner(dqn) -> Callable:
    """Build the jittable fused slab step
    ``(params, target, m, v, step0, batch, weights) ->
    (params, m, v, td [S, batch], loss [S])``."""
    learn = dqn.learn

    def learn_slab(params, target_params, opt_m, opt_v, step0, batch,
                   weights):
        def body(carry, inp):
            params, m, v = carry
            b, w, i = inp
            params, m, v, td, loss = learn(
                params, target_params, m, v, step0 + i, b, w)
            return (params, m, v), (td, loss)

        s = weights.shape[0]
        (params, opt_m, opt_v), (td, loss) = jax.lax.scan(
            body, (params, opt_m, opt_v),
            (batch, weights, jnp.arange(s, dtype=jnp.int32)))
        return params, opt_m, opt_v, td, loss

    return learn_slab


class Learner:
    """Drives the fused slab step; runs on the service's caller thread."""

    def __init__(self, learn_fn: Callable, *, in_q: queue.Queue,
                 feedback_put: Callable[[Feedback], bool],
                 publish: Callable[[Any], None], target_sync: int,
                 stop: threading.Event, start_steps: int = 0,
                 on_slab: Callable[..., bool] | None = None):
        self._learn = learn_fn            # jitted fused slab step
        self._in_q = in_q
        self._feedback_put = feedback_put
        self._publish = publish
        self._target_sync = max(int(target_sync), 1)
        self._stop = stop
        # Checkpoint hook: called after every completed slab (feedback
        # enqueued, params published) with the live (params, target,
        # opt_m, opt_v); returning True stops the run early — the
        # preemption exit used by the snapshot orchestrator.
        self._on_slab = on_slab
        self.steps_done = start_steps     # learner steps (batches) applied
        self.finished = False             # all feedback for the run emitted
        # Live optimizer moments, exposed for the final checkpoint after
        # the run ends (Python reference swaps, no copies).
        self.opt_m = None
        self.opt_v = None
        # Last loss per slab, kept as device arrays (no host sync) and
        # bounded so multi-million-step runs don't grow without limit.
        self.losses: collections.deque = collections.deque(maxlen=256)
        self.first_step_time: float | None = None
        # Compiles recorded before the first step: the run's compile
        # count starts here, with the learner's updates.
        self.compiles_at_first_step = 0

    def run(self, params, target_params, opt_m, opt_v,
            n_steps: int) -> tuple[Any, Any]:
        """Consume slabs until ``n_steps`` learner steps are done (rounded
        up to a whole slab).  Returns (params, target_params)."""
        self.opt_m, self.opt_v = opt_m, opt_v
        steps_c = get_registry().counter(
            "learner_steps_total", help="optimizer steps taken")
        try:
            while self.steps_done < n_steps and not self._stop.is_set():
                slab = self._get_slab()
                if slab is None:
                    break
                if self.first_step_time is None:
                    self.compiles_at_first_step = compile_count()
                    self.first_step_time = time.perf_counter()
                with span("learn", slab=slab.seq0):
                    params, opt_m, opt_v, td, loss = self._learn(
                        params, target_params, opt_m, opt_v,
                        jnp.int32(self.steps_done), slab.batch, slab.weights)
                self.opt_m, self.opt_v = opt_m, opt_v
                s = int(td.shape[0])
                steps_c.add(s)
                self._feedback_put(Feedback(
                    seq0=slab.seq0, idx=slab.idx, td=td,
                    stamp=slab.stamp, version=slab.version))
                prev = self.steps_done
                self.steps_done = prev + s
                # Keep the device array: a float() here would host-sync
                # the critical path once per slab.
                self.losses.append(loss[-1])
                if (self.steps_done // self._target_sync
                        > prev // self._target_sync):
                    target_params = params
                self._publish(params)
                if self._on_slab is not None and self._on_slab(
                        params, target_params, opt_m, opt_v):
                    break
        finally:
            # The replay thread's exit condition requires finished=True;
            # set it even when the learn step raises, or the replay-core
            # thread would spin for the rest of the process lifetime.
            self.finished = True
        return params, target_params

    def _get_slab(self) -> BatchSlab | None:
        with span("learner_wait"):
            while not self._stop.is_set():
                try:
                    return self._in_q.get(timeout=0.05)
                except queue.Empty:
                    continue
            return None
