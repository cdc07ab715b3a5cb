"""Double-buffered device prefetch of sampled batch slabs.

The pipeline thread draws batch N+1 while the learner consumes batch N:
it snapshots the replay service's current buffer state (an immutable
pytree, so the snapshot is a free Python reference), samples a *slab* of
S batches in one jitted vmap call — one dispatch instead of S, which is
what makes host-side sampling keep up with the learner on CPU — and
pushes the slab into a bounded queue of depth ``prefetch_depth`` (2 =
classic double buffering).  Any registry sampler works, including the
mesh-sharded ``amper-fr-sharded``: the pipeline only calls
``ReplayBuffer.sample`` under jit.

Each slab row carries the sample-time write stamps (for the stale-safe
deferred priority update) and the learner-step version at draw time (for
staleness accounting).  Batches are optionally ``device_put`` onto a
target device here, off the learner's critical path; the learner's jit
then donates the batch buffers, so a consumed batch's memory is recycled
into the next step's outputs instead of round-tripping the allocator.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.obs import span
from repro.runtime import prng
from repro.runtime.actor import PauseGate


class BatchSlab(NamedTuple):
    """S prefetched batches, stacked on a leading slab axis."""

    seq0: int           # global batch sequence number of row 0
    idx: jax.Array      # int32[S, batch] sampled replay rows
    batch: Any          # pytree, leaves [S, batch, ...]
    weights: jax.Array  # float32[S, batch] importance weights
    stamp: jax.Array    # int32[S, batch, 2] (counter, gen) write stamps
    #                     captured at sample time
    version: int        # learner steps completed when this slab was drawn


def make_slab_sampler(replay, batch: int, slab: int) -> Callable:
    """Build the jittable ``(buffer_state, key) -> (idx, batch, w, stamp)``
    slab draw: ONE ``S*batch`` draw of the sampler's law reshaped to
    ``[S, batch]``.

    The PER samplers draw stratified (one uniform per segment of the
    cumulative mass), so the S*batch rows are split by *interleaving*
    strata — batch j takes flat rows {j, S+j, 2S+j, ...} — which makes
    every batch a stratified sample spanning the full priority range (a
    row-major reshape would hand each batch one contiguous 1/S slice of
    the mass).  For AMPER (uniform over its CSP) the split is immaterial,
    and sharing one draw sets the CSP rebuild cadence to one rebuild per
    S batches — the candidate set the paper rebuilds per sampling event
    is shared by the slab, which is exactly the replay policy an AM
    accelerator would run when the host prefetches ahead (see README
    "Async runtime" on how this interacts with staleness).  Importance
    weights are max-normalized over the whole slab rather than per batch
    (the PER normalizer is a heuristic either way).
    """

    def sample_slab(state, key, beta):
        idx, tree, w = replay.sample(state, key, batch * slab, beta=beta)
        # [S*batch, ...] -> [S, batch, ...] with strata interleaved:
        # slab row j = flat rows {j, S+j, 2S+j, ...}.
        shape = lambda x: x.reshape(
            (batch, slab) + x.shape[1:]).swapaxes(0, 1)
        return (shape(idx), jax.tree.map(shape, tree), shape(w),
                shape(replay.stamps(state, idx)))

    return sample_slab


class PrefetchPipeline(threading.Thread):
    """Prefetch thread: snapshot -> slab draw -> bounded queue."""

    def __init__(self, sample_fn: Callable, state_fn: Callable, *,
                 out_q: queue.Queue, stop: threading.Event,
                 base_key: jax.Array, slab: int, min_size: int,
                 device=None, beta_fn: Callable[[int], float] | None = None,
                 gate: PauseGate | None = None, start_draw: int = 0,
                 start_seq: int = 0,
                 probe: Callable[[Any, jax.Array], None] | None = None,
                 probe_every: int = 0):
        super().__init__(name="replay-prefetch", daemon=True)
        self._sample = sample_fn          # jitted slab draw
        self._state_fn = state_fn         # () -> (buffer_state, version)
        self._out_q = out_q
        self._stop_evt = stop
        self._base_key = base_key
        self._slab = slab
        self._min_size = min_size
        self._device = device
        # version -> IS exponent: the annealed-β schedule evaluated at the
        # learner step this slab was drawn for (constant when disabled).
        self._beta_fn = beta_fn
        self._gate = gate
        # Resume counters: ``draws`` is the PRNG stream position (every
        # performed draw consumed sample_key(base_key, draw), delivered
        # or not), ``seq`` the global batch sequence of the next slab.
        self._start_draw = start_draw
        self._start_seq = start_seq
        # Replay-health probe: called with the exact (state, key) of one
        # in every ``probe_every`` slab draws, AFTER the draw itself, so
        # the probe can re-derive that draw's CSP/sampled-priority facts
        # (see repro.obs.probes) without touching the production path.
        self._probe = probe
        self._probe_every = max(int(probe_every), 0) if probe else 0
        self.draws = start_draw
        self.slabs_done = 0
        # IS exponent the latest slab draw used (None until the first
        # draw, or when no beta_fn is wired) — the annealed value the
        # service surfaces in its metrics dict.
        self.last_beta: float | None = None
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            self._loop()
        except BaseException as e:
            self.error = e
            self._stop_evt.set()

    def _try_put(self, slab) -> bool:
        """One bounded put attempt; abandon to the gate/stop checks."""
        with span("prefetch_wait"):
            try:
                self._out_q.put(slab, timeout=0.05)
                return True
            except queue.Full:
                return False

    def _loop(self) -> None:
        seq, draw, warm = self._start_seq, self._start_draw, False
        pending = None
        while not self._stop_evt.is_set():
            if self._gate is not None:
                # Park holding any undelivered slab: the learner stops
                # consuming during a snapshot, so a blocking put here
                # would deadlock the quiesce.  The pending slab is
                # delivered after resume — sequence numbers stay gapless.
                with span("prefetch_wait"):
                    self._gate.wait_if_paused(self._stop_evt)
            if pending is None:
                state, version = self._state_fn()
                if not warm:  # size only grows; skip the device sync once warm
                    with span("host_sync"):
                        size = int(state.size)
                    if size < self._min_size:
                        with span("prefetch_wait"):
                            time.sleep(0.002)  # buffer not yet sampleable
                        continue
                    warm = True
                # None (a leafless pytree, so still one jit trace) lets
                # replay.sample fall back to its constructor constant.
                beta = (jnp.float32(self._beta_fn(version))
                        if self._beta_fn is not None else None)
                key = prng.sample_key(self._base_key, draw)
                with span("slab_draw", slab=seq):
                    idx, batch, weights, stamp = self._sample(
                        state, key, beta)
                # Publish β only once the draw has returned: a draw that
                # raises must not leave metrics reporting the β of a
                # slab that never existed.
                if beta is not None:
                    with span("host_sync"):
                        self.last_beta = float(beta)
                if self._probe_every and draw % self._probe_every == 0:
                    self._probe(state, key)
                draw += 1
                self.draws = draw
                if self._device is not None:
                    batch, weights = jax.device_put(
                        (batch, weights), self._device)
                pending = BatchSlab(seq0=seq, idx=idx, batch=batch,
                                    weights=weights, stamp=stamp,
                                    version=version)
            if self._try_put(pending):
                pending = None
                seq += self._slab
                self.slabs_done += 1
