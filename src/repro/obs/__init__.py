"""repro.obs — low-overhead telemetry for the replay fabric.

Four layers, each usable alone:

* :mod:`repro.obs.metrics` — the registry.  Counters / gauges /
  histograms with per-thread lock-free accumulation (one private cell
  per writer thread; the only locks are cell creation and
  snapshot-on-read merge), cumulative Prometheus-style semantics, and
  ``Snapshot.diff`` for per-run views.
* :mod:`repro.obs.tracing` — ``span("name", **args)`` wall-time spans
  recording into ``span_<name>_ms`` histograms of the current registry;
  no-ops when the registry is disabled or inside a ``jax.jit`` trace,
  and can open ``jax.profiler.TraceAnnotation`` regions (carrying
  ``args`` as event stats) when profiling.  Also the process-wide
  compile counter (``jit_compiles_total``).
* :mod:`repro.obs.probes` — paper-grounded replay health: the canonical
  Fig. 7 KL/chi-square math (``BINS``/``kl_nats``), the windowed
  :class:`~repro.obs.probes.SamplingErrorMonitor` (Fig. 7 as a live
  gauge), and the jitted CSP draw probe behind
  :class:`~repro.obs.probes.ReplayHealth`.
* :mod:`repro.obs.exporters` — structured JSONL event log
  (:class:`~repro.obs.exporters.JsonlExporter`), Prometheus text
  exposition (:func:`~repro.obs.exporters.prometheus_text` /
  ``write_prometheus`` / :class:`~repro.obs.exporters.PrometheusServer`),
  and ``python -m repro.obs.report`` to summarise a JSONL log.

Instrument catalog (what a telemetry-enabled ReplayService run emits):

=========================  =========  ========================================
name                       kind       meaning
=========================  =========  ========================================
frames_total               counter    environment frames appended to replay
blocks_total               counter    transition blocks absorbed by the core
learner_steps_total        counter    optimizer steps taken
feedback_enqueued_total    counter    priority-feedback packets enqueued
feedback_applied_total     counter    priority-feedback packets applied
fallback_draws             counter    probed draws that fell back to uniform
probe_draws                counter    health-probe draws taken
checkpoint_full_bytes      counter    bytes written by full checkpoints
checkpoint_delta_bytes     counter    bytes written by delta checkpoints
staleness_steps            histogram  feedback staleness in learner steps
                                      (exact p50/p95/p99 via INT_BUCKETS)
work_queue_depth           histogram  actor->replay queue depth per drain
batch_queue_depth          histogram  prefetch->learner queue depth per step
snapshot_pause_us          histogram  COW snapshot capture pause (microsec)
span_rollout_ms            histogram  actor rollout launch wall time
span_slab_draw_ms          histogram  prefetch slab draw launch wall time
                                      (profiler arg ``slab`` = seq0)
span_learn_ms              histogram  learner slab step launch wall time
                                      (profiler arg ``slab`` = seq0)
span_add_block_ms          histogram  replay-core block absorb wall time
span_apply_feedback_ms     histogram  priority feedback apply wall time
                                      (profiler arg ``slab`` = seq0)
span_learner_wait_ms       histogram  learner blocked on the slab queue
span_prefetch_wait_ms      histogram  prefetch blocked on a full slab
                                      queue, the min_size sleep, the gate
span_replay_wait_ms        histogram  replay core blocked on the work
                                      queue (empty polls included)
span_actor_wait_ms         histogram  actor blocked on the replay-ratio
                                      budget, the block queue, the gate
span_host_sync_ms          histogram  a host thread blocked reading a
                                      device value (float/int/np.asarray)
span_checkpoint_save_ms    histogram  CheckpointManager.save wall time
jit_compiles_total         counter    jaxpr lowerings, one per compile
                                      (persistent-cache hits included)
jit_compile_ms             histogram  lowering wall time per compile
checkpoint_chain_len       gauge      delta-chain length since last full
csp_count                  gauge      CSP fill for the last probed draw
csp_occupancy              gauge      CSP fill / csp_capacity (0..1)
csp_match_count            gauge      TCAM match count before compaction
replay_live                gauge      live replay rows
sampling_kl_nats           gauge      windowed KL vs exact PER law (Fig. 7)
sampling_chi2              gauge      windowed chi-square vs exact PER law
sampling_window_samples    gauge      samples inside the monitor window
=========================  =========  ========================================

Over a window, a thread's time outside its ``*_wait`` spans is its host
busy time (launches, Python work, waits for the GIL).
``RunResult.metrics["compiles"]`` is the count of ``jit_compiles_total``
from the learner's first step to the end of the run.  Inside jitted
programs, ``jax.named_scope`` names the device work instead (op
metadata, not spans): ``csp_build``, ``csp_pick``, ``is_weights`` and
``frame_stack`` in the draw, ``td_loss`` and ``adam`` in the learner
step, ``priority_write`` in the write-back, ``ring_write`` in
``add_block``.

Disabled (the process default) every record call is one attribute
check, and instrumentation is host-side only, so the jitted sampling
paths keep their exact dispatch counts — pinned by tests/test_obs.py
against the committed BENCH_sampling.json.
"""
from typing import NamedTuple, Optional

from repro.obs.exporters import (JsonlExporter, PrometheusServer,
                                 parse_prometheus, prometheus_text,
                                 read_jsonl, write_prometheus)
from repro.obs.metrics import (INT_BUCKETS, TIME_BUCKETS_MS, US_BUCKETS,
                               Counter, Gauge, Histogram, Registry,
                               Snapshot, hist_stats)
from repro.obs.probes import (BINS, ReplayHealth, SamplingErrorMonitor,
                              chi_square, kl_nats, make_replay_probe,
                              priority_bin_counts)
from repro.obs.tracing import (compile_count, get_registry, set_registry,
                               span, use_registry)


class Telemetry(NamedTuple):
    """Telemetry spec consumed by ``ReplayService`` and the examples.

    Attributes:
      registry: use this registry instead of a fresh per-run one (pass a
        long-lived registry to aggregate across runs; RunResult.metrics
        stays per-run via snapshot diffs either way).
      metrics_out: JSONL event-log path (appended; see JsonlExporter).
      prometheus_out: write the Prometheus text exposition here when the
        run finishes.
      probe_every: replay-health probe cadence in prefetch slab draws
        (0 disables the probe; each probe re-derives one draw's CSP off
        the hot path and refreshes the Fig. 7 KL gauge).
      window: SamplingErrorMonitor window, in probed draws.
      profile: also open jax.profiler.TraceAnnotation regions for spans.
    """

    registry: Optional[Registry] = None
    metrics_out: Optional[str] = None
    prometheus_out: Optional[str] = None
    probe_every: int = 16
    window: int = 200
    profile: bool = False


__all__ = [
    "BINS", "Counter", "Gauge", "Histogram", "INT_BUCKETS",
    "JsonlExporter", "PrometheusServer", "Registry", "ReplayHealth",
    "SamplingErrorMonitor", "Snapshot", "TIME_BUCKETS_MS", "Telemetry",
    "US_BUCKETS", "chi_square", "compile_count", "get_registry",
    "hist_stats", "kl_nats", "make_replay_probe", "parse_prometheus",
    "priority_bin_counts", "prometheus_text", "read_jsonl",
    "set_registry", "span", "use_registry", "write_prometheus",
]
