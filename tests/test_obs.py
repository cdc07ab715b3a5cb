"""Observability subsystem: registry thread-safety with exact counts,
exporter schema round-trips, the online-vs-offline Fig. 7 KL pin, the
zero-dispatch guard for disabled telemetry, and the telemetry-enabled
``ReplayService`` integration (uniform sync/async metrics schema)."""
import json
import math
import os
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import INT_BUCKETS, Registry, _hist_percentile
from repro.obs.probes import (BINS, SamplingErrorMonitor, kl_nats,
                              priority_bin_counts)
from repro.rl.dqn import DQNConfig
from repro.runtime import ReplayService
from repro.train.checkpoint import CheckpointManager


# --- registry: lock-free writers, exact merge --------------------------------

def test_counter_race_exact_counts():
    """4 writer threads x 10k adds each merge to EXACT totals — the
    per-thread-cell design has no lost updates by construction."""
    reg = Registry()
    c = reg.counter("hits")
    h = reg.histogram("vals", bounds=INT_BUCKETS)
    N, T = 10_000, 4

    def work(tid):
        for i in range(N):
            c.add()
            h.observe(tid)  # each thread observes its own id N times

    threads = [threading.Thread(target=work, args=(t,)) for t in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    assert c.read()["events"] == N * T
    data = h.read()
    assert data["count"] == N * T
    assert data["min"] == 0 and data["max"] == T - 1
    # INT_BUCKETS make small-int series exact: each tid bucket holds N.
    for tid in range(T):
        assert data["buckets"][tid] == N


def test_gauge_freshest_write_wins_across_threads():
    reg = Registry()
    g = reg.gauge("depth")
    assert math.isnan(g.value)  # unset
    g.set(1.0)
    t = threading.Thread(target=lambda: g.set(7.0))
    t.start()
    t.join()
    assert g.value == 7.0  # the later write, from another thread's cell


def test_histogram_percentiles_exact_on_int_bounds():
    reg = Registry()
    h = reg.histogram("staleness_steps", bounds=INT_BUCKETS)
    for v in range(1, 61):  # 1..60: inside the exact 0..64 range
        h.observe(v)
    h.observe_n(3, 0)  # no-op
    assert h.percentile(0.50) == 30
    assert h.percentile(0.95) == 57
    assert h.percentile(1.0) == 60
    assert _hist_percentile(h.read(), h.bounds, 0.01) == 1
    # Past the exact range values fall in coarse power-of-two buckets,
    # whose percentile clamps to the observed max.
    h.observe(100)
    assert h.percentile(1.0) == 100


def test_observe_n_matches_n_observes():
    reg = Registry()
    a = reg.histogram("a", bounds=INT_BUCKETS)
    b = reg.histogram("b", bounds=INT_BUCKETS)
    for _ in range(7):
        a.observe(5)
    b.observe_n(5, 7)
    assert a.read() == b.read()


def test_snapshot_diff_gives_per_run_view():
    reg = Registry()
    c = reg.counter("frames_total")
    h = reg.histogram("lat", bounds=INT_BUCKETS)
    c.add(10)
    h.observe(3)
    base = reg.snapshot()
    c.add(5)
    h.observe(4)
    diff = reg.snapshot().diff(base)
    assert diff.data["frames_total"]["value"] == 5
    assert diff.data["lat"]["count"] == 1
    assert sum(diff.data["lat"]["buckets"]) == 1
    # summary() renders histograms as stats dicts.
    assert diff.summary()["lat"]["p50"] == 4


def test_disabled_registry_records_nothing():
    reg = Registry(enabled=False)
    c = reg.counter("c")
    g = reg.gauge("g")
    h = reg.histogram("h")
    c.add()
    g.set(1.0)
    h.observe(1.0)
    assert c.value == 0 and math.isnan(g.value) and h.read()["count"] == 0


# --- spans -------------------------------------------------------------------

def test_span_disabled_by_default_and_records_when_enabled():
    from repro.obs.tracing import _NULL_SPAN

    # Process default: disabled registry -> shared null span object.
    assert obs.span("anything") is _NULL_SPAN
    reg = Registry()
    with obs.span("unit", registry=reg):
        pass
    data = reg.instruments()["span_unit_ms"].read()
    assert data["count"] == 1 and data["sum"] >= 0.0


def test_span_is_noop_inside_jit_trace():
    """Compile time must never poison the wall-time histograms: spans
    opened while jax is tracing resolve to the null span."""
    reg = Registry()

    def f(x):
        with obs.span("traced_region", registry=reg):
            return x + 1

    jax.make_jaxpr(f)(1.0)
    assert "span_traced_region_ms" not in reg.instruments()
    f(1.0)  # eager call does record
    assert reg.instruments()["span_traced_region_ms"].read()["count"] == 1


def test_use_registry_thread_local_override():
    reg = Registry()
    with obs.use_registry(reg):
        assert obs.get_registry() is reg
        with obs.span("scoped"):
            pass
    assert obs.get_registry() is not reg
    assert reg.instruments()["span_scoped_ms"].read()["count"] == 1


# --- exporters: schema round-trips -------------------------------------------

def test_jsonl_roundtrip(tmp_path):
    reg = Registry()
    reg.counter("frames_total").add(42)
    reg.histogram("lat", bounds=INT_BUCKETS).observe(2)
    reg.gauge("unset_gauge")  # NaN -> null in JSON
    path = str(tmp_path / "m.jsonl")
    exp = obs.JsonlExporter(path)
    exp.write_event("run_start", mode="async")
    exp.write_snapshot(reg.snapshot(), extra={"step": 7})
    exp.close()
    records = obs.read_jsonl(path)
    assert [r["kind"] for r in records] == ["event", "snapshot"]
    ev, snap = records
    assert ev["event"] == "run_start" and ev["mode"] == "async"
    assert ev["schema"] == snap["schema"] == 1
    assert snap["step"] == 7
    m = snap["metrics"]
    assert m["frames_total"]["value"] == 42
    assert m["lat"]["count"] == 1 and m["lat"]["p50"] == 2
    assert m["unset_gauge"]["value"] is None  # NaN sanitised
    # Every line is independently parseable JSON (stream-safe).
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_jsonl_skips_torn_tail(tmp_path):
    path = str(tmp_path / "m.jsonl")
    exp = obs.JsonlExporter(path)
    exp.write_event("ok")
    exp.close()
    with open(path, "a") as f:
        f.write('{"kind": "event", "trunc')  # killed mid-write
    records = obs.read_jsonl(path)
    assert len(records) == 1 and records[0]["event"] == "ok"


def test_prometheus_text_roundtrip(tmp_path):
    reg = Registry()
    reg.counter("frames_total", help="frames").add(17)
    reg.gauge("csp_occupancy").set(0.25)
    h = reg.histogram("lat", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(99.0)
    text = obs.prometheus_text(reg)
    series = obs.parse_prometheus(text)
    assert series["repro_frames_total_total"] == 17.0
    assert series["repro_csp_occupancy"] == 0.25
    assert series['repro_lat_bucket{le="1.0"}'] == 1.0
    assert series['repro_lat_bucket{le="10.0"}'] == 2.0
    assert series['repro_lat_bucket{le="+Inf"}'] == 3.0
    assert series["repro_lat_count"] == 3.0
    assert series["repro_lat_sum"] == pytest.approx(104.5)
    path = obs.write_prometheus(reg, str(tmp_path / "metrics.prom"))
    assert obs.parse_prometheus(open(path).read()) == series


def test_prometheus_http_endpoint():
    reg = Registry()
    reg.counter("hits").add(3)
    srv = obs.PrometheusServer(reg)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read().decode()
        assert obs.parse_prometheus(body)["repro_hits_total"] == 3.0
    finally:
        srv.close()


def test_report_cli_smoke(tmp_path, capsys):
    from repro.obs import report

    reg = Registry()
    reg.counter("frames_total").add(5)
    path = str(tmp_path / "m.jsonl")
    exp = obs.JsonlExporter(path)
    exp.write_event("checkpoint", step=10)
    exp.write_snapshot(reg.snapshot())
    exp.close()
    report.main([path, "--events"])
    out = capsys.readouterr().out
    assert "frames_total" in out and "checkpoint" in out


# --- Fig. 7 pin: online monitor == offline benchmark on identical draws ------

@pytest.mark.tier1
@pytest.mark.stats
def test_online_kl_gauge_matches_fig7_benchmark_on_same_draws():
    """The live SamplingErrorMonitor and the offline Fig. 7 study are the
    same computation: feeding the monitor the exact draws the benchmark
    binned yields bit-identical bin counts and KL."""
    from benchmarks import fig7_sampling_error as fig7
    from repro.core.per import CumsumPER

    n = 2000
    key = jax.random.key(0)
    prio = jax.random.uniform(jax.random.fold_in(key, 99), (n,))
    prio_np = np.asarray(prio)
    per = CumsumPER(n)
    state = per.update(per.init(), jnp.arange(n), prio)

    q_ref = fig7.sample_counts(per, state, jax.random.fold_in(key, 1),
                               prio_np)

    # Replay the benchmark's exact draw loop into the online monitor.
    reg = Registry()
    mon = SamplingErrorMonitor(reg, window=fig7.RUNS)
    mon.set_reference_counts(q_ref)
    fn = jax.jit(lambda s, k: per.sample(s, k, fig7.BATCH))
    counts = np.zeros(BINS)
    k2 = jax.random.fold_in(key, 2)
    for r in range(fig7.RUNS):
        vals = prio_np[np.asarray(fn(state, jax.random.fold_in(k2, r)))]
        counts += priority_bin_counts(vals)
        mon.observe(vals)
    np.testing.assert_array_equal(mon.counts, counts)
    assert mon.kl() == kl_nats(counts, q_ref)  # exact, same code path
    assert mon.kl() == pytest.approx(
        reg.instruments()["sampling_kl_nats"].value)
    # PER-vs-PER on the shared binning sits near the noise floor, far
    # below a uniform sampler's divergence (the Fig. 7 ordering).
    uni = np.random.default_rng(0).integers(0, n, fig7.BATCH * fig7.RUNS)
    kl_uniform = kl_nats(
        priority_bin_counts(prio_np[uni]).astype(float), q_ref)
    assert kl_uniform > 5 * mon.kl()


def test_monitor_window_evicts_old_draws():
    mon = SamplingErrorMonitor(window=2)
    a = np.full(10, 0.1)
    b = np.full(10, 0.9)
    mon.observe(a)
    mon.observe(a)
    mon.observe(b)  # evicts the first draw of `a`
    expected = priority_bin_counts(a) + priority_bin_counts(b)
    np.testing.assert_array_equal(mon.counts, expected.astype(float))


# --- tier-1 guard: disabled telemetry adds ZERO dispatches -------------------

def test_disabled_telemetry_keeps_fused_dispatch_count():
    """Instrumentation is host-side only: the fused AMPER-fr sampling
    path keeps the committed dispatch count (BENCH_sampling.json) with
    telemetry disabled AND enabled — spans no-op inside traces."""
    from benchmarks.bench_samplers import BATCH, CSP_RATIO, dispatch_count
    from repro.core.amper import AmperConfig, AmperSampler

    bench = json.load(open(
        os.path.join(os.path.dirname(__file__), "..",
                     "BENCH_sampling.json")))
    row = next(r for r in bench["rows"] if r[0] == "fr-fused/n10000")
    pinned = int(dict(kv.split("=") for kv in row[2].split())["dispatches"])

    n = 10_000
    cfg = AmperConfig(capacity=n, m=20, lam_fr=2.0, v_max=1.0,
                      csp_capacity=max(int(n * CSP_RATIO), BATCH),
                      fr_mode="fused")
    amp = AmperSampler(cfg, "fr")
    s = amp.update(amp.init(), jnp.arange(n),
                   jax.random.uniform(jax.random.key(0), (n,)) + 0.01)
    key = jax.random.key(1)

    _, disp_off = dispatch_count(
        lambda st, k, a=amp: a.sample(st, k, BATCH), s, key)
    assert disp_off == pinned, (
        f"disabled telemetry changed fused dispatches: {disp_off} != "
        f"{pinned} (committed BENCH_sampling.json)")

    prev = obs.set_registry(Registry(enabled=True))
    try:
        _, disp_on = dispatch_count(
            lambda st, k, a=amp: a.sample(st, k, BATCH), s, key)
    finally:
        obs.set_registry(prev)
    assert disp_on == pinned, (
        f"ENABLED telemetry changed fused dispatches: {disp_on} != {pinned}")


# --- ReplayService integration ----------------------------------------------

def _small_cfg(**kw):
    base = dict(num_envs=2, replay_size=256, batch=16, learn_start=8,
                eps_decay_steps=200, target_sync=50, v_max=8.0)
    base.update(kw)
    return DQNConfig(**base)


def test_service_async_telemetry_jsonl(tmp_path):
    """Telemetry-enabled async run: RunResult keeps the pinned metric
    keys, the JSONL log carries staleness percentiles / CSP occupancy /
    fallback rate, and the Prometheus file parses."""
    jpath = str(tmp_path / "run.jsonl")
    ppath = str(tmp_path / "run.prom")
    tel = obs.Telemetry(metrics_out=jpath, prometheus_out=ppath,
                        probe_every=4, window=50)
    svc = ReplayService(_small_cfg(sampler="amper-fr"), num_actors=2,
                        chunk_len=4, slab=2, max_replay_ratio=64,
                        telemetry=tel)
    res = svc.run(jax.random.key(0), 40)
    m = res.metrics

    # Compatibility view: the pre-registry metric keys survive.
    for k in ("staleness", "queue_depth", "snapshot", "checkpoint"):
        assert k in m, k
    assert m["staleness"]["count"] == 40
    assert {"p50", "p95", "p99"} <= set(m["staleness"])
    assert m["staleness"]["p50"] <= m["staleness"]["p95"] <= \
        m["staleness"]["p99"] <= m["staleness"]["max"]
    assert {"kl_nats", "csp_occupancy", "fallback_draws",
            "probe_draws"} <= set(m["health"])
    assert m["health"]["probe_draws"] >= 1

    records = obs.read_jsonl(jpath)
    snaps = [r for r in records if r["kind"] == "snapshot"]
    assert len(snaps) >= 2  # per-probe timeline + the final snapshot
    final = snaps[-1]
    mm = final["metrics"]
    for name in ("frames_total", "blocks_total", "learner_steps_total",
                 "feedback_applied_total", "staleness_steps",
                 "work_queue_depth", "batch_queue_depth", "csp_occupancy",
                 "sampling_kl_nats", "probe_draws", "span_learn_ms",
                 "span_slab_draw_ms", "span_rollout_ms"):
        assert name in mm, name
    assert mm["staleness_steps"]["count"] == 40
    assert mm["learner_steps_total"]["value"] == 40
    assert 0.0 <= mm["csp_occupancy"]["value"] <= 1.0

    series = obs.parse_prometheus(open(ppath).read())
    assert series["repro_learner_steps_total_total"] == 40.0
    assert "repro_staleness_steps_count" in series

    # The run restored the process default registry on exit.
    assert not obs.get_registry().enabled


def test_service_sync_uniform_schema(tmp_path):
    """Sync mode emits the SAME snapshot/checkpoint schema as async:
    pause stats, drain_cycles, checkpoint bytes split and chain length."""
    manager = CheckpointManager(str(tmp_path / "ckpt"), keep=3,
                                save_interval=20)
    tel = obs.Telemetry(metrics_out=str(tmp_path / "sync.jsonl"),
                        probe_every=0)
    svc = ReplayService(_small_cfg(num_envs=1), sync=True, num_actors=1,
                        telemetry=tel)
    res = svc.run(jax.random.key(0), 60, manager=manager)
    m = res.metrics
    assert m["mode"] == "sync"
    assert set(m["snapshot"]) == {"count", "saved", "pause_us_mean",
                                  "pause_us_max", "drain_cycles"}
    assert m["snapshot"]["count"] == 3  # steps 20/40/60
    assert m["snapshot"]["pause_us_max"] > 0
    ck = m["checkpoint"]
    assert ck["saves"] == 3
    assert ck["full_bytes"] > 0 and ck["delta_bytes"] > 0
    assert ck["chain_len"] >= 1
    # Sync staleness is structurally zero but the schema is uniform.
    assert m["staleness"] == {"count": 0, "mean": 0.0, "max": 0,
                              "p50": 0, "p95": 0, "p99": 0}
    events = [r for r in obs.read_jsonl(str(tmp_path / "sync.jsonl"))
              if r["kind"] == "event" and r["event"] == "checkpoint"]
    assert [e["step"] for e in events] == [20, 40, 60]
    assert [e["delta"] for e in events] == [False, True, True]


def test_service_without_telemetry_unchanged(tmp_path):
    """No Telemetry spec -> no files, no global registry flip, and the
    compatibility metric keys still exist (registry-backed, disabled)."""
    svc = ReplayService(_small_cfg(), num_actors=2, chunk_len=4, slab=2,
                        max_replay_ratio=64)
    res = svc.run(jax.random.key(0), 20)
    assert res.metrics["staleness"]["count"] == 20
    assert "health" not in res.metrics
    assert not obs.get_registry().enabled
    assert os.listdir(tmp_path) == []


# --- span arguments, compile counter, named scopes ---------------------------

def test_span_args_reach_only_the_profiler_annotation():
    """``span(name, **args)``: the histogram ignores the arguments, and
    with profiling off no annotation is built from them."""
    from repro.obs import tracing

    reg = Registry()
    with obs.span("unit", registry=reg, slab=12):
        pass
    assert reg.instruments()["span_unit_ms"].read()["count"] == 1
    assert tracing.span("unit", registry=reg, slab=3)._annotation is None
    prev = obs.set_registry(reg, profile=True)
    try:
        sp = obs.span("unit", slab=3)
        assert isinstance(sp._annotation, jax.profiler.TraceAnnotation)
        with sp:
            pass
    finally:
        obs.set_registry(prev)
    assert reg.instruments()["span_unit_ms"].read()["count"] == 2


_forced = jax.jit(lambda x: x * 2.0)


def _force_shape_on_second_learn(svc, size):
    """Wrap the service's learner so the second call of every run also
    runs a jitted function on ``size[0]`` elements (a new size compiles)."""
    learn = svc._learn

    def wrapped(params, target, m, v, step0, batch, weights):
        if int(step0) == 2:            # each run counts from step 0
            _forced(np.zeros(size[0], np.float32)).block_until_ready()
        return learn(params, target, m, v, step0, batch, weights)

    svc._learn = wrapped


def test_compiles_counts_new_shape_in_run_and_zero_when_warm():
    svc = ReplayService(_small_cfg(sampler="amper-fr"), num_actors=1,
                        chunk_len=4, slab=2, max_replay_ratio=64)
    size = [3]
    _force_shape_on_second_learn(svc, size)
    first = svc.run(jax.random.key(0), 12).metrics["compiles"]
    assert first >= 1            # the learner's own first compiles count
    assert svc.run(jax.random.key(1), 12).metrics["compiles"] == 0
    size[0] = 5                  # a shape the forced function never saw
    assert svc.run(jax.random.key(2), 12).metrics["compiles"] == 1
    assert svc.run(jax.random.key(3), 12).metrics["compiles"] == 0
    # Sync mode reports the same count over its own learning window.
    sync = ReplayService(_small_cfg(num_envs=1), sync=True, num_actors=1)
    sync.run(jax.random.key(0), 12)
    assert sync.run(jax.random.key(1), 12).metrics["compiles"] == 0


def test_compile_counter_records_into_enabled_registry_only():
    reg = Registry()
    with obs.use_registry(reg):              # installs the listener
        jax.jit(lambda x: x - 1.0)(np.zeros(7, np.float32))
        assert obs.compile_count() == 1
        assert reg.instruments()["jit_compile_ms"].read()["count"] == 1
    default = obs.get_registry()
    assert not default.enabled
    jax.jit(lambda x: x - 2.0)(np.zeros(7, np.float32))
    assert obs.compile_count(default) == 0
    assert obs.compile_count(reg) == 1


def test_slab_draw_lowering_carries_named_scopes():
    """The service's slab draw names its CSP build and FrameStore stacks
    in the op metadata of the lowered program."""
    cfg = _small_cfg(env="breakout", sampler="amper-fr", replay_size=512)
    svc = ReplayService(cfg, num_actors=1, chunk_len=4, slab=2)
    state = svc.dqn.init(jax.random.key(0)).buffer
    text = svc._sample.lower(state, jax.random.key(1),
                             jnp.float32(0.4)).as_text(debug_info=True)
    for scope in ("csp_build", "csp_pick", "frame_stack", "is_weights"):
        assert scope in text, scope
