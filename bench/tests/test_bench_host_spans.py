"""The host-span readers on a synthetic trace, and the service's host
spans as the profiler records them (CPU)."""
from pathlib import Path

import jax
import pytest

from bench import spec
from bench import trace as T
from bench.tests._tiny import run_tiny

NEW = ["learner_wait_ms.learn", "prefetch_host_ms.learn",
       "replay_host_ms.learn", "host_sync_ms.learn", "compiles.learn"]


class _Ctx:
    def __init__(self, spans, window=(1000, 11000), slabs=5, metrics=None):
        self.trace = T.Trace(spans=spans)
        self.window = window
        self.result = {"slabs": slabs,
                       "service_metrics": ({"compiles": 0}
                                           if metrics is None else metrics)}


def _read(metric, ctx):
    return spec.load_module(
        spec.BENCH / "metrics" / f"{metric}.py").read(ctx)


def _spans():
    # ns; window 1000..11000 (0.01 ms) over 5 slabs.
    return [
        ("learner_wait", 0, 2000),          # clipped to 1000 ns
        ("learn", 2000, 3000),
        ("learner_wait", 3000, 6000),       # 3000 ns
        ("prefetch_wait", 4000, 5000),      # 1000 ns
        ("prefetch_wait", 10500, 12000),    # clipped to 500 ns
        ("replay_wait", 1000, 9000),        # 8000 ns
        ("host_sync", 1500, 2500),          # prefetch thread
        ("host_sync", 2000, 2200),          # actor thread, overlapping
        ("host_sync", 11000, 12000),        # after the window
    ]


def test_wait_readers_sum_clipped_spans_per_slab():
    ctx = _Ctx(_spans())
    assert _read("learner_wait_ms.learn", ctx) == pytest.approx(4000e-6 / 5)
    # Threads' host_sync ranges add up even where they overlap in time.
    assert _read("host_sync_ms.learn", ctx) == pytest.approx(1200e-6 / 5)


def test_busy_readers_take_wait_from_the_window():
    ctx = _Ctx(_spans())
    assert _read("prefetch_host_ms.learn", ctx) == pytest.approx(
        (10000 - 1500) * 1e-6 / 5)
    assert _read("replay_host_ms.learn", ctx) == pytest.approx(
        2000e-6 / 5)


def test_compiles_reader_and_silence_without_the_spans():
    assert _read("compiles.learn", _Ctx([], metrics={"compiles": 3})) == 3.0
    # A program without the spans or the counter gives no reading.
    bare = _Ctx([("learn", 2000, 3000)], metrics={})
    for metric in NEW:
        assert _read(metric, bare) is None, metric


def test_service_host_spans_on_the_profiler_clock(tmp_path):
    """A tiny profiled service run: the wait and host-sync spans are on
    the trace, the learner's wait never overlaps its launch, and one
    slab's draw, update and write-back share a ``slab`` identifier."""
    from jax.profiler import ProfileData

    from repro import obs
    from repro.rl.dqn import DQNConfig
    from repro.runtime import ReplayService

    cfg = DQNConfig(num_envs=2, replay_size=256, batch=16, learn_start=8,
                    eps_decay_steps=200, target_sync=50, v_max=8.0,
                    sampler="amper-fr")
    svc = ReplayService(cfg, num_actors=1, chunk_len=4, slab=2,
                        max_replay_ratio=64,
                        telemetry=obs.Telemetry(probe_every=0, profile=True))
    svc.run(jax.random.key(0), 8)                    # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.run(jax.random.key(1), 20)
    finally:
        jax.profiler.stop_trace()
    path = str(sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1])
    tr = T.load(path)
    names = {n for n, _, _ in tr.spans}
    assert {"learner_wait", "prefetch_wait", "replay_wait",
            "host_sync", "actor_wait"} <= names
    waits = [(s, e) for n, s, e in tr.spans if n == "learner_wait"]
    learns = [(s, e) for n, s, e in tr.spans if n == "learn"]
    assert len(learns) == 10
    assert not any(ws < le and ls < we
                   for ws, we in waits for ls, le in learns)
    slabs: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("slab_draw", "learn", "apply_feedback"):
                    slabs.setdefault(ev.name, set()).update(
                        v for k, v in ev.stats if k == "slab")
    assert slabs["learn"] == slabs["apply_feedback"] == set(range(0, 20, 2))
    # The prefetcher draws ahead: slabs past the last update were drawn
    # and never learned from.
    ahead = slabs["slab_draw"] - slabs["learn"]
    assert slabs["learn"] <= slabs["slab_draw"]
    assert all(s > max(slabs["learn"]) for s in ahead)


@pytest.mark.parametrize("cell", ["amper-1m.learn", "per-1m.learn"])
def test_traced_learn_cell_reports_host_metrics(cell, monkeypatch):
    from bench import work

    # The CPU has no published peak: give learner_mfu one to divide by.
    monkeypatch.setattr(work, "peaks", lambda kind: {"bf16_flops": 1e12})
    out, _ = run_tiny(cell, trace=True, control=False)
    assert out["correct"] is True, out["checks"]
    for metric in NEW:
        assert metric in out["metrics"], metric
    m = {k: out["metrics"][k]["value"] for k in NEW}
    assert m["learner_wait_ms.learn"] >= 0 and m["host_sync_ms.learn"] > 0
    assert m["prefetch_host_ms.learn"] > 0 and m["replay_host_ms.learn"] > 0
    assert m["compiles.learn"] == 0
