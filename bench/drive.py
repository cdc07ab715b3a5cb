"""The two loops a traffic file can name (its ``loop`` key).

``service``: the async ``ReplayService`` as a user runs it, on a replay
ring prefilled in set-up.  The window is one ``run``: it starts at the
learner's first update and ends when the last update's result is ready.
``run`` is bounded by update count, so set-up measures the rate on two
short runs and sizes the window's run to ``--seconds``; the rate is taken
over the window's measured length.

``draw_loop``: one closed-loop client on the configuration's buffer:
draw, write back seeded |delta| for the drawn rows, and insert one
``num_envs``-wide step every ``draws_per_insert`` draws.  Each draw is
timed from its dispatch until its indices, weights and stacked batch are
ready, so writes queued ahead of it count in its time.

Both keep, for the check, what the timed path produced on a few steps
picked from the seed, with the state those steps read.
"""
from __future__ import annotations

import contextlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


class _Annotation:
    """A profiler range opened and closed on one thread (no-op when off)."""

    def __init__(self, name: str, on: bool):
        self._a = jax.profiler.TraceAnnotation(name) if on else None

    def open(self):
        if self._a is not None:
            self._a.__enter__()

    def close(self):
        if self._a is not None:
            self._a.__exit__(None, None, None)
            self._a = None


class ServiceRecorder:
    """Wraps a service's jitted draw, learner and write-back stages.

    Counts the learner's updates and times the window on the learner
    thread, and keeps what the check reads: picked slab draws (state,
    key, beta and copies of the outputs, which the learner later
    donates), the first learner call (its inputs and outputs) and the
    write-back of that call's TD errors (state before and after)."""

    def __init__(self, svc):
        self._sample, self._learn = svc._sample, svc._learn
        self._feedback = svc._apply_feedback
        svc._sample, svc._learn = self.sample, self.learn
        svc._apply_feedback = self.feedback
        self.arm(calls=0, picks=())

    def arm(self, calls: int, picks, annotate: bool = False):
        self.calls, self.picks = calls, set(picks)
        self.draw_n = self.learn_n = 0
        self.t_start = self.t_end = None
        self.draws, self.learn0, self.fb0 = {}, None, None
        self._window = _Annotation("bench_window", annotate)

    def sample(self, state, key, beta):
        out = self._sample(state, key, beta)
        i = self.draw_n
        self.draw_n += 1
        if i in self.picks:
            self.draws[i] = {"state": state, "key": key, "beta": beta,
                             "out": _copy(out)}
        return out

    def learn(self, params, target, m, v, step0, batch, weights):
        i = self.learn_n
        if i == 0:
            self.t_start = time.perf_counter()
            self._window.open()
        out = self._learn(params, target, m, v, step0, batch, weights)
        self.learn_n += 1
        if i == 0:
            self.learn0 = {"params": params, "target": target,
                           "step0": step0, "out_params": out[0],
                           "td_obj": out[3], "td": jnp.copy(out[3]),
                           "loss": out[4]}
        if self.learn_n == self.calls:
            jax.block_until_ready(out)
            self.t_end = time.perf_counter()
            self._window.close()
        return out

    def feedback(self, state, idx, td, stamp):
        rec = self.learn0 is not None and self.fb0 is None and \
            td is self.learn0["td_obj"]
        if rec:
            self.fb0 = {"before": state, "idx": jnp.copy(idx),
                        "td": jnp.copy(td), "stamp": jnp.copy(stamp)}
        out = self._feedback(state, idx, td, stamp)
        if rec:
            self.fb0["after"] = out
        return out


def _service(cell, key, seconds: float, trace_on: bool, profile):
    """-> (window result dict, records for the check)."""
    from repro import obs
    from repro.rl.dqn import DQNConfig
    from repro.runtime import ReplayService

    conf, traffic = cell.config, cell.traffic
    cfg = DQNConfig(**conf["dqn"])
    srv = conf["service"]
    svc = ReplayService(
        cfg, num_actors=srv["num_actors"], chunk_len=srv["chunk_len"],
        slab=srv["slab"], min_size=cfg.batch,
        max_replay_ratio=cfg.batch * traffic["frames_per_update_per_row"],
        telemetry=obs.Telemetry(probe_every=0, profile=trace_on))
    dqn = svc.dqn
    k_fill, k_run = jax.random.split(key)
    prefill = generator.make_prefill(
        dqn.replay, dqn.example_transition, cfg.num_envs, dqn.env.n_actions,
        traffic["prefill"])
    filled = jax.block_until_ready(prefill(k_fill))
    init = dqn.init
    svc.dqn = dqn._replace(init=lambda k: init(k)._replace(buffer=filled))
    rec = ServiceRecorder(svc)
    slab = svc.slab
    run_keys = jax.random.split(k_run, 4)

    def run(k, calls, picks, annotate=False):
        rec.arm(calls, picks, annotate)
        res = svc.run(k, calls * slab)
        return res, calls * slab / (rec.t_end - rec.t_start)

    # Compile every stage (the copies the recorder makes included), then
    # measure the rate twice and size the window from the second.
    run(run_keys[0], traffic["warmup_slabs"], (0,))
    _, rate = run(run_keys[1], traffic["calibrate_slabs"], (0,))
    calls = max(math.ceil(rate * traffic["calibrate_s"] / slab), 8)
    _, rate = run(run_keys[2], calls, (0,))
    calls = max(math.ceil(rate * seconds / slab), 8)
    rng = np.random.default_rng(cell.seed_words)
    picks = {0, *rng.choice(np.arange(1, calls),
                            size=min(traffic["check_slabs"] - 1, calls - 1),
                            replace=False).tolist()}
    t_setup = time.perf_counter()
    with profile():
        res, rate = run(run_keys[3], calls, picks, annotate=True)
    window = rec.t_end - rec.t_start
    return {
        "t_setup": t_setup, "window_s": window, "attempted": calls * slab,
        "updates_per_s": calls * slab / window,
        "service_metrics": res.metrics, "slabs": calls,
        "draws": rec.draw_n,
    }, {"run_key": run_keys[3],
        "shards": getattr(dqn.replay.sampler, "n_shards", 1),
        "draws": rec.draws, "learn0": rec.learn0, "fb0": rec.fb0,
        "slab": slab}


def _draw_loop(cell, key, seconds: float, trace_on: bool, profile):
    from repro.rl.dqn import DQNConfig, make_dqn

    conf, traffic = cell.config, cell.traffic
    cfg = DQNConfig(**conf["dqn"])
    dqn = make_dqn(cfg)
    rb, batch = dqn.replay, cfg.batch
    p = traffic["prefill"]
    k_fill, k_pool, k_draw = jax.random.split(key, 3)
    state = jax.block_until_ready(generator.make_prefill(
        rb, dqn.example_transition, cfg.num_envs, dqn.env.n_actions, p)(
            k_fill))
    n_ins, n_td = traffic["insert_pool_steps"], traffic["td_pool_rows"]
    ins_pool, td_pool = jax.block_until_ready(generator.make_pools(
        dqn.example_transition, cfg.num_envs, dqn.env.n_actions, batch, p,
        n_ins, n_td)(k_pool))
    every = traffic["draws_per_insert"]

    def replay_draw(s, k, i):
        return rb.sample(s, jax.random.fold_in(k, i), batch)

    def replay_write(s, idx, pool, i):
        return rb.update_priorities(s, idx, pool[i % n_td])

    def replay_insert(s, pool, j):
        step = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, j % n_ins, 0), pool)
        return rb.add_block(s, step, aggregated=True)

    draw, write = jax.jit(replay_draw), jax.jit(replay_write)
    insert = jax.jit(replay_insert)
    ann = (jax.profiler.TraceAnnotation if trace_on
           else lambda _n: contextlib.nullcontext())
    records = {}

    def loop(state, t_stop, n_max, picks, lat):
        i = 0
        while i < n_max and time.perf_counter() < t_stop:
            before = state
            t0 = time.perf_counter()
            with ann("draw"):
                out = draw(state, k_draw, i)
                jax.block_until_ready(out)
            lat.append(time.perf_counter() - t0)
            with ann("write"):
                state = write(state, out[0], td_pool, i)
            written = state
            if i % every == every - 1:
                with ann("insert"):
                    state = insert(state, ins_pool, i // every)
            if i in picks:
                records[i] = {"state": before, "out": out, "after": written}
            i += 1
        return state, i

    lat: list = []
    state, _ = loop(state, math.inf, traffic["warmup_draws"], {0}, lat)
    jax.block_until_ready(state)
    est = len(lat) / max(sum(lat[len(lat) // 2:]) * 2, 1e-9)
    expect = max(int(est * seconds), 16)
    rng = np.random.default_rng(cell.seed_words)
    picks = {0, *rng.choice(np.arange(1, expect // 2),
                            size=traffic["check_draws"] - 1,
                            replace=False).tolist()}
    records.clear()
    lat = []
    t_setup = time.perf_counter()
    with profile():
        with ann("bench_window"):
            t0 = time.perf_counter()
            state, n = loop(state, t0 + seconds, math.inf, picks, lat)
            t1 = time.perf_counter()
        jax.block_until_ready(state)
    lat_ms = np.asarray(lat) * 1e3
    return {
        "t_setup": t_setup, "window_s": t1 - t0, "attempted": n,
        "draw_p95_ms": float(np.percentile(lat_ms, 95)),
        "draw_latencies_ms": lat_ms, "draws": n,
    }, {"key": k_draw,
        "shards": getattr(rb.sampler, "n_shards", 1),
        "draws": records, "td_pool": td_pool}


LOOPS = {"service": _service, "draw_loop": _draw_loop}
