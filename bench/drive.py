"""The two loops a traffic file can name (its ``loop`` key).

``service``: the async ``ReplayService`` as a user runs it, on a replay
ring prefilled in set-up that each run hands on to the next, set-up's
runs and then the window's.  The window is one ``run``: it starts at the
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
picked from the seed, and copies of what the reference reads of the state
those steps read (``Keep``): never the state itself, which a program that
donates its replay state deletes at its next write.
"""
from __future__ import annotations

import contextlib
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from bench import generator
from bench.reference import frames as ref_frames

# The ring's keys the frame law reads (the write stamps are kept with them).
_ROW_KEYS = ("frame", "done", "reward", "action")


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _table(sstate, capacity: int) -> dict:
    """The sampler table the check reads: AMPER's quantized priorities and
    validity, or the sum tree's leaves."""
    if hasattr(sstate, "pq"):
        return {"pq": sstate.pq, "valid": sstate.valid}
    leaf0 = 1 << max(capacity - 1, 0).bit_length()
    return {"prios": sstate.tree[leaf0:leaf0 + capacity]}


def _take(x, slots, perm):
    """``x[slots]`` along axis 0, for slots in range.  A leaf of more than
    one axis is read a row at a time, by dynamic slices of ``x`` seen in
    its device layout's own axis order (``perm``, major to minor), so that
    XLA never re-lays the ring out: a plain gather of rows from a uint8 ring
    of 84x84 frames on a v5e first copies the whole ring into another
    layout (5.6 GB for 500,000 rows, AOT)."""
    if x.ndim == 1:
        return x[slots]
    flat = slots.reshape(-1)
    xt = jnp.transpose(x, perm)  # the layout's own order: no copy
    ax = perm.index(0)

    def row(i, out):
        r = jax.lax.dynamic_slice_in_dim(xt, flat[i], 1, ax)
        return jax.lax.dynamic_update_slice_in_dim(out, r, i, ax)

    out = jax.lax.fori_loop(0, flat.shape[0], row, jnp.zeros(
        xt.shape[:ax] + flat.shape + xt.shape[ax + 1:], x.dtype))
    back = tuple(int(a) for a in np.argsort(perm))
    return jnp.transpose(out, back).reshape(slots.shape + x.shape[1:])


def _take_sharded(sharding, x, slots, perm):
    """``_take`` of a leaf sharded on axis 0: each shard reads the slots it
    holds, and a sum over the shards puts them together (a loop of slices
    across shards would gather the whole ring to every chip)."""
    axes = sharding.spec[0]

    def local(x, slots):
        n = x.shape[0]
        loc = slots - jax.lax.axis_index(axes) * n
        mine = (loc >= 0) & (loc < n)
        got = _take(x, jnp.where(mine, loc, 0), perm)
        mine = mine.reshape(mine.shape + (1,) * (got.ndim - mine.ndim))
        return jax.lax.psum(jnp.where(mine, got, 0), axes)

    return jax.shard_map(local, mesh=sharding.mesh, in_specs=(P(axes), P()),
                         out_specs=P(), check_vma=False)(x, slots)


class Keep:
    """Jitted device copies of what the check reads of a replay state, so
    that nothing of the state is held past the call that recorded it.

    ``view(state, idx)``: the sampler table, ``size`` and, for every row
    ``a`` of ``idx``, the ring's rows at the slots ``a + k*stride`` (mod
    the capacity) for ``k`` in ``frames.window``: ``[*idx.shape, K, ...]``
    per key.  ``fed(state, idx)``: the table and the write stamps at
    ``idx`` before a write-back.  ``table(state)``: the table alone."""

    def __init__(self, replay):
        fs, cap = replay.frame_store, replay.capacity
        steps = ref_frames.window(fs.history_len, fs.n_step) * fs.stride
        sharding = replay.storage_sharding
        take = (_take if sharding is None
                else functools.partial(_take_sharded, sharding))

        def keep_view(state, idx, perms):
            perms = dict(perms)
            slots = (idx[..., None] + jnp.asarray(steps, idx.dtype)) % cap
            ring = {k: state.storage[k] for k in _ROW_KEYS}
            ring.update(write_stamp=state.write_stamp,
                        write_gen=state.write_gen)
            return {"table": _table(state.sampler_state, cap),
                    "size": state.size,
                    "rows": {k: take(v, slots, perms.get(k, (0,)))
                             for k, v in ring.items()}}

        def keep_fed(state, idx):
            return {"table": _table(state.sampler_state, cap),
                    "write_stamp": state.write_stamp[idx],
                    "write_gen": state.write_gen[idx]}

        def keep_table(state):
            return _table(state.sampler_state, cap)

        self._view = jax.jit(keep_view, static_argnums=2)
        self.fed, self.table = jax.jit(keep_fed), jax.jit(keep_table)

    def view(self, state, idx):
        perms = tuple((k, tuple(state.storage[k].format.layout.major_to_minor))
                      for k in _ROW_KEYS if state.storage[k].ndim > 1)
        return self._view(state, idx, perms)


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
    thread, and keeps what the check reads: picked slab draws (the
    ``Keep.view`` of the drawn rows, key, beta and copies of the outputs,
    which the learner later donates), the first learner call (its inputs
    and outputs) and the write-back of that call's TD errors (the table
    and stamps before, the table after)."""

    def __init__(self, svc):
        self._keep = Keep(svc.dqn.replay)
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
            self.draws[i] = {"view": self._keep.view(state, out[0]),
                             "key": key, "beta": beta, "out": _copy(out)}
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
            self.fb0 = {"before": self._keep.fed(state, idx),
                        "idx": jnp.copy(idx), "td": jnp.copy(td),
                        "stamp": jnp.copy(stamp)}
        out = self._feedback(state, idx, td, stamp)
        if rec:
            self.fb0["after"] = self._keep.table(out)
        return out


def _service(cell, key, seconds: float, trace_on: bool, profile):
    """-> (window result dict, records for the check)."""
    from repro import obs
    from repro.rl.dqn import DQNConfig
    from repro.runtime import ReplayService

    conf, traffic = cell.config, cell.traffic
    cfg = DQNConfig(**conf["dqn"])
    # The configuration's service section as keyword arguments: a key
    # ReplayService does not take, or one the harness sets, raises.
    svc = ReplayService(
        cfg, **conf["service"], min_size=cfg.batch,
        max_replay_ratio=cfg.batch * traffic["frames_per_update_per_row"],
        telemetry=obs.Telemetry(probe_every=0, profile=trace_on))
    dqn = svc.dqn
    k_fill, k_run = jax.random.split(key)
    prefill = generator.make_prefill(
        dqn.replay, dqn.example_transition, cfg.num_envs, dqn.env.n_actions,
        traffic["prefill"])
    # One ring: the first run starts from the prefilled one, each later
    # run from the final ring of the run before it (``RunResult.buffer``).
    # Between runs the harness holds that ring alone, and no copy of it.
    ring = [jax.block_until_ready(prefill(k_fill))]
    init = dqn.init

    def init_filled(k):
        return init(k)._replace(buffer=ring.pop())

    svc.dqn = dqn._replace(init=init_filled)
    rec = ServiceRecorder(svc)
    slab = svc.slab
    run_keys = jax.random.split(k_run, 4)

    def run(k, calls, picks, annotate=False):
        rec.arm(calls, picks, annotate)
        res = svc.run(k, calls * slab)
        ring.append(res.buffer)
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


def _draw_programs(rb, batch: int, n_td: int, n_ins: int):
    """The client's jitted draw, write-back and insert."""

    def replay_draw(s, k, i):
        return rb.sample(s, jax.random.fold_in(k, i), batch)

    def replay_write(s, idx, pool, i):
        return rb.update_priorities(s, idx, pool[i % n_td])

    def replay_insert(s, pool, j):
        step = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, j % n_ins, 0), pool)
        return rb.add_block(s, step, aggregated=True)

    return jax.jit(replay_draw), jax.jit(replay_write), jax.jit(replay_insert)


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
    draw, write, insert = _draw_programs(rb, batch, n_td, n_ins)
    keep = Keep(rb)
    ann = (jax.profiler.TraceAnnotation if trace_on
           else lambda _n: contextlib.nullcontext())
    records = {}

    def loop(state, t_stop, n_max, picks, lat):
        i = 0
        while i < n_max and time.perf_counter() < t_stop:
            t0 = time.perf_counter()
            with ann("draw"):
                out = draw(state, k_draw, i)
                jax.block_until_ready(out)
            lat.append(time.perf_counter() - t0)
            if i in picks:  # before the write, which may consume state
                records[i] = {"view": keep.view(state, out[0]), "out": out}
            with ann("write"):
                state = write(state, out[0], td_pool, i)
            if i in picks:
                records[i]["after"] = keep.table(state)
            if i % every == every - 1:
                with ann("insert"):
                    state = insert(state, ins_pool, i // every)
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
