"""What ``correct`` compares, and with which limits.

Each number is a gap between what the timed path produced and a plain
reference (``bench/reference``) given the same inputs:

``init``       learner weights at the run's start that differ from the
               reference's He initialisation from the run key (count);
``draw``       sampled rows that differ from the sampler law's draw with
               the same table and key (count), or for a sum tree the
               largest distance of a drawn row's cumulative interval from
               its target, as a share of the total mass;
``weights``    largest relative gap of the importance weights;
``stacks``     largest absolute gap of the materialised batch (frame
               stacks, n-step return, terminal flag, action);
``td``         largest TD-error gap over the first slab, over the RMS of
               the reference's TD errors;
``loss``       largest relative gap of the first slab's per-step losses;
``delta``      worst leaf's gap between the norms of the program's and
               the reference's parameter change over the first slab, over
               that leaf's reference norm or the median leaf's, whichever
               is larger (leaves whose reference gradient is under a
               thousandth of the median leaf's are left out);
``priorities`` largest gap of the written-back priority table, as a share
               of ``v_max`` (quantized table) or of the largest written
               priority (float table).

With ``control=True`` the reference in bfloat16 takes the program's place
(the lower precision a later change could be tempted to use); its
numbers must fail at least one limit.

The sampler and learner references are the modules the configuration
names in ``law.reference_sampler`` and ``law.reference_learner``; the
learner's interface is set out in ``bench/reference/td_loss.py``.
"""
from __future__ import annotations

import inspect

import numpy as np

import jax
import jax.numpy as jnp

from bench.reference import frames as ref_frames
from bench.reference import writeback as ref_wb

BF16 = jnp.bfloat16


def _host(x):
    return np.asarray(jax.device_get(x))


def _flat_slab(x):
    """[S, B, ...] slab rows -> the one draw's [S*B, ...] order (slab row
    s, column b is draw row b*S + s)."""
    x = _host(x)
    return x.swapaxes(0, 1).reshape((-1,) + x.shape[2:])


def _table(c, t: dict) -> dict:
    """Host view of a recorded sampler table (``drive.Keep``): (pq, valid)
    or float priorities."""
    if c.quant:
        return {"pq": _host(t["pq"]), "valid": _host(t["valid"])}
    return {"prios": _host(t["prios"]).astype(np.float64)}


class Cell:
    """Law parameters of one configuration, and its reference modules."""

    def __init__(self, conf: dict, ref_sampler, ref_learner, shards: int):
        d = conf["dqn"]
        self.sampler = d["sampler"]
        self.ref, self.learner = ref_sampler, ref_learner
        self.quant = self.sampler.startswith("amper")
        self.shards = shards
        self.cap = d["replay_size"]
        self.alpha, self.beta = d["alpha"], d["beta"]
        self.eps = conf["law"]["priority_eps"]
        self.frac_bits = conf["law"]["frac_bits"]
        self.v_max = d["v_max"]
        self.m, self.lam_fr = d["amper_m"], d["amper_lam_fr"]
        self.csp = max(int(self.cap * d["amper_csp_ratio"]), d["batch"])
        self.hist, self.stride = d["history_len"], d["num_envs"]
        self.n_step, self.gamma = d["n_step"], d["gamma"]
        self.is_weights = conf["law"]["importance_weights"]

    def prios(self, t):
        if self.quant:
            return self.ref.priorities(t["pq"], t["valid"], v_max=self.v_max,
                                       frac_bits=self.frac_bits)
        return t["prios"]

    def law_draw(self, t, key, n, dtype=np.float32):
        kw = dict(m=self.m, lam_fr=self.lam_fr, v_max=self.v_max,
                  frac_bits=self.frac_bits, csp_capacity=self.csp,
                  dtype=dtype)
        if "shards" in inspect.signature(self.ref.draw).parameters:
            kw["shards"] = self.shards
        return self.ref.draw(t["pq"], t["valid"], key, n, **kw)

    def materialize(self, view, idx, dtype=np.float32):
        """The reference batch at ``idx`` from a recorded view, whose
        ``rows`` are in ``idx``'s order."""
        return ref_frames.materialize(
            view["rows"], idx, int(view["size"]), capacity=self.cap,
            history_len=self.hist, stride=self.stride, n_step=self.n_step,
            gamma=self.gamma, scale=1.0 / 255.0, dtype=dtype)


def _draw_numbers(c: Cell, view, key, beta, idx, batch, w, control):
    """draw / weights / stacks numbers of one draw of ``len(idx)`` rows;
    ``view`` is the draw's recorded ``drive.Keep.view``."""
    t = _table(c, view["table"])
    prios = c.prios(t)
    size = int(view["size"])
    out = {}
    if c.quant:
        want = c.law_draw(t, key, len(idx))
        got = c.law_draw(t, key, len(idx), BF16) if control else idx
        out["draw"] = float(np.sum(np.asarray(got) != want))
    else:
        got = c.ref.draw(prios, key, len(idx), BF16) if control else idx
        out["draw"] = c.ref.mass_gap(prios, key, got)
    want_w = c.ref.weights(prios, idx, size, beta)
    got_w = c.ref.weights(prios, idx, size, beta, BF16) if control else w
    out["weights"] = float(np.max(np.abs(np.asarray(got_w, np.float64)
                                         - want_w) / want_w))
    want_b = c.materialize(view, idx)
    got_b = c.materialize(view, idx, BF16) if control else batch
    out["stacks"] = ref_frames.gap(got_b, want_b)
    return out, want_b, want_w


def _writeback_number(c: Cell, before, after, idx, abs_td, live, control):
    """Gap of the table written back over ``idx``: ``before`` and
    ``after`` are recorded tables, ``live`` the rows whose sample-time
    stamps still held (None: a write without stamps)."""
    t0 = _table(c, before)
    want, allowed = ref_wb.new_priorities(
        c.prios(t0), idx, abs_td, alpha=c.alpha, eps=c.eps, live=live)
    if control:
        got, _ = ref_wb.new_priorities(c.prios(t0), idx, abs_td,
                                       alpha=c.alpha, eps=c.eps, live=live,
                                       dtype=BF16)
    else:
        got = c.prios(_table(c, after))
    if c.quant:
        want = np.minimum(want, c.v_max)
        allowed = {r: {min(v, c.v_max) for v in vs}
                   for r, vs in allowed.items()}
        got = np.minimum(got, c.v_max)
        scale = c.v_max
    else:
        scale = float(np.max(want))
    return ref_wb.gap(got, want, allowed, scale)


def _learner_numbers(c: Cell, conf, rec, batch_ref, w_ref, control):
    l0 = rec["learn0"]
    s = rec["slab"]
    p0 = c.learner.learner_init(rec["run_key"], conf)
    init = sum(int(np.sum(_host(a) != _host(b))) for a, b in zip(
        jax.tree.leaves(p0), jax.tree.leaves(l0["params"])))
    # Back to slab order: flat row b*S + s -> [s, b].
    slab = lambda x: jnp.asarray(np.asarray(x).reshape(
        (-1, s) + np.shape(x)[1:]).swapaxes(0, 1))
    b = {k: slab(v) for k, v in batch_ref.items()}
    b["action"] = b["action"].astype(jnp.int32)
    w = slab(w_ref if c.is_weights else np.ones(len(w_ref), np.float32))
    w = w.astype(jnp.float32)
    step0 = int(l0["step0"])
    loss_r, td_r, par_r, g0 = c.learner.learner_follow(p0, b, w, conf,
                                                       step0=step0)
    if control:
        loss_p, td_p, par_p, _ = c.learner.learner_follow(
            p0, b, w, conf, step0=step0, dtype=BF16)
    else:
        loss_p, td_p, par_p = l0["loss"], l0["td"], l0["out_params"]
    loss_r, td_r = _host(loss_r), _host(td_r)
    loss_p, td_p = _host(loss_p), _host(td_p)
    td_n = float(np.max(np.abs(td_p - td_r))
                 / max(np.sqrt(np.mean(td_r ** 2)), 1e-12))
    loss_n = float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r)))
    gn = np.array([np.linalg.norm(_host(x)) for x in jax.tree.leaves(g0)])
    dr = np.array([np.linalg.norm(_host(a) - _host(p)) for a, p in zip(
        jax.tree.leaves(par_r), jax.tree.leaves(p0))])
    dp = np.array([np.linalg.norm(_host(a) - _host(p)) for a, p in zip(
        jax.tree.leaves(par_p), jax.tree.leaves(p0))])
    moved = gn >= 1e-3 * np.median(gn)
    floor = np.median(dr[moved])
    delta = float(np.max(np.abs(dp - dr)[moved]
                         / np.maximum(dr[moved], floor)))
    return {"init": float(init), "td": td_n, "loss": loss_n, "delta": delta}


def service_numbers(c: Cell, conf: dict, rec: dict, control: bool) -> dict:
    """Numbers of a ``service`` window: worst over the picked slabs."""
    worst: dict = {}

    def keep(nums):
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0.0), v)

    learn_in = None
    for i, d in sorted(rec["draws"].items()):
        idx, batch, w, _stamp = d["out"]
        idx_f = _flat_slab(idx)
        batch_f = {k: _flat_slab(v) for k, v in batch.items()}
        view = {**d["view"], "rows": {k: _flat_slab(v) for k, v in
                                      d["view"]["rows"].items()}}
        beta = float(d["beta"]) if d["beta"] is not None else c.beta
        nums, want_b, want_w = _draw_numbers(
            c, view, d["key"], beta, idx_f, batch_f, _flat_slab(w),
            control)
        keep(nums)
        if i == 0:
            learn_in = (want_b, want_w)
    keep(_learner_numbers(c, conf, rec, *learn_in, control))
    fb = rec["fb0"]
    before, stamp = fb["before"], _host(fb["stamp"]).reshape(-1, 2)
    live = ((_host(before["write_stamp"]).reshape(-1) == stamp[:, 0])
            & (_host(before["write_gen"]).reshape(-1) == stamp[:, 1]))
    keep({"priorities": _writeback_number(
        c, before["table"], fb["after"], _host(fb["idx"]).reshape(-1),
        np.abs(_host(fb["td"]).reshape(-1)), live, control)})
    return worst


def draw_loop_numbers(c: Cell, conf: dict, rec: dict, control: bool) -> dict:
    worst: dict = {}
    pool = _host(rec["td_pool"])
    for i, d in sorted(rec["draws"].items()):
        idx, batch, w = (_host(x) if not isinstance(x, dict) else
                         {k: _host(v) for k, v in x.items()}
                         for x in d["out"])
        key = jax.random.fold_in(rec["key"], i)
        nums, _, _ = _draw_numbers(c, d["view"], key, c.beta, idx, batch,
                                   w, control)
        nums["priorities"] = _writeback_number(
            c, d["view"]["table"], d["after"], idx, pool[i % len(pool)],
            None, control)
        for k, v in nums.items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


NUMBERS = {"service": service_numbers, "draw_loop": draw_loop_numbers}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit; a number without a limit fails."""
    out = {k: {"value": v, "limit": limits.get(k)} for k, v in
           sorted(numbers.items())}
    ok = all(e["limit"] is not None and np.isfinite(e["value"])
             and e["value"] <= e["limit"] for e in out.values())
    return ok, out
