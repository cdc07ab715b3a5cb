"""How a configuration wires the harness (CPU, tiny sizes): the learner
reference named by ``law.reference_learner`` and what the check and
``learner_mfu`` read through it, the one replay ring the service loop
hands from run to run, and the ``service`` section passed through to
``ReplayService``."""
import contextlib
import gc
import types

import jax
import numpy as np
import pytest

from bench import check, drive, run, spec
from bench.reference import td_loss
from bench.tests._tiny import TINY, run_tiny

CELLS = ["amper-1m.learn", "per-1m.learn", "amper-1m.draw"]
SEED = 2**31 + 11


def _direct_learner_numbers(c, conf, rec, batch_ref, w_ref, control):
    """The learner numbers as the check took them before it read the
    configuration's learner reference: ``td_loss`` called directly."""
    d = conf["dqn"]
    l0 = rec["learn0"]
    s = rec["slab"]
    shape = batch_ref["obs"].shape
    p0 = td_loss.init(rec["run_key"], d["history_len"], shape[1:3],
                      d["hidden"], conf["law"]["n_actions"])
    init = sum(int(np.sum(check._host(a) != check._host(b))) for a, b in zip(
        jax.tree.leaves(p0), jax.tree.leaves(l0["params"])))
    slab = lambda x: jax.numpy.asarray(np.asarray(x).reshape(  # noqa: E731
        (-1, s) + np.shape(x)[1:]).swapaxes(0, 1))
    b = {k: slab(v) for k, v in batch_ref.items()}
    b["action"] = b["action"].astype(jax.numpy.int32)
    w = slab(w_ref if c.is_weights else np.ones(len(w_ref), np.float32))
    w = w.astype(jax.numpy.float32)
    gamma_n = d["gamma"] ** d["n_step"]
    loss_r, td_r, par_r, g0 = td_loss.follow(
        p0, b, w, gamma_n=gamma_n, lr=d["lr"], step0=int(l0["step0"]))
    if control:
        loss_p, td_p, par_p, _ = td_loss.follow(
            p0, b, w, gamma_n=gamma_n, lr=d["lr"], step0=int(l0["step0"]),
            dtype=check.BF16)
    else:
        loss_p, td_p, par_p = l0["loss"], l0["td"], l0["out_params"]
    host = check._host
    loss_r, td_r = host(loss_r), host(td_r)
    loss_p, td_p = host(loss_p), host(td_p)
    td_n = float(np.max(np.abs(td_p - td_r))
                 / max(np.sqrt(np.mean(td_r ** 2)), 1e-12))
    loss_n = float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r)))
    gn = np.array([np.linalg.norm(host(x)) for x in jax.tree.leaves(g0)])
    dr = np.array([np.linalg.norm(host(a) - host(p)) for a, p in zip(
        jax.tree.leaves(par_r), jax.tree.leaves(p0))])
    dp = np.array([np.linalg.norm(host(a) - host(p)) for a, p in zip(
        jax.tree.leaves(par_p), jax.tree.leaves(p0))])
    moved = gn >= 1e-3 * np.median(gn)
    floor = np.median(dr[moved])
    delta = float(np.max(np.abs(dp - dr)[moved]
                         / np.maximum(dr[moved], floor)))
    return {"init": float(init), "td": td_n, "loss": loss_n, "delta": delta}


def _ring_arrays(shape) -> set:
    """Ids of the live device arrays of the ring's frame shape."""
    gc.collect()
    return {id(x) for x in jax.live_arrays()
            if x.shape == shape and x.dtype == np.uint8}


def _watched_run(cell: str) -> dict:
    """A tiny run that keeps the check's inputs, the ring arrays alive as
    the window starts, and the buffers each service run started from and
    ended with."""
    from repro.runtime import ReplayService

    conf = spec.load_cell(cell, SEED, TINY).config
    shape = (conf["dqn"]["replay_size"], *conf["law"]["frame_hw"])
    seen = {"runs": []}
    before = _ring_arrays(shape)

    @contextlib.contextmanager
    def window_start(on, out):
        seen["rings"] = len(_ring_arrays(shape) - before)
        yield

    def capture(numbers):
        def call(law, conf, rec, control):
            seen.setdefault("args", (law, conf, rec))
            return numbers(law, conf, rec, control)
        return call

    svc_run = ReplayService.run

    def handing_run(self, key, n, manager=None):
        init, start = self.dqn.init, {}

        def remember(k):
            st = init(k)
            start["buffer"] = id(st.buffer)
            return st

        self.dqn = self.dqn._replace(init=remember)
        res = svc_run(self, key, n, manager)
        self.dqn = self.dqn._replace(init=init)
        seen["runs"].append((start["buffer"], id(res.buffer)))
        return res

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "_profiling", window_start)
        mp.setattr(ReplayService, "run", handing_run)
        for loop, numbers in check.NUMBERS.items():
            mp.setitem(check.NUMBERS, loop, capture(numbers))
        out, _ = run_tiny(cell, seed=SEED, control=False)
    assert out["correct"] is True, out["checks"]
    return seen


@pytest.fixture(scope="module", params=CELLS)
def watched(request):
    return request.param, _watched_run(request.param)


def test_readings_through_the_named_learner_equal_the_direct_calls(
        watched, monkeypatch):
    cell, seen = watched
    law, conf, rec = seen["args"]
    loop = "service" if "fb0" in rec else "draw_loop"
    assert law.learner is not td_loss  # loaded by name, not imported
    named = {c: check.NUMBERS[loop](law, conf, rec, c) for c in (False, True)}
    monkeypatch.setattr(check, "_learner_numbers", _direct_learner_numbers)
    for control in (False, True):
        direct = check.NUMBERS[loop](law, conf, rec, control)
        assert named[control] == direct, (cell, control)
    if loop == "service":
        assert {"init", "td", "loss", "delta"} <= set(named[False])


def test_the_window_starts_with_one_ring_handed_on_from_set_up(watched):
    cell, seen = watched
    assert seen["rings"] == 1, cell
    runs = seen["runs"]
    if cell.endswith(".learn"):
        # Warm-up, two rate runs, the window: each starts from the ring
        # the run before it ended with.
        assert len(runs) == 4
        for (_, end), (start, _) in zip(runs, runs[1:]):
            assert start == end
    else:
        assert runs == []


def test_a_stand_in_learner_reference_is_called_and_moves_the_numbers(
        monkeypatch):
    calls = []

    def follow(*args, **kw):
        calls.append("follow")
        losses, td, params, grad0 = td_loss.learner_follow(*args, **kw)
        return 1.5 * losses, td, params, grad0

    def init(key, conf):
        calls.append("init")
        return td_loss.learner_init(key, conf)

    stand_in = types.SimpleNamespace(learner_init=init,
                                     learner_follow=follow,
                                     update_flops=td_loss.update_flops)
    load = spec.Cell.reference
    monkeypatch.setattr(spec.Cell, "reference", lambda self, name: (
        stand_in if name == "td_loss" else load(self, name)))
    out, _ = run_tiny("amper-1m.learn", control=False)
    assert calls[0] == "init" and "follow" in calls
    assert out["correct"] is False
    # The program's losses against 1.5 times the reference's.
    assert out["checks"]["loss"]["value"] == pytest.approx(1 / 3, abs=0.05)
    assert out["checks"]["stacks"]["value"] == 0


def test_a_law_without_reference_learner_fails_naming_the_key(monkeypatch):
    load_cell = spec.load_cell

    def without(*args, **kw):
        cell = load_cell(*args, **kw)
        del cell.config["law"]["reference_learner"]
        return cell

    monkeypatch.setattr(spec, "load_cell", without)
    with pytest.raises(KeyError, match="reference_learner"):
        run.run("amper-1m.draw", 1, 0.5, False, platform="cpu",
                overrides=TINY)


@pytest.mark.parametrize("cell", ["amper-1m.learn", "per-1m.learn"])
def test_learner_mfu_reads_the_formula_it_read_before(cell):
    from bench import work

    c = spec.load_cell(cell, 1)
    d, law = c.config["dqn"], c.config["law"]
    ctx = types.SimpleNamespace(
        config=c.config, reference=c.reference, chips=1,
        device_kind="TPU v5 lite", result={"updates_per_s": 1026.6123})
    fwd = work.conv_qnet_forward_flops(
        *law["frame_hw"], d["history_len"], hidden=d["hidden"],
        n_actions=law["n_actions"])
    flops = work.qnet_update_flops(
        d["batch"], fwd, double=d.get("agent", "dqn") == "double")
    before = 100.0 * flops * 1026.6123 / 197e12
    assert c.reader("learner_mfu").read(ctx) == before


class _Built(Exception):
    pass


def test_service_section_passes_to_replay_service(monkeypatch):
    import repro.runtime as runtime

    got = {}

    def recorded(cfg, **kw):
        got.update(kw)
        raise _Built

    service = {"prefetch_depth": 3, "queue_size": 5}
    cell = spec.load_cell("amper-1m.learn", 1, {
        **TINY, "config": {**TINY["config"], "service": service}})
    monkeypatch.setattr(runtime, "ReplayService", recorded)
    with pytest.raises(_Built):
        drive._service(cell, jax.random.key(0), 1.0, False, None)
    # The file's keys and the two added ones, each under its own name.
    assert {k: got[k] for k in cell.config["service"]} == {
        "num_actors": 1, "chunk_len": 32, "slab": 8, **service}


@pytest.mark.parametrize("extra", [{"prefetch_deep": 3}, {"min_size": 64}])
def test_service_section_key_the_service_cannot_take_raises(extra):
    name = next(iter(extra))
    cell = spec.load_cell("amper-1m.learn", 1, {
        **TINY, "config": {**TINY["config"], "service": extra}})
    with pytest.raises(TypeError, match=name):
        drive._service(cell, jax.random.key(0), 1.0, False, None)
