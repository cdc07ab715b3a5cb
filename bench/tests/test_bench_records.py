"""What the harness keeps for the check (``drive.Keep``), driven through
every cell at a tiny size on the CPU: the recorded rows give the numbers
that the whole replay states gave, a pick holds no ring, and a run whose
states are deleted after each recorded call still reads correct."""
import math

import jax
import numpy as np
import pytest

from bench import check, drive
from bench.tests._tiny import run_tiny

CELLS = ["amper-1m.learn", "per-1m.learn", "amper-1m.draw"]


def _old_table(sampler: str, sstate, capacity: int):
    """The whole-state table read the check made before it kept copies."""
    if sampler.startswith("amper"):
        return {"pq": np.asarray(sstate.pq), "valid": np.asarray(sstate.valid)}
    tree = np.asarray(sstate.tree)
    leaf0 = 1 << max(capacity - 1, 0).bit_length()
    return {"prios": tree[leaf0:leaf0 + capacity].astype(np.float64)}


def _old_materialize(ring: dict, stamp, size: int, idx, *, history_len: int,
                     stride: int, n_step: int, gamma: float, scale: float,
                     dtype=np.float32) -> dict:
    """The frame law over the whole ring, as the check ran it before."""
    frame = np.asarray(ring["frame"])
    done = np.asarray(ring["done"], np.float32)
    reward = np.asarray(ring["reward"], np.float32)
    stamp = np.asarray(stamp, np.int64)
    cap = len(stamp)
    a = np.asarray(idx, np.int64) % cap
    ref = stamp[a]
    sc = np.float32(scale)

    def cast(x):
        if dtype == np.float32:
            return np.asarray(x, np.float32)
        import ml_dtypes

        return np.asarray(np.asarray(x, ml_dtypes.bfloat16), np.float32)

    def stack(end, end_stamp, ok):
        out = []
        for j in range(history_len):
            slot = (end - j * stride) % cap
            if j:
                ok = (ok & (stamp[slot] - end_stamp == -j * stride)
                      & (slot < size) & (done[slot] < 0.5))
            f = cast(frame[slot].astype(np.float32) * sc)
            out.append(f * ok.reshape(ok.shape + (1,) * (f.ndim - 1)))
        return np.stack(out[::-1], axis=-1)

    written = a < size
    obs = stack(a, ref, written)
    enter = written.copy()
    ret = np.zeros(len(a), np.float32)
    for k in range(n_step):
        slot = (a + k * stride) % cap
        use = enter & (stamp[slot] - ref == k * stride) & (slot < size)
        ret = cast(ret + use * cast(np.float32(gamma ** k) * reward[slot]))
        enter = use & (done[slot] < 0.5)
    boot = (a + n_step * stride) % cap
    has = enter & (stamp[boot] - ref == n_step * stride) & (boot < size)
    nxt = stack(boot, stamp[boot], has)
    term = 1.0 - has.astype(np.float32)
    return {"obs": obs, "action": np.asarray(ring["action"])[a],
            "reward": ret, "next_obs": nxt, "terminated": term}


def _whole_view(state):
    """A draw's view that carries its whole replay state."""
    return {"table": state.sampler_state, "size": state.size, "rows": {},
            "state": state}


@pytest.fixture
def old_path(monkeypatch):
    """Route the check's table and stack reads of whole-state views and
    of sampler states through the whole-state path it had before."""
    table, materialize = check._table, check.Cell.materialize

    def old_table(c, t):
        if isinstance(t, dict):
            return table(c, t)
        return _old_table(c.sampler, t, c.cap)

    def old_materialize(self, view, idx, dtype=np.float32):
        if "state" not in view:
            return materialize(self, view, idx, dtype)
        st = view["state"]
        return _old_materialize(
            st.storage, st.write_stamp, int(st.size), idx,
            history_len=self.hist, stride=self.stride, n_step=self.n_step,
            gamma=self.gamma, scale=1.0 / 255.0, dtype=dtype)

    monkeypatch.setattr(check, "_table", old_table)
    monkeypatch.setattr(check.Cell, "materialize", old_materialize)


def _spied_run(cell: str):
    """A tiny run that also keeps the whole state behind each kept copy.
    -> (check.Cell, config, records, {id of a kept copy: its state})."""
    seen, whole = {}, {}

    class Spy(drive.Keep):
        def __init__(self, replay):
            super().__init__(replay)
            for name in ("view", "fed", "table"):
                setattr(self, name, self._remember(getattr(self, name)))

        @staticmethod
        def _remember(keep):
            def call(state, *args):
                out = keep(state, *args)
                whole[id(out)] = state
                return out
            return call

    def capture(numbers):
        def call(law, conf, rec, control):
            seen.setdefault("args", (law, conf, rec))
            return numbers(law, conf, rec, control)
        return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(drive, "Keep", Spy)
        for loop, numbers in check.NUMBERS.items():
            mp.setitem(check.NUMBERS, loop, capture(numbers))
        out, _ = run_tiny(cell, control=False)
    assert out["correct"] is True, out["checks"]
    return (*seen["args"], whole)


@pytest.fixture(scope="module", params=CELLS)
def spied(request):
    return request.param, _spied_run(request.param)


def _whole_records(rec: dict, whole: dict) -> dict:
    """The same records with every kept copy replaced by its whole state."""
    draws = {i: {**d, "view": _whole_view(whole[id(d["view"])])}
             for i, d in rec["draws"].items()}
    for i, d in draws.items():
        if "after" in d:
            d["after"] = whole[id(d["after"])].sampler_state
    out = {**rec, "draws": draws}
    if "fb0" in rec:
        fb = rec["fb0"]
        before = whole[id(fb["before"])]
        idx = np.asarray(fb["idx"]).reshape(-1)
        out["fb0"] = {**fb, "after": whole[id(fb["after"])].sampler_state,
                      "before": {"table": before.sampler_state,
                                 "write_stamp":
                                     np.asarray(before.write_stamp)[idx],
                                 "write_gen":
                                     np.asarray(before.write_gen)[idx]}}
    return out


def test_recorded_rows_give_the_whole_state_numbers(spied, old_path):
    cell, (law, conf, rec, whole) = spied
    loop = "service" if "fb0" in rec else "draw_loop"
    rec_whole = _whole_records(rec, whole)
    for control in (False, True):
        kept = check.NUMBERS[loop](law, conf, rec, control)
        full = check.NUMBERS[loop](law, conf, rec_whole, control)
        assert kept == full, (cell, control)
    # The reference batches themselves, bit for bit, at every pick.
    flat = check._flat_slab if loop == "service" else np.asarray
    for i, d in rec["draws"].items():
        idx = flat(d["out"][0])
        view = {**d["view"], "rows": {k: flat(v) for k, v in
                                      d["view"]["rows"].items()}}
        for dtype in (np.float32, check.BF16):
            got = law.materialize(view, idx, dtype)
            want = law.materialize(rec_whole["draws"][i]["view"], idx, dtype)
            for k in want:
                np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_a_pick_keeps_rows_and_the_table_not_the_ring(spied):
    cell, (law, conf, rec, _) = spied
    d = conf["dqn"]
    cap, k = d["replay_size"], d["history_len"] + d["n_step"]
    rows = d["batch"] * (conf["service"]["slab"] if "fb0" in rec else 1)
    frame = conf["law"]["frame_hw"]
    row_bytes = math.prod(frame) + 5 * 4  # + done, reward, action, stamps
    entry_bytes = 5 if law.quant else 4   # pq and valid, or a tree leaf
    bound = rows * k * row_bytes + cap * entry_bytes + 4
    assert rows * k < cap
    for pick in rec["draws"].values():
        view = pick["view"]
        for key, leaf in view["rows"].items():
            lead = leaf.shape[:leaf.ndim - (len(frame) if key == "frame"
                                            else 0)]
            assert math.prod(lead) == rows * k, (key, leaf.shape)
        kept = sum(x.nbytes for x in jax.tree.leaves(view))
        assert kept <= bound, (cell, kept, bound)
    # Besides the sampler tables, no recorded array has the ring's rows.
    for path, leaf in jax.tree_util.tree_flatten_with_path(rec)[0]:
        if getattr(leaf, "ndim", 0) and leaf.shape[0] == cap:
            assert path[-1].key in ("pq", "valid", "prios"), path


def _delete(tree):
    for x in jax.tree.leaves(tree):
        x.delete()


@pytest.mark.parametrize("cell", CELLS)
def test_correct_with_states_deleted_after_each_recorded_call(
        monkeypatch, cell):
    """The service's draw and write-back get a private copy of the state,
    deleted as soon as the call returns; the closed loop's write-back and
    insert delete the state they were given, as donating programs do."""

    class Deleting(drive.ServiceRecorder):
        def __init__(self, svc):
            super().__init__(svc)

            def on_copy(f):
                def call(state, *args):
                    mine = drive._copy(state)
                    out = f(mine, *args)
                    _delete(mine)
                    return out
                return call

            svc._sample = on_copy(svc._sample)
            svc._apply_feedback = on_copy(svc._apply_feedback)

    programs = drive._draw_programs

    def donating(f):
        def call(state, *args):
            out = f(state, *args)
            _delete(state)
            return out
        return call

    def deleting_programs(*args):
        draw, write, insert = programs(*args)
        return draw, donating(write), donating(insert)

    monkeypatch.setattr(drive, "ServiceRecorder", Deleting)
    monkeypatch.setattr(drive, "_draw_programs", deleting_programs)
    out, ctrl = run_tiny(cell)
    assert out["correct"] is True, out["checks"]
    assert any(c["value"] > c["limit"] for c in ctrl.values()), ctrl


@pytest.mark.parametrize("perm", [(0, 1, 2), (1, 2, 0), (2, 0, 1)])
def test_take_reads_rows_in_any_layout_order(perm):
    k1, k2 = jax.random.split(jax.random.key(3))
    x = jax.random.randint(k1, (64, 3, 5), 0, 256).astype(jax.numpy.uint8)
    slots = jax.random.randint(k2, (4, 7), 0, 64)
    got = jax.jit(drive._take, static_argnums=2)(x, slots, perm)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(x)[np.asarray(slots)])


@pytest.mark.parametrize("shards", [4, 8])
def test_keep_view_of_a_sharded_ring_reads_the_same_rows(shards):
    """Each shard reads the slots it holds; together they give the plain
    gather of the whole ring, across the ring's wrap."""
    if jax.device_count() < shards:
        pytest.skip(f"needs {shards} devices")
    from bench import generator
    from repro.core.replay_buffer import FrameStore, ReplayBuffer
    from repro.core.samplers import make_sampler
    from repro.launch.mesh import make_mesh

    cap, envs = 512, 4
    mesh = make_mesh((shards,), ("data",))
    rb = ReplayBuffer(
        cap, make_sampler("amper-fr-sharded", cap, mesh=mesh,
                          axis_names=("data",), v_max=8.0),
        frame_store=FrameStore(history_len=4, frame_shape=(10, 10),
                               stride=envs, n_step=3), num_envs=envs)
    ex = {"frame": jax.numpy.zeros((10, 10), jax.numpy.uint8),
          "action": jax.numpy.int32(0), "reward": jax.numpy.float32(0),
          "done": jax.numpy.float32(0), "terminated": jax.numpy.float32(0)}
    p = {"episode_len": [3, 12], "reward_rate": 0.3, "pixel_density": 0.2}
    st = rb.init(ex)
    for i in range(2):   # 800 rows: the second block wraps the ring
        st = rb.add_block(st, generator.steps_block(
            jax.random.key(5 + i), ex, 100, envs, p, 3), aggregated=True)
    assert len(st.storage["frame"].sharding.device_set) == shards
    idx = jax.numpy.arange(0, cap, 5, dtype=jax.numpy.int32).reshape(-1, 1)
    view = drive.Keep(rb).view(st, idx)
    slots = (np.asarray(idx)[..., None]
             + np.arange(-3, 4) * envs) % cap
    ring = dict(st.storage, write_stamp=st.write_stamp,
                write_gen=st.write_gen)
    for k, rows in view["rows"].items():
        np.testing.assert_array_equal(np.asarray(rows),
                                      np.asarray(ring[k])[slots], err_msg=k)
    np.testing.assert_array_equal(np.asarray(view["table"]["pq"]),
                                  np.asarray(st.sampler_state.pq))
