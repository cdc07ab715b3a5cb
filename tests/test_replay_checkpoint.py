"""Replay-aware checkpointing: every registry sampler's state round-trips
bitwise, hidden exact-resume state (write stamps, add counter,
max_priority, ring position) survives, and sharded checkpoints restore
elastically onto a different shard count with membership-exact
priorities."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.replay_buffer import ReplayBuffer
from repro.core import sharded as sharded_mod
from repro.core.samplers import abstract_state, make_sampler
from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ck
from repro.train import replay_checkpoint as rck

CAP = 512
EX = {"obs": jnp.zeros(4), "reward": jnp.float32(0)}


def _populated(rb, seed=0, rounds=5):
    """Buffer exercised through add / sample / priority-update cycles so
    every piece of hidden state is non-trivial (incl. ring wraparound)."""
    st = rb.init(EX)
    k = jax.random.key(seed)
    for i in range(rounds):
        st = rb.add_batch(st, {
            "obs": jax.random.normal(jax.random.fold_in(k, i), (200, 4)),
            "reward": jnp.arange(200, dtype=jnp.float32)})
        idx, _, _ = rb.sample(st, jax.random.fold_in(k, 100 + i), 32)
        st = rb.update_priorities(
            st, idx, jax.random.normal(jax.random.fold_in(k, 200 + i), (32,)))
    return st


@pytest.mark.parametrize("kind", ["uniform", "per-sumtree", "per-cumsum",
                                  "amper-k", "amper-fr"])
def test_replay_state_roundtrips_bitwise(kind, tmp_path):
    rb = ReplayBuffer(CAP, make_sampler(kind, CAP, v_max=8.0, min_csp=64))
    st = _populated(rb)
    rck.save_replay(str(tmp_path), 7, st, meta={"sampler": kind})
    out = rck.restore_replay(str(tmp_path), 7, rb, EX)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the hidden exact-resume state, explicitly
    assert int(out.pos) == int(st.pos)
    assert int(out.total_adds) == int(st.total_adds)
    assert float(out.max_priority) == float(st.max_priority)
    np.testing.assert_array_equal(np.asarray(out.write_stamp),
                                  np.asarray(st.write_stamp))
    assert ck.load_meta(str(tmp_path), 7)["sampler"] == kind


def test_abstract_state_matches_init():
    for kind in ["uniform", "per-sumtree", "per-cumsum", "amper-fr"]:
        s = make_sampler(kind, 64, v_max=8.0)
        abs_leaves = jax.tree.leaves(abstract_state(s))
        for a, b in zip(abs_leaves, jax.tree.leaves(s.init())):
            assert tuple(np.shape(a)) == tuple(np.shape(b))


def test_wrong_sampler_restore_raises(tmp_path):
    rb = ReplayBuffer(CAP, make_sampler("per-sumtree", CAP))
    rck.save_replay(str(tmp_path), 1, _populated(rb))
    rb2 = ReplayBuffer(CAP, make_sampler("amper-fr", CAP, v_max=8.0))
    with pytest.raises(ValueError):
        rck.restore_replay(str(tmp_path), 1, rb2, EX)


# --- exact dirty sets / incremental saves ------------------------------------


@pytest.mark.parametrize("kind", ["uniform", "per-cumsum", "amper-fr"])
def test_replay_dirty_delta_roundtrips_bitwise(kind, tmp_path):
    """Delta saves driven by replay_marks/replay_dirty restore bitwise
    identical to a full dump — across a wrapping ring arc and
    out-of-band priority-feedback rows."""
    cap = 16
    rb = ReplayBuffer(cap, make_sampler(kind, cap, v_max=8.0, min_csp=4))
    st = rb.init(EX)
    k = jax.random.key(3)
    st = rb.add_batch(st, {"obs": jax.random.normal(k, (12, 4)),
                           "reward": jnp.arange(12, dtype=jnp.float32)})
    rck.save_replay(str(tmp_path), 1, st)  # legacy full base
    marks = rck.replay_marks(st)
    assert marks == {"pos": 12, "total_adds": 12, "add_gen": 0}
    # write 9 more rows: the arc wraps (12..16 then 0..5), and touch
    # priorities on rows the arc does NOT cover
    st = rb.add_batch(st, {"obs": jax.random.normal(jax.random.fold_in(k, 1),
                                                    (9, 4)),
                           "reward": jnp.ones(9)})
    idx = jnp.array([6, 7, 10], jnp.int32)
    st = rb.update_priorities(st, idx, jnp.array([0.5, 2.0, 1.5]))
    dirty = rck.replay_dirty(rb, st, marks, priority_rows=[6, 7, 10])
    ck.save_incremental(str(tmp_path), 2, st, base_step=1, dirty=dirty)
    out = rck.restore_replay(str(tmp_path), 2, rb, EX)
    for name, a, b in zip(ck._flatten_with_names(st)[0],
                          jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_replay_dirty_full_wrap_is_whole_ring():
    cap = 8
    rb = ReplayBuffer(cap, make_sampler("per-cumsum", cap))
    st = rb.init(EX)
    for _ in range(4):
        st = rb.add_batch(st, {"obs": jnp.zeros((5, 4)),
                               "reward": jnp.zeros(5)})
    marks = {"pos": 4, "total_adds": 4}  # 16 adds since marks > capacity
    dirty = rck.replay_dirty(rb, st, marks)
    spec = jax.tree.leaves(
        dirty.storage, is_leaf=lambda x: isinstance(x, ck.Rows))[0]
    assert spec.ranges == [(0, cap)]


def test_replay_dirty_wrap_safe_across_int32_rollover():
    """Marks captured just below the signed-int32 add-counter boundary
    plus a state whose counter crossed it must still yield the exact
    9-row wrapped arc — the plain signed difference would be negative
    (an empty dirty set) and the delta save would silently drop rows."""
    cap = 16
    rb = ReplayBuffer(cap, make_sampler("per-cumsum", cap))
    st = rb.init(EX)
    marks = {"pos": 12, "total_adds": (2**31 - 3) & 0xFFFFFFFF,
             "add_gen": 0}
    st = st._replace(pos=jnp.int32(5), size=jnp.int32(cap),
                     total_adds=jnp.int32(-(2**31) + 6),  # 2^31 + 6 unsigned
                     add_gen=jnp.int32(1))
    dirty = rck.replay_dirty(rb, st, marks)
    spec = jax.tree.leaves(
        dirty.storage, is_leaf=lambda x: isinstance(x, ck.Rows))[0]
    assert spec.ranges == [(12, cap), (0, 5)]


def test_replay_dirty_full_lap_detected_by_generation():
    """An identical (masked) add counter with a bumped generation means
    a full 2^32-add lap ran between snapshots: everything is dirty, not
    nothing."""
    cap = 16
    rb = ReplayBuffer(cap, make_sampler("per-cumsum", cap))
    st = rb.init(EX)
    marks = {"pos": 3, "total_adds": 77, "add_gen": 0}
    st = st._replace(pos=jnp.int32(3), size=jnp.int32(cap),
                     total_adds=jnp.int32(77), add_gen=jnp.int32(1))
    dirty = rck.replay_dirty(rb, st, marks)
    spec = jax.tree.leaves(
        dirty.storage, is_leaf=lambda x: isinstance(x, ck.Rows))[0]
    assert spec.ranges == [(0, cap)]


def test_replay_dirty_no_writes_skips_storage(tmp_path):
    """A save with nothing written since the marks stores no storage
    rows at all (the delta is scalars + any touched priority rows)."""
    cap = 16
    rb = ReplayBuffer(cap, make_sampler("uniform", cap))
    st = rb.init(EX)
    st = rb.add_batch(st, {"obs": jnp.zeros((4, 4)), "reward": jnp.zeros(4)})
    rck.save_replay(str(tmp_path), 1, st)
    dirty = rck.replay_dirty(rb, st, rck.replay_marks(st))
    ck.save_incremental(str(tmp_path), 2, st, base_step=1, dirty=dirty)
    man = ck.load_manifest(str(tmp_path), 2)
    obs_i = man["names"].index("storage/obs")
    assert man["delta"][obs_i] is None
    out = rck.restore_replay(str(tmp_path), 2, rb, EX)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- n-step accumulator state ------------------------------------------------


@pytest.mark.parametrize("kind", ["per-cumsum", "amper-fr"])
def test_nstep_replay_state_roundtrips_bitwise(kind, tmp_path):
    """The in-state NStepAccumulator (ring window, count, cursor) must
    round-trip bitwise — a resumed n-step run has to keep aggregating
    mid-window exactly where the killed one stopped."""
    n_envs = 4
    rb = ReplayBuffer(CAP, make_sampler(kind, CAP, v_max=8.0, min_csp=64),
                      n_step=3, gamma=0.97, num_envs=n_envs)
    ex = {"obs": jnp.zeros(4), "action": jnp.int32(0),
          "reward": jnp.float32(0), "next_obs": jnp.zeros(4),
          "done": jnp.float32(0)}
    st = rb.init(ex)
    k = jax.random.key(0)
    # 7 pushes: window warmed up AND mid-cycle (7 % 3 != 0), so the
    # cursor, saturated count, and ring contents are all non-trivial
    for i in range(7):
        st = rb.add_batch(st, {
            "obs": jax.random.normal(jax.random.fold_in(k, i), (n_envs, 4)),
            "action": jnp.full(n_envs, i % 2, jnp.int32),
            "reward": jnp.arange(n_envs, dtype=jnp.float32) + i,
            "next_obs": jax.random.normal(jax.random.fold_in(k, 50 + i),
                                          (n_envs, 4)),
            "done": jnp.where(jnp.arange(n_envs) == i % n_envs, 1.0, 0.0)})
    assert int(st.nstep.count) == 3 and int(st.nstep.pos) == 7 % 3
    rck.save_replay(str(tmp_path), 4, st, meta={"sampler": kind})
    out = rck.restore_replay(str(tmp_path), 4, rb, ex)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(out.nstep.count) == int(st.nstep.count)
    assert int(out.nstep.pos) == int(st.nstep.pos)
    # the restored accumulator keeps emitting the same stream
    nxt = {"obs": jnp.ones((n_envs, 4)), "action": jnp.zeros(n_envs, jnp.int32),
           "reward": jnp.ones(n_envs), "next_obs": jnp.ones((n_envs, 4)),
           "done": jnp.zeros(n_envs)}
    a_after = rb.add_batch(st, nxt)
    b_after = rb.add_batch(out, nxt)
    for a, b in zip(jax.tree.leaves(a_after), jax.tree.leaves(b_after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nstep_restore_into_wrong_horizon_raises(tmp_path):
    """A checkpoint written with n_step=3 must not silently load into an
    n_step=1 buffer (the manifest's leaf names differ)."""
    rb3 = ReplayBuffer(CAP, make_sampler("per-cumsum", CAP), n_step=3,
                       num_envs=2)
    ex = {"obs": jnp.zeros(4), "action": jnp.int32(0),
          "reward": jnp.float32(0), "next_obs": jnp.zeros(4),
          "done": jnp.float32(0)}
    st = rb3.init(ex)
    for i in range(4):
        st = rb3.add_batch(st, jax.tree.map(
            lambda x: jnp.ones((2,) + jnp.shape(x), jnp.asarray(x).dtype),
            ex))
    rck.save_replay(str(tmp_path), 1, st)
    rb1 = ReplayBuffer(CAP, make_sampler("per-cumsum", CAP))
    with pytest.raises(ValueError):
        rck.restore_replay(str(tmp_path), 1, rb1, ex)


# --- elastic sharded restore -------------------------------------------------


def _sharded_rb(n_shards):
    mesh = make_mesh((n_shards,), ("data",))
    s = make_sampler("amper-fr-sharded", CAP, mesh=mesh,
                     axis_names=("data",), v_max=8.0)
    return ReplayBuffer(CAP, s)


@pytest.mark.parametrize("to_shards", [2, 1])
def test_sharded_restore_onto_fewer_shards(tmp_path, to_shards):
    """Acceptance pin: a sampler saved on 8 shards restores onto 2 (and
    1) with membership-exact priorities and keeps training."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    rb8 = _sharded_rb(8)
    st8 = _populated(rb8)
    rck.save_replay(str(tmp_path), 3, st8)

    rb = _sharded_rb(to_shards)
    st = rck.restore_replay(str(tmp_path), 3, rb, EX)
    np.testing.assert_array_equal(
        np.asarray(rb8.sampler.priorities(st8.sampler_state)),
        np.asarray(rb.sampler.priorities(st.sampler_state)))
    # CSP membership for the same key is identical across shard counts
    m8 = np.asarray(rb8.sampler.membership(st8.sampler_state,
                                           jax.random.key(42)))
    m = np.asarray(rb.sampler.membership(st.sampler_state,
                                         jax.random.key(42)))
    np.testing.assert_array_equal(m8, m)
    # the restored table really is partitioned over the target mesh
    assert (st.sampler_state.pq.sharding.num_devices_indexed_by_this_sharding
            if hasattr(st.sampler_state.pq.sharding, "num_devices_indexed_by_this_sharding")
            else len(st.sampler_state.pq.sharding.device_set)) == to_shards
    # ...and keeps training: a full add/sample/update cycle runs
    st = rb.add_batch(st, {"obs": jnp.ones((32, 4)),
                           "reward": jnp.zeros(32)})
    idx, _, w = rb.sample(st, jax.random.key(9), 16)
    st = rb.update_priorities(st, idx, jnp.ones(16))
    assert np.isfinite(np.asarray(w)).all()


def test_sharded_to_single_device_restore(tmp_path):
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    rb8 = _sharded_rb(8)
    st8 = _populated(rb8)
    rck.save_replay(str(tmp_path), 1, st8)
    rb1 = ReplayBuffer(CAP, make_sampler("amper-fr", CAP, v_max=8.0))
    st1 = rck.restore_replay(str(tmp_path), 1, rb1, EX)
    np.testing.assert_array_equal(
        np.asarray(rb8.sampler.priorities(st8.sampler_state)),
        np.asarray(rb1.sampler.priorities(st1.sampler_state)))


def test_repartition_moves_state_onto_mesh():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    rb2 = _sharded_rb(2)
    # state built dense on one device, repartitioned onto the 2-mesh
    dense = make_sampler("amper-fr", CAP, v_max=8.0).init()
    moved = sharded_mod.repartition(rb2.sampler, dense)
    assert len(moved.pq.sharding.device_set) == 2
    np.testing.assert_array_equal(np.asarray(dense.pq), np.asarray(moved.pq))
