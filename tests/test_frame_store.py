"""Frame-deduplicated pixel replay: the uint8 frame store must be an
*exact* drop-in for a naive float buffer.

The core pin is bit-exact materialization: a numpy reference replays the
full add stream (per-env episodes, ring wraparound, warm-up) and builds
the stacked float obs / sample-time n-step return every anchor *should*
produce; ``materialize`` must match it bitwise — including the zero
padding at episode starts, the masking of chains cut by the write head,
and the exact f32 ``frame * scale`` conversion the actor uses.

Plus the pixel-mode system guarantees: uint8 storage round-trips through
the replay checkpoint (incl. elastic 8->2->1 shard restore), every
``fr_mode`` draws bit-identical materialized batches on 1/2/8-shard
meshes, and a killed + resumed sync pixel run equals an uninterrupted
one bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amper import FR_MODES
from repro.core.replay_buffer import FrameStore, ReplayBuffer
from repro.core.samplers import make_sampler
from repro.launch.mesh import make_mesh
from repro.rl.dqn import DQNConfig, make_dqn
from repro.runtime import ReplayService
from repro.train import replay_checkpoint as rck
from repro.train.checkpoint import CheckpointManager

HW = (5, 5)


# --- numpy stream reference ---------------------------------------------------


def _gen_stream(seed, n_envs, n_steps, p_done=0.15):
    """Per-env episode streams flattened to global add order: the row
    written at global counter ``t`` is env ``t % n_envs`` at vectorized
    step ``t // n_envs`` (exactly how ``add_batch`` lays out lockstep
    arcs)."""
    rng = np.random.default_rng(seed)
    T = n_envs * n_steps
    return {
        "frame": rng.integers(0, 256, size=(T,) + HW, dtype=np.uint8),
        "action": rng.integers(0, 3, size=T).astype(np.int32),
        "reward": rng.standard_normal(T).astype(np.float32),
        "done": (rng.random(T) < p_done).astype(np.float32),
    }


def _fill(rb, hist, n_envs):
    st = rb.init({"frame": jnp.zeros(HW, jnp.uint8),
                  "action": jnp.int32(0), "reward": jnp.float32(0),
                  "done": jnp.float32(0)})
    T = len(hist["frame"])
    for v in range(T // n_envs):
        rows = slice(v * n_envs, (v + 1) * n_envs)
        st = rb.add_batch(st, {k: jnp.asarray(hist[k][rows])
                               for k in hist})
    return st


def _ref_materialize(hist, cap, fs):
    """Replay the stream on host and build what every anchor slot must
    materialize to.  Mirrors the device arithmetic operation-for-
    operation (f32 accumulation order included) so the comparison can be
    bitwise."""
    T = len(hist["frame"])
    size = min(T, cap)
    K, S, N = fs.history_len, fs.stride, fs.n_step
    scale = np.float32(fs.scale)

    def latest(slot):          # newest stream time resident in `slot`
        return slot + ((T - 1 - slot) // cap) * cap

    def live(t):               # stream time t still in the ring
        return t >= 0 and t >= T - cap

    def stack(slot, base_ok):
        ta = latest(slot) if slot < size else -1
        frames, ok = [], base_ok
        for j in range(K):
            t = ta - j * S
            if j > 0:
                ok = ok and live(t) and hist["done"][t] < 0.5
            f = (hist["frame"][t].astype(np.float32) * scale if ok
                 else np.zeros(HW, np.float32))
            frames.append(f)
        return np.stack(frames[::-1], axis=-1)

    out = {k: [] for k in ("obs", "action", "reward", "next_obs", "done")}
    for slot in range(cap):
        written = slot < size
        ta = latest(slot) if written else -1
        out["obs"].append(stack(slot, written))
        out["action"].append(hist["action"][ta] if written else
                             np.asarray(hist["action"][0]) * 0)
        enter, reward = np.float32(written), np.float32(0.0)
        for k in range(N):
            t = ta + k * S
            avail = written and t < T
            use = enter * np.float32(avail)
            if avail:
                reward = reward + (use * np.float32(float(fs.gamma ** k))
                                   ) * hist["reward"][t]
                enter = use * (np.float32(1.0) - hist["done"][t])
            else:
                enter = np.float32(0.0)
        tb = ta + N * S
        has_boot = bool(enter > 0.5) and tb < T
        out["reward"].append(reward)
        out["next_obs"].append(stack(tb % cap, has_boot) if has_boot
                               else np.zeros(HW + (K,), np.float32))
        out["done"].append(np.float32(not has_boot))
    return {k: np.stack(v) for k, v in out.items()}


@pytest.mark.parametrize("cap,K,n_envs,N,steps", [
    (32, 4, 1, 1, 50),      # single stream, ring wrapped once
    (32, 4, 1, 1, 10),      # warm-up: most of the ring unwritten
    (48, 3, 1, 3, 70),      # sample-time n-step across the wrap
    (40, 4, 2, 2, 18),      # two interleaved env streams (stride=2)
    (64, 2, 4, 1, 40),      # wider stride, short stacks, two laps
])
def test_materialize_bit_exact_vs_stream_reference(cap, K, n_envs, N, steps):
    fs = FrameStore(history_len=K, frame_shape=HW, stride=n_envs,
                    n_step=N, gamma=0.9)
    rb = ReplayBuffer(cap, make_sampler("uniform", cap), frame_store=fs)
    hist = _gen_stream(7 * cap + K, n_envs, steps)
    st = _fill(rb, hist, n_envs)
    got = rb.materialize(st, jnp.arange(cap))
    ref = _ref_materialize(hist, cap, fs)
    np.testing.assert_array_equal(np.asarray(got["obs"]), ref["obs"])
    np.testing.assert_array_equal(np.asarray(got["next_obs"]),
                                  ref["next_obs"])
    np.testing.assert_array_equal(np.asarray(got["reward"]), ref["reward"])
    np.testing.assert_array_equal(np.asarray(got["done"]), ref["done"])
    np.testing.assert_array_equal(np.asarray(got["terminated"]),
                                  ref["done"])
    written = np.arange(cap) < int(st.size)
    np.testing.assert_array_equal(np.asarray(got["action"])[written],
                                  ref["action"][written])


def test_episode_boundary_zero_pads_like_naive_buffer():
    """A stack whose backward chain crosses a ``done`` row zeroes every
    older frame — byte-for-byte the padding a naive float buffer records
    at an episode start."""
    fs = FrameStore(history_len=4, frame_shape=HW)
    rb = ReplayBuffer(32, make_sampler("uniform", 32), frame_store=fs)
    hist = _gen_stream(3, 1, 12, p_done=0.0)
    hist["done"][5] = 1.0                      # one episode cut at t=5
    st = _fill(rb, hist, 1)
    got = np.asarray(rb.materialize(st, jnp.arange(32))["obs"])
    # anchor t=7: chain 7,6 valid; 5 is done -> frames 5,4 masked
    scale = np.float32(1.0 / 255.0)
    expect = np.stack([np.zeros(HW, np.float32),
                       np.zeros(HW, np.float32),
                       hist["frame"][6].astype(np.float32) * scale,
                       hist["frame"][7].astype(np.float32) * scale],
                      axis=-1)
    np.testing.assert_array_equal(got[7], expect)
    # anchor t=5 (the done row itself) keeps its full history
    assert (got[5] != 0).any(axis=(0, 1)).all()


def test_frame_store_config_validation():
    with pytest.raises(ValueError, match="n_step=1"):
        ReplayBuffer(64, make_sampler("uniform", 64), n_step=3,
                     frame_store=FrameStore(4, HW))
    with pytest.raises(ValueError, match="capacity"):
        ReplayBuffer(16, make_sampler("uniform", 16),
                     frame_store=FrameStore(8, HW, stride=2))
    rb = ReplayBuffer(64, make_sampler("uniform", 64),
                      frame_store=FrameStore(4, HW))
    with pytest.raises(ValueError, match="frame"):
        rb.init({"obs": jnp.zeros(4), "reward": jnp.float32(0)})
    with pytest.raises(ValueError, match="uint8"):
        rb.init({"frame": jnp.zeros(HW, jnp.float32),
                 "action": jnp.int32(0), "reward": jnp.float32(0),
                 "done": jnp.float32(0)})


# --- uint8 checkpoint round-trip / elastic restore ---------------------------


def _pixel_rb(sampler):
    return ReplayBuffer(256, sampler,
                        frame_store=FrameStore(history_len=4,
                                               frame_shape=HW, n_step=2))


PIX_EX = {"frame": jnp.zeros(HW, jnp.uint8), "action": jnp.int32(0),
          "reward": jnp.float32(0), "done": jnp.float32(0)}


def test_uint8_replay_checkpoint_roundtrips_bitwise(tmp_path):
    rb = _pixel_rb(make_sampler("amper-fr", 256, v_max=8.0))
    hist = _gen_stream(11, 1, 300)
    st = _fill(rb, hist, 1)
    idx, _, _ = rb.sample(st, jax.random.key(0), 32)
    st = rb.update_priorities(st, idx, jnp.ones(32))
    rck.save_replay(str(tmp_path), 5, st)
    out = rck.restore_replay(str(tmp_path), 5, rb, PIX_EX)
    assert out.storage["frame"].dtype == jnp.uint8
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored buffer materializes the identical float batch
    np.testing.assert_array_equal(
        np.asarray(rb.materialize(st, jnp.arange(256))["obs"]),
        np.asarray(rb.materialize(out, jnp.arange(256))["obs"]))


@pytest.mark.parametrize("to_shards", [2, 1])
def test_uint8_elastic_restore_onto_fewer_shards(tmp_path, to_shards):
    """A pixel buffer saved on 8 shards restores onto 2 (and 1) with the
    uint8 frames, stamps, and priorities all bitwise intact — and the
    restored buffer samples identical materialized batches."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")

    def sharded(n):
        mesh = make_mesh((n,), ("data",))
        return _pixel_rb(make_sampler("amper-fr-sharded", 256, mesh=mesh,
                                      axis_names=("data",), v_max=8.0))

    rb8 = sharded(8)
    hist = _gen_stream(13, 1, 300)
    st8 = _fill(rb8, hist, 1)
    rck.save_replay(str(tmp_path), 2, st8)
    rb = sharded(to_shards)
    st = rck.restore_replay(str(tmp_path), 2, rb, PIX_EX)
    assert st.storage["frame"].dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(st8.storage["frame"]),
                                  np.asarray(st.storage["frame"]))
    np.testing.assert_array_equal(
        np.asarray(rb8.sampler.priorities(st8.sampler_state)),
        np.asarray(rb.sampler.priorities(st.sampler_state)))
    # membership is shard-count invariant; the drawn offsets are not —
    # so compare the materialized float batch at the SAME anchors.
    np.testing.assert_array_equal(
        np.asarray(rb8.sampler.membership(st8.sampler_state,
                                          jax.random.key(42))),
        np.asarray(rb.sampler.membership(st.sampler_state,
                                         jax.random.key(42))))
    anchors = jnp.arange(256)
    for k in ("obs", "next_obs", "reward", "done"):
        np.testing.assert_array_equal(
            np.asarray(rb8.materialize(st8, anchors)[k]),
            np.asarray(rb.materialize(st, anchors)[k]), err_msg=k)
    # ...and the restored buffer keeps training: full pixel cycle runs
    idx, batch, w = rb.sample(st, jax.random.key(4), 64)
    st = rb.update_priorities(st, idx, jnp.ones(64))
    assert np.isfinite(np.asarray(batch["obs"])).all()
    assert np.isfinite(np.asarray(w)).all()


# --- fr_mode x shard-count bit-identity on the pixel path --------------------


def _pixel_rb_cap(cap, sampler):
    return ReplayBuffer(cap, sampler,
                        frame_store=FrameStore(history_len=4,
                                               frame_shape=HW, n_step=2))


def test_pixel_fr_modes_bit_identical_dense():
    """Acceptance: on the dense single-device sampler, every fr_mode
    (incl. the fused Pallas dispatch) draws bit-identical indices, IS
    weights, AND materialized pixel batches."""
    cap = 512
    hist = _gen_stream(17, 1, 600)
    out = {}
    for mode in FR_MODES:
        rb = _pixel_rb_cap(cap, make_sampler("amper-fr", cap, v_max=8.0,
                                             fr_mode=mode))
        st = _fill(rb, hist, 1)
        idx, batch, w = rb.sample(st, jax.random.key(21), 64)
        out[mode] = (np.asarray(idx), np.asarray(batch["obs"]),
                     np.asarray(batch["reward"]), np.asarray(w))
    base = out.pop("broadcast")
    for mode, got in out.items():
        for a, b in zip(base, got):
            np.testing.assert_array_equal(a, b, err_msg=mode)


@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_pixel_fused_bit_identical_per_mesh(n_shards):
    """Acceptance: fused == broadcast (indices, weights, materialized
    pixel batch) through the frame-store buffer on 1/2/8-shard meshes."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    cap = 512
    hist = _gen_stream(19, 1, 600)
    out = {}
    for mode in ("broadcast", "fused"):
        s = make_sampler("amper-fr-sharded", cap, v_max=8.0, fr_mode=mode,
                         mesh=make_mesh((n_shards,), ("data",)))
        rb = _pixel_rb_cap(cap, s)
        st = _fill(rb, hist, 1)
        idx, batch, w = rb.sample(st, jax.random.key(23), 64)
        out[mode] = (np.asarray(idx), np.asarray(batch["obs"]),
                     np.asarray(batch["reward"]), np.asarray(w))
    for a, b in zip(out["broadcast"], out["fused"]):
        np.testing.assert_array_equal(a, b)


# --- sync kill/resume on a pixel env -----------------------------------------


PIX_CFG = DQNConfig(env="breakout", sampler="amper-fr", num_envs=2,
                    replay_size=256, batch=16, learn_start=30,
                    history_len=4, eps_decay_steps=200, target_sync=25)


def test_pixel_sync_kill_resume_bit_identical(tmp_path):
    """Acceptance pin: a checkpointed + killed + resumed pixel run (conv
    head, frame-store replay, amper-fr) equals the uninterrupted run
    bitwise — params and the full uint8 replay state."""
    n = 70
    key = jax.random.key(6)
    svc = ReplayService(PIX_CFG, sync=True, num_actors=1)
    baseline = svc.run(key, n)
    mgr = CheckpointManager(str(tmp_path), save_interval=20)
    mgr.request_preemption()
    r1 = svc.run(key, n, manager=mgr)
    assert r1.metrics["preempted_at"] is not None
    r2 = svc.run(key, n, manager=CheckpointManager(str(tmp_path),
                                                   save_interval=20))
    for a, b in zip(jax.tree.leaves(baseline.params),
                    jax.tree.leaves(r2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(baseline.buffer),
                    jax.tree.leaves(r2.buffer)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert baseline.buffer.storage["frame"].dtype == jnp.uint8


def test_frame_store_service_requires_single_actor():
    with pytest.raises(ValueError, match="num_actors"):
        ReplayService(PIX_CFG, sync=False, num_actors=2)
