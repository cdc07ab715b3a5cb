"""Chip smoke test: drive the AMPER replay path once on a TPU.

Run from the repository root, one process per chip host:

    python chip_smoke.py              # one chip: kernel + trainer phases
    python chip_smoke.py --chips 4    # four-chip host: sharded phase only

Phases (each prints one JSON line with its numbers):

* ``kernel``  — an AMPER-fr table of 1,000,000 rows with seeded
  priorities (about a tenth of the rows invalid), m = 20, CSP ratio 0.15,
  batch 32.  ``ReplayBuffer.sample`` through the fused Pallas draw and
  through the broadcast reference must return bitwise-equal indices and
  importance weights over several keys, every drawn row must be a CSP
  member of the reference ``build_csp_fr``, and the compiled fused draw
  must contain the Mosaic kernel (``tpu_custom_call``).
* ``trainer`` — ``DQNConfig(env="breakout", replay_size=1_000_000,
  batch=32, history_len=4, alpha=0.6, beta=0.4, num_envs=16)`` through
  the async ``ReplayService`` as ``examples/async_dqn.py`` drives it, once
  with ``amper-fr`` and once with the ``per-sumtree`` baseline: losses
  finite, priority feedback applied, the ring filled as far as the
  frames say.
* ``sharded`` (``--chips 4``) — ``amper-fr-sharded`` at 2,000,000 rows
  over a 4-device mesh with the Ape-X batch of 512: the table really
  spans the mesh, CSP membership equals single-device ``build_csp_fr``
  bitwise, fused equals broadcast, and a few DQN learner steps run on
  the sharded sampler.

Any failed check raises, so the script exits non-zero and never prints
the final line.  Without a TPU it exits non-zero before any phase.  The
last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def _log(phase: str, **numbers) -> None:
    print(json.dumps({"phase": phase, **numbers}), flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def _seeded_priorities(n: int, seed: int):
    """float32[n] priorities in (0, 1], about a tenth of the rows 0
    (invalid: a zero priority marks a row the samplers must skip)."""
    import jax
    import jax.numpy as jnp

    k_p, k_v = jax.random.split(jax.random.key(seed))
    prio = jax.random.uniform(k_p, (n,), minval=0.01, maxval=1.0)
    return jnp.where(jax.random.uniform(k_v, (n,)) > 0.1, prio, 0.0)


def _filled_buffer(sampler, n: int, prio):
    """A ReplayBuffer over ``sampler`` whose n rows all hold ``prio``."""
    import jax.numpy as jnp

    from repro.core.replay_buffer import ReplayBuffer

    rb = ReplayBuffer(n, sampler)
    st = rb.init({"row": jnp.int32(0)})
    st = st._replace(
        storage={"row": jnp.arange(n, dtype=jnp.int32)},
        sampler_state=sampler.update(st.sampler_state,
                                     jnp.arange(n, dtype=jnp.int32), prio),
        size=jnp.int32(n))
    return rb, st


def _draws(rb, st, keys, batch: int):
    """(idx, weights) host arrays of ``rb.sample`` per key, plus the
    compiled program's text."""
    import jax
    import numpy as np

    sample = jax.jit(lambda s, k: rb.sample(s, k, batch))
    text = sample.lower(st, keys[0]).compile().as_text()
    out = []
    for k in keys:
        idx, rows, w = sample(st, k)
        _check(np.array_equal(np.asarray(rows["row"]), np.asarray(idx)),
               "sampled storage rows are the sampled indices")
        out.append((np.asarray(idx), np.asarray(w)))
    return out, text


def _compare(fused, broadcast) -> tuple[int, int]:
    """Count of (index, weight) entries that differ bitwise."""
    import numpy as np

    d_idx = sum(int(np.sum(a[0] != b[0])) for a, b in zip(fused, broadcast))
    d_w = sum(int(np.sum(a[1].view(np.uint32) != b[1].view(np.uint32)))
              for a, b in zip(fused, broadcast))
    return d_idx, d_w


def kernel_phase(n: int = 1_000_000, n_keys: int = 4, batch: int = 32,
                 *, seed: int = 0) -> dict:
    """Fused vs broadcast AMPER-fr draws through ``ReplayBuffer.sample``.
    Off a TPU the kernel runs in interpret mode, so the compiled-kernel
    check applies on the chip only."""
    import jax
    import numpy as np

    from repro.core.amper import build_csp_fr
    from repro.core.samplers import make_sampler

    prio = _seeded_priorities(n, seed)
    keys = list(jax.random.split(jax.random.key(seed + 1), n_keys))
    draws, texts = {}, {}
    for mode in ("fused", "broadcast"):
        s = make_sampler("amper-fr", n, m=20, csp_ratio=0.15, v_max=1.0,
                         min_csp=batch, fr_mode=mode)
        rb, st = _filled_buffer(s, n, prio)
        draws[mode], texts[mode] = _draws(rb, st, keys, batch)
    kernel = "tpu_custom_call" in texts["fused"]
    if jax.devices()[0].platform == "tpu":
        _check(kernel, "compiled fused draw contains tpu_custom_call")
    d_idx, d_w = _compare(draws["fused"], draws["broadcast"])
    _check(d_idx == 0 and d_w == 0, "fused == broadcast bitwise")
    # Every draw is a member of the reference CSP built from the same
    # key split the sampler makes (kcsp, kpick).
    matches, counts, non_members = [], [], 0
    for k, (idx, _w) in zip(keys, draws["broadcast"]):
        kcsp, _ = jax.random.split(k)
        csp = build_csp_fr(st.sampler_state.pq, st.sampler_state.valid,
                           kcsp, s.cfg)
        selected = np.asarray(csp.selected)
        matches.append(int(selected.sum()))
        counts.append(int(csp.count))  # truncated at the CSP capacity
        non_members += int(np.sum(~selected[idx]))
    _check(min(counts) > 0, "every CSP is non-empty")
    _check(non_members == 0, "every draw is a CSP member")
    live = int(np.sum(np.asarray(prio) > 0))
    out = {"n": n, "live_rows": live, "batch": batch, "keys": n_keys,
           "csp_matches": matches, "csp_count": counts,
           "idx_mismatch": d_idx,
           "weight_mismatch": d_w, "non_member_draws": non_members,
           "tpu_custom_call": kernel}
    _log("kernel", **out)
    return out


def trainer_phase(sampler: str, replay_size: int = 1_000_000,
                  learner_steps: int = 320, *, batch: int = 32,
                  num_envs: int = 16, slab: int = 8, chunk: int = 32,
                  seed: int = 0) -> dict:
    """DQN on breakout through the async ReplayService, as
    ``examples/async_dqn.py`` drives it."""
    import jax
    import numpy as np

    from repro.rl.dqn import DQNConfig
    from repro.runtime import ReplayService

    replay_ratio = 4  # frames per learner step, in units of num_envs
    cfg = DQNConfig(env="breakout", sampler=sampler,
                    replay_size=replay_size, batch=batch, history_len=4,
                    alpha=0.6, beta=0.4, num_envs=num_envs, learn_start=50,
                    eps_decay_steps=max(learner_steps // 2, 1) * replay_ratio,
                    target_sync=100, v_max=8.0)
    svc = ReplayService(cfg, num_actors=1, chunk_len=chunk, slab=slab,
                        max_replay_ratio=replay_ratio * num_envs)
    key = jax.random.key(seed)
    svc.run(key, 2 * slab)  # compile warm-up
    res = svc.run(key, learner_steps)
    m = res.metrics
    losses = np.asarray(m["losses"], np.float32)
    fb_rows = m["feedback_applied"] * slab * cfg.batch
    size = int(res.buffer.size)
    _check(m["learner_steps"] >= learner_steps, "learner steps taken")
    _check(losses.size > 0 and bool(np.all(np.isfinite(losses))),
           "losses finite")
    _check(fb_rows > 0, "priority feedback applied")
    _check(size == min(m["frames"], replay_size), "ring holds the frames")
    stats = jax.devices()[0].memory_stats() or {}
    out = {"sampler": sampler, "replay_size": replay_size,
           "batch": cfg.batch, "learner_steps": m["learner_steps"],
           "frames": m["frames"], "buffer_size": size,
           "loss_last": float(losses[-1]), "loss_max": float(losses.max()),
           "feedback_rows": fb_rows,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    _log("trainer", **out)
    return out


def sharded_phase(n: int = 2_000_000, batch: int = 512, shards: int = 4,
                  n_keys: int = 2, learner_steps: int = 16,
                  *, slab: int = 8, seed: int = 0) -> dict:
    """amper-fr-sharded over ``shards`` devices against one device."""
    import jax
    import numpy as np

    from repro.core.amper import build_csp_fr
    from repro.core.samplers import make_sampler
    from repro.launch.mesh import make_replay_mesh

    mesh = make_replay_mesh(shards)
    prio = _seeded_priorities(n, seed)
    keys = list(jax.random.split(jax.random.key(seed + 1), n_keys))
    one = jax.devices()[0]
    draws, member_mismatch = {}, 0
    for mode in ("fused", "broadcast"):
        s = make_sampler("amper-fr-sharded", n, m=20, csp_ratio=0.15,
                         v_max=1.0, min_csp=batch, fr_mode=mode, mesh=mesh)
        rb, st = _filled_buffer(s, n, prio)
        table = st.sampler_state
        _check(len(table.pq.sharding.device_set) == shards,
               f"priority table spans {shards} devices")
        draws[mode], _ = _draws(rb, st, keys, batch)
        pq1, valid1 = jax.device_put((table.pq, table.valid), one)
        for k in keys:
            got = np.asarray(s.membership(table, k))
            ref = np.asarray(build_csp_fr(
                pq1, valid1, k, s.cfg._replace(fr_mode="broadcast")).selected)
            member_mismatch += int(np.sum(got != ref))
    _check(member_mismatch == 0, "sharded membership == single device")
    d_idx, d_w = _compare(draws["fused"], draws["broadcast"])
    _check(d_idx == 0 and d_w == 0, "sharded fused == broadcast bitwise")
    out = {"n": n, "shards": shards, "batch": batch, "keys": n_keys,
           "membership_mismatch": member_mismatch, "idx_mismatch": d_idx,
           "weight_mismatch": d_w}
    _log("sharded", **out)
    train = trainer_phase("amper-fr-sharded", n, learner_steps, batch=batch,
                          slab=slab, seed=seed)
    out["trainer"] = train
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase on a 4-chip host")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.chips == 4:
        sharded_phase(shards=4)
    else:
        kernel_phase()
        for sampler in ("amper-fr", "per-sumtree"):
            trainer_phase(sampler)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
