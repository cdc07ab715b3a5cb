"""Training launcher: AMPER-prioritized LM training with fault tolerance.

Runs any ``--arch`` (full or ``--reduced`` smoke scale) with the
prioritized sequence-replay data pipeline (``--sampler uniform | per |
amper-fr | amper-k``), periodic atomic checkpoints, auto-resume from the
latest checkpoint, and a SIGTERM preemption hook — kill the process mid
-run and relaunching continues bitwise-identically (step-seeded
sampling).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --reduced --steps 200 --batch 8 --seq-len 128 --sampler amper-fr \
      --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.model_api import Model
from repro.train import checkpoint as ckpt_mod
from repro.train import data as data_mod
from repro.train import train_step as ts_mod
from repro.train.optimizer import AdamW, cosine_schedule


def per_sequence_loss(model, params, batch):
    """Per-sequence mean NLL — the replay priorities (LM 'TD errors')."""
    from repro.models import transformer
    cfg = model.cfg
    logits, _ = transformer.forward(cfg, params, batch["tokens"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None], -1)[..., 0]
    m = batch["loss_mask"]
    return (nll * m).sum(-1) / jnp.maximum(m.sum(-1), 1.0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--n-seqs", type=int, default=2048)
    ap.add_argument("--sampler", default="amper-fr",
                    choices=["uniform", "per", "amper-fr", "amper-k"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = Model.from_config(cfg)
    opt = AdamW(cosine_schedule(args.lr, 20, args.steps))
    step_fn = jax.jit(ts_mod.make_train_step(
        model, opt, microbatches=args.microbatches), donate_argnums=0)
    loss_by_seq = jax.jit(lambda p, b: per_sequence_loss(model, p, b))

    tokens = data_mod.corpus_tokens(args.n_seqs, args.seq_len + 1,
                                    cfg.vocab_size, seed=args.seed)
    data = data_mod.PrioritizedSeqData(tokens, args.batch,
                                       sampler=args.sampler)
    data_state = data.init()
    state = ts_mod.init_train_state(model, opt, jax.random.key(args.seed))

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = ckpt_mod.CheckpointManager(args.ckpt_dir, keep=3,
                                         save_interval=args.ckpt_every)
        mgr.install_preemption_hook()
        latest = mgr.restore_latest((state, data_state))
        if latest[0] is not None:
            start_step, (state, data_state) = latest
            print(f"resumed from step {start_step}")

    t0 = time.perf_counter()
    for step in range(start_step, args.steps):
        key = jax.random.fold_in(jax.random.key(args.seed), step)
        idx, batch = data.sample(data_state, key)
        state, metrics = step_fn(state, batch)
        seq_loss = loss_by_seq(state.params, batch)
        data_state = data.update(data_state, idx, seq_loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({(time.perf_counter() - t0):.1f}s)", flush=True)
        if mgr and mgr.should_save(step + 1):
            mgr.save(step + 1, (state, data_state))
            if mgr.preempted:
                print(f"preempted: checkpointed at step {step + 1}, exiting")
                return 0
    if mgr:
        mgr.save(args.steps, (state, data_state))
    print(f"done: {args.steps} steps, final loss "
          f"{float(metrics['loss']):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
