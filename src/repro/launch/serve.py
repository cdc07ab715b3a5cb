"""Serving launcher: batched prefill + decode with KV caches.

Drives any arch (reduced scale on CPU) through the real serving path:
prefill a batch of prompts, then decode N tokens greedily, reporting
per-token latency.  The full-scale decode path is exercised shape-only
by the dry-run (decode_32k / long_500k cells).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --reduced \
      --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_api import Model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = Model.from_config(cfg)
    params = model.init_params(jax.random.key(args.seed))
    max_len = args.prompt_len + args.gen + 1

    k_tok, k_aud, k_vis = jax.random.split(jax.random.key(args.seed + 1), 3)
    B = args.batch
    prompts = jax.random.randint(k_tok, (B, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    batch = {"tokens": prompts}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            k_aud, (B, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.vis_prefix_len:
        batch["patch_embeds"] = jax.random.normal(
            k_vis, (B, cfg.vis_prefix_len, cfg.d_model), jnp.float32)
        max_len += cfg.vis_prefix_len

    from repro.serving import Engine
    engine = Engine(model, params)

    t0 = time.perf_counter()
    logits, cache = engine.prefill(batch, max_len)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"prefill: batch={B} prompt={args.prompt_len} "
          f"{t_prefill * 1e3:.1f} ms")

    t0 = time.perf_counter()
    res = engine.generate(batch, args.gen)
    jax.block_until_ready(res.tokens)
    dt = (time.perf_counter() - t0) / args.gen
    print(f"decode: {args.gen} tokens, {dt * 1e3:.2f} ms/token "
          f"({B / dt:.1f} tok/s aggregate)")
    print("sample:", res.tokens[0, :16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
