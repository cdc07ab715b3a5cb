"""Jit'd public wrappers for the Pallas kernels.

Handles padding to TPU tile boundaries, dtype plumbing, and the
interpret-mode switch (kernels execute in Python on CPU backends so the
whole suite validates without TPU silicon; on TPU backends they lower to
Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import amper_sample as _as
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import tcam_match as _tm
from repro.kernels.common import (LANES, auto_block_rows as _auto_block_rows,
                                  force_interpret,
                                  interpret_default as _interpret_default,
                                  pad_table as _pad_table)

__all__ = ["LANES", "force_interpret", "tcam_match", "multi_query_match",
           "amper_sample", "rank_select", "flash_attention",
           "decode_attention"]


def _jit_kernel(fn, *, static=()):
    """``jax.jit`` with ``interpret=None`` resolved OUTSIDE the trace cache.

    The interpret default depends on ambient state (backend +
    :func:`force_interpret` override), so it must be folded into the jit
    cache key as the actual bool.  Resolving it inside the jitted body
    would let the first call under ``force_interpret`` poison the cached
    entry for ``interpret=None`` with the wrong lowering.
    """
    jitted = jax.jit(fn, static_argnames=tuple(static) + ("interpret",))

    @functools.wraps(fn)
    def wrapper(*args, interpret=None, **kwargs):
        if interpret is None:
            interpret = _interpret_default()
        return jitted(*args, interpret=interpret, **kwargs)

    return wrapper


@functools.partial(_jit_kernel, static=("block_rows",))
def tcam_match(pq: jax.Array, query: jax.Array, mask: jax.Array, *,
               block_rows: int | None = None,
               interpret: bool = False) -> jax.Array:
    """Single ternary-CAM query over a flat int32[n] table -> bool[n]."""
    block_rows = _auto_block_rows(pq.shape[0]) if block_rows is None else block_rows
    pq2, _, n = _pad_table(pq, jnp.ones_like(pq, jnp.bool_), block_rows)
    out = _tm.tcam_match(pq2, jnp.asarray(query, jnp.int32),
                         jnp.asarray(mask, jnp.int32),
                         block_rows=block_rows, interpret=interpret)
    return out.reshape(-1)[:n]


@functools.partial(_jit_kernel, static=("block_rows",))
def multi_query_match(pq: jax.Array, valid: jax.Array, lo: jax.Array,
                      hi: jax.Array, *,
                      block_rows: int | None = None,
                      interpret: bool = False):
    """Fused m-range AMPER search over a flat table.

    Returns (sel bool[n], counts int32[m]).  Padding rows carry pq = -1
    (matches no non-negative range) and valid = False.
    """
    block_rows = _auto_block_rows(pq.shape[0]) if block_rows is None else block_rows
    pq2, valid2, n = _pad_table(pq, valid, block_rows)
    sel, counts = _tm.multi_query_match(
        pq2, valid2, lo.astype(jnp.int32), hi.astype(jnp.int32),
        block_rows=block_rows, interpret=interpret)
    return sel.reshape(-1)[:n], counts


@functools.partial(_jit_kernel, static=("batch", "csp_capacity",
                                        "block_rows"))
def amper_sample(pq: jax.Array, valid: jax.Array, lo: jax.Array,
                 hi: jax.Array, shift: jax.Array, key: jax.Array,
                 *, batch: int, csp_capacity: int,
                 block_rows: int | None = None,
                 interpret: bool = False):
    """The whole AMPER-fr draw fused into one Pallas dispatch.

    match + CSP count + in-kernel key split + threefry draw + rank gather
    over a flat int32[n] table; bit-identical to the reference
    ``_compact`` + ``sample_from_csp`` pipeline under the same
    (shift, key) randomness.

    Args:
      pq, valid: flat int32[n] / bool[n] table.
      lo, hi: int32[m] inclusive range bounds per group.
      shift: int32 scalar compaction rotation (``randint(kroll, (), 0, n)``).
      key: typed PRNG key of the pick key (the kernel performs the
        pick/fallback ``split`` itself, bit-exact with ``jax.random``).
      batch: number of draws (static).
      csp_capacity: CSP buffer capacity (static).

    Returns:
      (idx int32[batch], stats int32[4] = [members, members below shift,
      live rows, truncated CSP count]).
    """
    block_rows = _auto_block_rows(pq.shape[0]) if block_rows is None else block_rows
    pq2, valid2, _n = _pad_table(pq, valid, block_rows)
    idx, stats = _as.amper_sample(
        pq2, valid2, lo.astype(jnp.int32), hi.astype(jnp.int32),
        jnp.asarray(shift, jnp.int32),
        jax.random.key_data(key).astype(jnp.uint32),
        batch=batch, csp_capacity=csp_capacity,
        block_rows=block_rows, interpret=interpret)
    return idx, stats


@functools.partial(_jit_kernel, static=("block_rows",))
def rank_select(pq: jax.Array, valid: jax.Array, lo: jax.Array,
                hi: jax.Array, rank: jax.Array, *,
                block_rows: int | None = None,
                interpret: bool = False):
    """Index of each rank-th member of the fused m-range match (one pass).

    Streaming replacement for ``nonzero``-compaction + gather on the
    sharded per-shard pick path.  Ranks >= member count return 0 (callers
    mask by ownership).  Returns (idx int32[batch], count int32 scalar).
    """
    block_rows = _auto_block_rows(pq.shape[0]) if block_rows is None else block_rows
    pq2, valid2, _n = _pad_table(pq, valid, block_rows)
    return _as.rank_select(pq2, valid2, lo.astype(jnp.int32),
                           hi.astype(jnp.int32), rank.astype(jnp.int32),
                           block_rows=block_rows, interpret=interpret)


@functools.partial(_jit_kernel, static=("causal", "window", "bq", "bkv"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Blockwise attention with seq/head-dim padding to tile boundaries."""
    b, hq, s, d = q.shape
    s_pad = -s % max(bq, bkv)
    d_pad = -d % LANES
    if s_pad or d_pad:
        pad4 = ((0, 0), (0, 0), (0, s_pad), (0, d_pad))
        # Pre-scale q so the kernel's 1/sqrt(d_padded) equals the true
        # 1/sqrt(d): zero-padding the head dim leaves q.k unchanged, only
        # the softmax temperature needs the correction, applied to q.
        if d_pad:
            q = q * (((d + d_pad) / d) ** 0.5)
        q = jnp.pad(q, pad4)
        k = jnp.pad(k, pad4)
        v = jnp.pad(v, pad4)
    # Padded KV columns sit at positions >= s, so causal/window geometry
    # masks them for every real q row.  Non-causal inputs must be aligned.
    if not causal and s_pad:
        raise ValueError("non-causal flash path requires tile-aligned seq")
    out = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                  bq=bq, bkv=bkv, interpret=interpret)
    if d_pad or s_pad:
        out = out[:, :, :s, :d]
    return out


@functools.partial(_jit_kernel, static=("bkv",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     cur_len, *, bkv: int = 512,
                     interpret: bool = False) -> jax.Array:
    """Single-token cache attention; pads S and D to tile boundaries."""
    b, hkv, group, d = q.shape
    s_len = k.shape[2]
    s_pad = -s_len % bkv
    d_pad = -d % LANES
    if d_pad:
        q = q * (((d + d_pad) / d) ** 0.5)  # keep true softmax temperature
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad)))
    if s_pad or d_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, s_pad), (0, d_pad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, s_pad), (0, d_pad)))
    out = _da.decode_attention_fwd(q, k, v, jnp.asarray(cur_len, jnp.int32),
                                   bkv=min(bkv, k.shape[2]),
                                   interpret=interpret)
    return out[..., :d]
