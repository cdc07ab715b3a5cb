"""Pallas TPU kernel: ternary-CAM match over an int32 priority table.

This is the TPU incarnation of the paper's TCAM search (Fig. 3 / Fig. 6(c)).
A TCAM compares the query against every stored row in O(1) wall-clock by
physics; the TPU equivalent is streaming (8,128) int32 tiles HBM->VMEM and
XOR/AND/compare-ing them on the VPU — 1024 lanes per cycle, arithmetic
intensity ~1 op/byte, i.e. perfectly memory-bound streaming with zero
irregular access (exactly what the sum tree is not).

Two kernels:

* :func:`tcam_match_kernel` — single ternary query ``(p ^ q) & ~mask == 0``
  over the whole table.  Bit-faithful to the exact-match TCAM sensing.

* :func:`multi_query_kernel` — the fused AMPER search: all m group queries
  in ONE pass over HBM, emitting the OR'd selection mask plus per-group
  match counts (the C_{Δi} the paper's CSP sizing needs).  Queries are
  expressed as inclusive int32 ranges [lo_i, hi_i]; a prefix query with
  don't-care mask M is exactly the range [q & ~M, (q & ~M) | M] (see
  quantize.prefix_range), so this one kernel serves the faithful prefix
  mode, the beyond-paper exact-radius mode, AND the group histogram
  (ranges = group boundaries).

The priority table is viewed as (rows, 128) so the last dim matches the
VPU lane width; callers pad to a multiple of (block_rows * 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import DEFAULT_BLOCK_ROWS, LANES, smem_spec


def tcam_match_kernel(q_ref, mask_ref, p_ref, out_ref):
    """One ternary query against a (block_rows, 128) tile."""
    p = p_ref[...]
    q = q_ref[0]
    m = mask_ref[0]
    out_ref[...] = jnp.bitwise_and(jnp.bitwise_xor(p, q), jnp.bitwise_not(m)) == 0


def tcam_match(pq: jax.Array, query: jax.Array, mask: jax.Array,
               *, block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False) -> jax.Array:
    """Ternary match of one (query, mask) against pq viewed as (R, 128).

    Args:
      pq: int32[R, 128] quantized priority table (R multiple of block_rows).
      query, mask: int32 scalars (arrays of shape ()).
    Returns:
      bool[R, 128] match mask.
    """
    rows = pq.shape[0]
    grid = (rows // block_rows,)
    return pl.pallas_call(
        tcam_match_kernel,
        grid=grid,
        in_specs=[
            smem_spec(), smem_spec(),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.bool_),
        interpret=interpret,
    )(query.reshape(1), mask.reshape(1), pq)


def multi_query_kernel(lo_ref, hi_ref, p_ref, valid_ref, sel_ref, cnt_ref, *, m: int):
    """Fused m-range match on one tile: OR'd selection + per-group counts.

    cnt_ref is the whole int32[m] count vector in SMEM, resident across
    the (sequential) grid and accumulated per tile.  The in-kernel loop
    over m is unrolled (m is small, <= 32) so each tile is read from VMEM
    once and compared m times — the VPU analogue of issuing m TCAM
    searches while the array is precharged.
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for i in range(m):
            cnt_ref[i] = 0

    p = p_ref[...]
    valid = valid_ref[...]
    sel = jnp.zeros(p.shape, jnp.bool_)
    for i in range(m):
        match = (p >= lo_ref[i]) & (p <= hi_ref[i]) & valid
        sel = sel | match
        cnt_ref[i] += jnp.sum(match.astype(jnp.int32))
    sel_ref[...] = sel


def multi_query_match(pq: jax.Array, valid: jax.Array, lo: jax.Array,
                      hi: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """All m range queries in one pass over the (R, 128) table.

    Returns (sel bool[R,128], counts int32[m]).
    """
    rows = pq.shape[0]
    m = lo.shape[0]
    return pl.pallas_call(
        functools.partial(multi_query_kernel, m=m),
        grid=(rows // block_rows,),
        in_specs=[
            smem_spec(), smem_spec(),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            smem_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.bool_),
            jax.ShapeDtypeStruct((m,), jnp.int32),
        ],
        interpret=interpret,
    )(lo, hi, pq, valid)
