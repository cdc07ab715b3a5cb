"""Fused AMPER-fr sampling: the whole draw in one Pallas dispatch.

The reference law (``build_csp`` + ``sample_from_csp``) runs Algorithm 1
as separate XLA ops: quantized m-range TCAM match -> stream compaction of
the CSP (``nonzero`` after a random rotation) -> uniform counter draw ->
index gather; the XLA draw path (``amper.rank_pick``) rank-selects from
the mask instead, by the identity below.  This kernel is the paper's
Fig. 3 pipeline as ONE pass machine:

* phase 0 streams the (rows, 128) priority table once, evaluating the
  m-range match per tile and accumulating three scalars in SMEM — the
  CSP member count, the count of members below the rotation point, and
  the live-row count;
* between phases it draws the batch in-kernel: a threefry2x32 counter
  PRNG (bit-exact with ``jax.random.bits``) keyed by the caller's pick /
  fallback subkeys, reduced mod the CSP count;
* phase 1 streams the table a second time, rank-selecting each drawn
  CSP member directly from the match mask (hierarchical row/lane select
  via one-hot matmuls) — the compacted CSP index buffer never exists.

Bit-identity with the reference is exact, not statistical.  The key
identity: the reference rolls the selection mask by a random ``shift``
before ``nonzero``-compacting, so ``csp.indices[u]`` is the member with
*cyclic* rank u — which equals the member with ordinary (index-order)
rank ``(u + s_shift) % total`` where ``s_shift`` counts members at
indices below ``shift``.  Rank-selecting that member from the mask in
index order therefore reproduces the compacted buffer's answer without
materialising it, including under capacity truncation (the draw is
``bits % min(total, csp_capacity)``, always a valid cyclic rank).

The one-hot row/lane gathers run as f32 matmuls at
``Precision.HIGHEST`` (MXU-friendly); a single bf16 pass would round the
gathered row offsets above 256, so full f32 is what keeps them exact
for integers below 2^24, which bounds ``frac_bits <= 24`` (the
default).  ``interpret=True`` off-TPU executes the identical program in
Python, so CPU CI pins the exact kernel logic; the Mosaic lowering is
pinned by ``tests/test_tpu_compile.py``.

Mosaic constraints the layout follows: scalars (range bounds, shift,
key words, counters, stats) live in SMEM, iotas are integer (cast to
f32 where a float index is needed), and every VMEM block is
(8, 128)-aligned or the whole array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import DEFAULT_BLOCK_ROWS, LANES, smem_spec

MAX_FRAC_BITS = 24  # one-hot f32 matmul gathers are exact below 2^24

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, d):
    return (x << jnp.uint32(d)) | (x >> jnp.uint32(32 - d))


def _threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32 block cipher on uint32 lanes (bit-exact with
    ``jax.random``'s threefry, 20 rounds with the standard key schedule)."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def counter_bits(key_data, j: jax.Array) -> jax.Array:
    """``jax.random.bits(key, (n,), uint32)`` evaluated at positions ``j``.

    Under jax's partitionable threefry layout, bit ``j`` of a 1-D draw is
    ``o0 ^ o1`` of ``threefry2x32(key, (0, j))`` for any length ``n``, so
    each lane computes its own word: the whole draw is a map — no
    slicing, no cross-lane traffic, safe inside a kernel at any
    alignment.  ``key_data`` is any pair indexable as ``[0]``/``[1]``.
    """
    o0, o1 = _threefry2x32(key_data[0], key_data[1], jnp.uint32(0),
                           j.astype(jnp.uint32))
    return o0 ^ o1


def split_key(key_data, i: int):
    """Subkey ``i`` of ``jax.random.split(key)``: the word pair
    ``threefry2x32(key, (0, i))`` (partitionable layout)."""
    return _threefry2x32(key_data[0], key_data[1], jnp.uint32(0),
                         jnp.uint32(i))


def _gather_dot(a, b):
    """f32 matmul exact for integer operands below 2^24 (one-hot gathers)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _match_tile(p, valid, lo_ref, hi_ref, m: int):
    """OR of the m inclusive range matches on one (block_rows, 128) tile."""
    sel = jnp.zeros(p.shape, jnp.bool_)
    for i in range(m):
        sel = sel | ((p >= lo_ref[i]) & (p <= hi_ref[i]))
    return sel & valid


def _rank_select_tile(sel, rank, base, b, block_rows: int):
    """Members of ordinary rank ``rank`` that fall in this tile.

    ``sel`` is the tile's (block_rows, 128) match mask, ``base`` the member
    count of all earlier tiles and ``b`` the tile's block index.  Returns
    (int32[1, bp] flat indices, 0 where a rank lies in another tile; the
    tile's member count).  Hierarchical select via one-hot matmuls: row
    first (inclusive row cumsum), then lane within the chosen row.
    """
    bp = rank.shape[0]
    f32 = jnp.float32
    rowsum = jnp.sum(sel.astype(jnp.int32), axis=1)  # (block_rows,)
    blk_cnt = jnp.sum(rowsum)
    # inclusive row cumsum via triangular mask-sum (exact: counts < 2^24)
    r_i = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 0)
    r_j = jax.lax.broadcasted_iota(jnp.int32, (block_rows, block_rows), 1)
    tri_rows = (r_i <= r_j).astype(f32)  # [i, j] = i <= j
    row_ck = _gather_dot(rowsum.astype(f32)[None, :], tri_rows)[0]

    lr = rank - base                                 # local rank in tile
    hit = (lr >= 0) & (lr < blk_cnt)
    lr_f = jnp.clip(lr, 0, jnp.maximum(blk_cnt - 1, 0)).astype(f32)
    # row r holds local member lr iff exclusive_ck[r] <= lr < inclusive
    t_row = jnp.sum((row_ck[None, :] <= lr_f[:, None]).astype(f32), axis=1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bp, block_rows), 1)
    onehot = (rows.astype(f32) == t_row[:, None]).astype(f32)
    excl = row_ck - rowsum.astype(f32)               # exclusive cumsum
    row_base = _gather_dot(onehot, excl[:, None])[:, 0]
    selrow = _gather_dot(onehot, sel.astype(f32))    # (bp, LANES)
    rem = lr_f - row_base
    l_i = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    l_j = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    lane_ck = _gather_dot(selrow, (l_i <= l_j).astype(f32))  # inclusive
    t_lane = jnp.sum((lane_ck <= rem[:, None]).astype(f32), axis=1)
    flat = ((b * block_rows + t_row.astype(jnp.int32)) * LANES
            + t_lane.astype(jnp.int32))
    return jnp.where(hit, flat, 0)[None, :], blk_cnt


def amper_sample_kernel(lo_ref, hi_ref, shift_ref, key_ref,
                        p_ref, valid_ref, idx_ref, stats_ref,
                        acc_ref, draw_ref,
                        *, m: int, csp_capacity: int, block_rows: int):
    """Grid (2, nblk), executed sequentially (TPU grid order).

    acc_ref (SMEM int32[4]): [total members, members below shift, live
    rows, running member prefix before the current phase-1 block].
    draw_ref (VMEM int32[2, batch_pad]): row 0 = target ordinary ranks,
    row 1 = fallback raw indices.
    """
    phase = pl.program_id(0)
    b = pl.program_id(1)
    nblk = pl.num_programs(1)
    bp = draw_ref.shape[1]

    @pl.when((phase == 0) & (b == 0))
    def _init():
        acc_ref[0] = 0
        acc_ref[1] = 0
        acc_ref[2] = 0
        acc_ref[3] = 0

    @pl.when(phase == 0)
    def _count():
        rows2d = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0)
        lanes2d = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
        gidx = (b * block_rows + rows2d) * LANES + lanes2d  # global flat index
        sel = _match_tile(p_ref[...], valid_ref[...], lo_ref, hi_ref, m)
        shift = shift_ref[0]
        acc_ref[0] += jnp.sum(sel.astype(jnp.int32))
        acc_ref[1] += jnp.sum((sel & (gidx < shift)).astype(jnp.int32))
        acc_ref[2] += jnp.sum(valid_ref[...].astype(jnp.int32))

    @pl.when((phase == 1) & (b == 0))
    def _draw():
        total = acc_ref[0]
        s_shift = acc_ref[1]
        live = acc_ref[2]
        count = jnp.minimum(total, csp_capacity)
        j = jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
        # In-kernel jax.random.split(key): the pick / fallback subkeys are
        # two more cipher evaluations, so the host never touches raw key
        # data beyond the caller's one key.
        pick = counter_bits(split_key(key_ref, 0), j)
        fb = counter_bits(split_key(key_ref, 1), j)
        # same arithmetic as amper.pick_uniform: bits mod max(bound, 1)
        u = (pick % jnp.maximum(count, 1).astype(jnp.uint32)).astype(jnp.int32)
        rank = (u + s_shift) % jnp.maximum(total, 1)
        draw_ref[0:1, :] = rank
        draw_ref[1:2, :] = (fb % jnp.maximum(live, 1).astype(jnp.uint32)
                            ).astype(jnp.int32)
        idx_ref[...] = jnp.zeros((1, bp), jnp.int32)
        stats_ref[0] = total
        stats_ref[1] = s_shift
        stats_ref[2] = live
        stats_ref[3] = count

    @pl.when(phase == 1)
    def _select():
        sel = _match_tile(p_ref[...], valid_ref[...], lo_ref, hi_ref, m)
        base = acc_ref[3]
        found, blk_cnt = _rank_select_tile(sel, draw_ref[0:1, :][0], base, b,
                                           block_rows)
        idx_ref[...] += found
        acc_ref[3] = base + blk_cnt

    @pl.when((phase == 1) & (b == nblk - 1))
    def _finish():
        idx_ref[...] = jnp.where(acc_ref[0] > 0, idx_ref[...],
                                 draw_ref[1:2, :])


def amper_sample(pq: jax.Array, valid: jax.Array, lo: jax.Array,
                 hi: jax.Array, shift: jax.Array, key_data: jax.Array,
                 *, batch: int, csp_capacity: int,
                 block_rows: int = DEFAULT_BLOCK_ROWS,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """One fused dispatch: m-range match + CSP count + draw + rank gather.

    Args:
      pq: int32[R, 128] quantized priority table (R multiple of block_rows;
        padding rows carry -1 / invalid, so they never match).
      valid: bool[R, 128].
      lo, hi: int32[m] inclusive range bounds per group.
      shift: int32 scalar — the compaction rotation (from the roll key).
      key_data: uint32[2] raw threefry key of the UN-SPLIT pick key; the
        kernel derives the pick and fallback subkeys itself (bit-exact
        with ``jax.random.split``).
      batch: draws per call (static).
      csp_capacity: CSP buffer capacity (static; truncates the count).

    Returns:
      (idx int32[batch] flat indices, stats int32[4] = [members, members
      below shift, live rows, truncated CSP count]).
    """
    rows = pq.shape[0]
    m = lo.shape[0]
    nblk = rows // block_rows
    bp = -(-batch // LANES) * LANES  # batch padded to the lane width
    idx, stats = pl.pallas_call(
        functools.partial(amper_sample_kernel, m=m,
                          csp_capacity=csp_capacity, block_rows=block_rows),
        grid=(2, nblk),
        in_specs=[
            smem_spec(), smem_spec(), smem_spec(), smem_spec(),
            pl.BlockSpec((block_rows, LANES), lambda p, b: (b, 0)),
            pl.BlockSpec((block_rows, LANES), lambda p, b: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bp), lambda p, b: (0, 0)),
            smem_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
            jax.ShapeDtypeStruct((4,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((4,), jnp.int32),
            pltpu.VMEM((2, bp), jnp.int32),
        ],
        interpret=interpret,
    )(lo, hi, shift.reshape(1), key_data, pq, valid)
    return idx[0, :batch], stats


def rank_select_kernel(rank_ref, p_ref, valid_ref, lo_ref, hi_ref,
                       idx_ref, cnt_ref, acc_ref,
                       *, m: int, block_rows: int):
    """Grid (nblk,): index of the rank-th CSP member, in index order.

    The sharded per-shard pick: replaces ``nonzero``-compaction + gather
    with a single streaming pass.  Ranks >= member count return 0 (the
    caller masks by ownership, exactly as the reference clips).
    """
    b = pl.program_id(0)
    nblk = pl.num_programs(0)

    @pl.when(b == 0)
    def _init():
        acc_ref[0] = 0
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    sel = _match_tile(p_ref[...], valid_ref[...], lo_ref, hi_ref, m)
    base = acc_ref[0]
    found, blk_cnt = _rank_select_tile(sel, rank_ref[0:1, :][0], base, b,
                                       block_rows)
    idx_ref[...] += found
    acc_ref[0] = base + blk_cnt

    @pl.when(b == nblk - 1)
    def _count():
        cnt_ref[0] = acc_ref[0]


def rank_select(pq: jax.Array, valid: jax.Array, lo: jax.Array,
                hi: jax.Array, rank: jax.Array, *,
                block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Flat index of each rank-th member of the m-range match, one pass.

    Args:
      pq, valid: (R, 128) padded table view.
      lo, hi: int32[m] inclusive range bounds.
      rank: int32[batch] 0-based member ranks (index order).
    Returns:
      (idx int32[batch] — 0 where rank >= count, cnt int32 scalar member
      count).
    """
    rows = pq.shape[0]
    m = lo.shape[0]
    nblk = rows // block_rows
    batch = rank.shape[0]
    bp = -(-batch // LANES) * LANES
    rank2 = jnp.pad(rank, (0, bp - batch)).reshape(1, bp)
    idx, cnt = pl.pallas_call(
        functools.partial(rank_select_kernel, m=m, block_rows=block_rows),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, bp), lambda b: (0, 0)),
            pl.BlockSpec((block_rows, LANES), lambda b: (b, 0)),
            pl.BlockSpec((block_rows, LANES), lambda b: (b, 0)),
            smem_spec(), smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, bp), lambda b: (0, 0)),
            smem_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(rank2, pq, valid, lo, hi)
    return idx[0, :batch], cnt[0]
