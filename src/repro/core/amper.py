"""AMPER: associative-memory-based prioritized experience replay (Algorithm 1).

Implements both paper variants as shape-static, jit/shard-friendly JAX:

* :func:`build_csp_fr` -- AMPER-fr: one ternary prefix match per group
  (Fig. 6(b2)/(c)), the faithful TPU mapping of the exact-match TCAM search.
  ``exact_radius=True`` swaps the power-of-2 prefix approximation for an
  exact ``|p - V| <= Delta`` range compare at identical vector cost — the
  beyond-paper variant (a VPU, unlike a TCAM, range-compares for free).

* :func:`build_csp_k` -- AMPER-k: the N_i nearest stored priorities per
  group representative (Eqn. 1).  The oracle path selects via a full sort;
  the fast path (`knn_mode="bisect"`) finds a per-group radius by bisecting
  on the count returned by parallel range matches — the TPU-native
  replacement for the paper's k sequential best-match TCAM sensings.

Each builder splits into a membership step (``*_members``: the CSP as a
mask plus the rotation key) and the compaction.  :meth:`AmperSampler.sample`
never compacts: :func:`rank_pick` rank-selects the drawn members straight
from the mask (a count pass, then a search for ``batch`` ranks), bit-for-bit
equal to compacting first.  :func:`build_csp_fr` / :func:`build_csp_k`
still build the fixed-capacity index buffer (``jnp.nonzero(size=...)``) for
callers that analyse the CSP itself.  Every path is shape-static, so the
whole sampler jits, vmaps and shards.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

import repro.core.quantize as qz


class AmperConfig(NamedTuple):
    """Hyper-parameters of Algorithm 1.

    Attributes:
      capacity: replay size n (number of priority rows).
      m: number of groups (paper sweeps 2..20; Fig. 9 uses 20).
      lam: scaling factor (lambda) for AMPER-k, Eqn. 1.
      lam_fr: scaling factor (lambda') for AMPER-fr, Eqn. 4.
      v_max: static maximum priority value V_max.
      csp_capacity: static CSP buffer size (paper: CSP ratio * capacity;
        Fig. 9 uses ratio 0.15).
      frac_bits: fixed-point fraction bits for int32 quantization.
      exact_radius: AMPER-fr only — use exact range compare instead of the
        prefix-mask power-of-2 approximation (beyond-paper mode).
      knn_mode: "sort" (oracle top-N_i), "bisect" (radius bisection) or
        "hist" (shared cumulative histogram — 2 table passes).
      fr_mode: "broadcast" ((m,N) compare, the faithful m-query search),
        "interval" (merged-interval stabbing, one table pass), "window"
        (per-row neighbour-group gather, O(ceil(2*lam')) ops/row),
        "kernel" (fused Pallas multi-query kernel, one HBM pass;
        interpret mode off-TPU) or "fused" (the whole draw — match, CSP
        count, threefry pick, rank gather — in ONE Pallas dispatch via
        :func:`repro.kernels.ops.amper_sample`; membership queries fall
        back to the "kernel" path).  All five produce bit-identical CSP
        membership, sampled indices and importance weights.
    """

    capacity: int
    m: int = 20
    lam: float = 0.05
    lam_fr: float = 1.0
    v_max: float = 1.0
    csp_capacity: int = 1500
    frac_bits: int = qz.DEFAULT_FRAC_BITS
    exact_radius: bool = False
    knn_mode: str = "sort"
    fr_mode: str = "broadcast"


FR_MODES = ("broadcast", "interval", "window", "kernel", "fused")
KNN_MODES = ("sort", "bisect", "hist")


def check_modes(cfg: AmperConfig) -> None:
    """Raise on an ``fr_mode`` / ``knn_mode`` no path implements (an
    unknown mode must not fall through to another search silently)."""
    if cfg.fr_mode not in FR_MODES:
        raise ValueError(f"unknown fr_mode {cfg.fr_mode!r} "
                         f"(available: {FR_MODES})")
    if cfg.knn_mode not in KNN_MODES:
        raise ValueError(f"unknown knn_mode {cfg.knn_mode!r} "
                         f"(available: {KNN_MODES})")


class CspResult(NamedTuple):
    """Stream-compacted candidate set of priorities."""

    indices: jax.Array  # int32[csp_capacity], -1 padded
    count: jax.Array    # int32 scalar, number of valid entries
    selected: jax.Array  # bool[capacity] membership mask (for analysis/tests)


def group_representatives(key: jax.Array, cfg: AmperConfig) -> jax.Array:
    """Line 3 of Algorithm 1: V(g_i) ~ U[ V_max*i/m, V_max*(i+1)/m )."""
    i = jnp.arange(cfg.m, dtype=jnp.float32)
    lo = cfg.v_max * i / cfg.m
    width = cfg.v_max / cfg.m
    return lo + width * jax.random.uniform(key, (cfg.m,))


def group_counts(pq: jax.Array, valid: jax.Array, cfg: AmperConfig) -> jax.Array:
    """Line 5: C(g_i) — histogram of stored priorities over the m groups."""
    width_q = (1 << cfg.frac_bits) // cfg.m
    g = jnp.clip(pq // jnp.maximum(width_q, 1), 0, cfg.m - 1)
    return jnp.zeros(cfg.m, jnp.int32).at[g].add(valid.astype(jnp.int32))


def _compact(selected: jax.Array, csp_capacity: int,
             key: jax.Array | None = None) -> CspResult:
    """Stream compaction of a membership mask into a fixed-size index buffer.

    If the match count exceeds the buffer capacity, plain ``nonzero``
    keeps the lowest indices — a systematic bias toward whichever rows
    the hardware scans first.  With ``key`` we start the scan at a random
    rotation, so truncation drops a uniformly-random contiguous arc
    instead of always the same rows (unbiased in expectation).

    Only :meth:`AmperSampler.build_csp` (probes, analysis, tests) builds
    this buffer; the draw reads the same entries through :func:`rank_pick`,
    because ``nonzero(size=...)`` lowers to a scatter of every row into
    the buffer, which the TPU serialises.
    """
    n = selected.shape[0]
    if key is not None:
        shift = jax.random.randint(key, (), 0, n)
        rolled = jnp.roll(selected, -shift)
        (idx,) = jnp.nonzero(rolled, size=csp_capacity, fill_value=-1)
        idx = jnp.where(idx >= 0, (idx + shift) % n, -1)
    else:
        (idx,) = jnp.nonzero(selected, size=csp_capacity, fill_value=-1)
    count = jnp.minimum(jnp.sum(selected.astype(jnp.int32)), csp_capacity)
    return CspResult(indices=idx.astype(jnp.int32), count=count, selected=selected)


def fr_queries(v_rep: jax.Array, cfg: AmperConfig) -> tuple[jax.Array, jax.Array]:
    """AMPER-fr query generator (Fig. 6(b2)): (query, dont-care mask) per group.

    Delta_i = round(lambda'/m * V(g_i))   [Eqn. 4, in quantized units]
    mask_i  = bits at/below leading '1' of Delta_i.
    """
    vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
    delta_q = jnp.round((cfg.lam_fr / cfg.m) * vq.astype(jnp.float32)).astype(jnp.int32)
    mask = qz.prefix_mask(delta_q)
    return vq, mask


def fr_radii(v_rep: jax.Array, cfg: AmperConfig) -> jax.Array:
    """Exact (non-power-of-2) radii for the beyond-paper range-compare mode."""
    vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
    return jnp.round((cfg.lam_fr / cfg.m) * vq.astype(jnp.float32)).astype(jnp.int32)


def fr_members(pq: jax.Array, valid: jax.Array, key: jax.Array,
               cfg: AmperConfig) -> tuple[jax.Array, jax.Array]:
    """AMPER-fr CSP membership (Algorithm 1, lines 2-3, 9-12).

    Args:
      pq: int32[capacity] quantized priorities.
      valid: bool[capacity] — slot currently holds a real experience with
        non-zero priority.
      key: PRNG key, split into the group representatives' key and the
        compaction rotation key ``kroll``.

    Returns:
      (selected bool[capacity], kroll).
    """
    check_modes(cfg)
    if cfg.fr_mode in ("kernel", "fused"):
        # "fused" only differs on the *sampling* path (AmperSampler.sample
        # dispatches the whole draw as one kernel); explicit CSP builds
        # share the fused-membership kernel.
        return fr_members_kernel(pq, valid, key, cfg)
    kv, kroll = jax.random.split(key)
    v_rep = group_representatives(kv, cfg)
    if cfg.fr_mode == "interval":
        lo, hi = fr_intervals(v_rep, cfg)
        return _interval_membership(pq, lo, hi) & valid, kroll
    if cfg.fr_mode == "window":
        lo, hi = fr_intervals(v_rep, cfg)
        return _window_membership(pq, lo, hi, cfg) & valid, kroll
    if cfg.exact_radius:
        vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
        radius = fr_radii(v_rep, cfg)
        match = jnp.abs(pq[None, :] - vq[:, None]) <= radius[:, None]
    else:
        vq, mask = fr_queries(v_rep, cfg)
        match = qz.ternary_match(pq[None, :], vq[:, None], mask[:, None])
    return jnp.any(match, axis=0) & valid, kroll


def build_csp_fr(pq: jax.Array, valid: jax.Array, key: jax.Array,
                 cfg: AmperConfig) -> CspResult:
    """AMPER-fr CSP: :func:`fr_members` compacted into the index buffer."""
    selected, kroll = fr_members(pq, valid, key, cfg)
    return _compact(selected, cfg.csp_capacity, kroll)


def knn_sizes(v_rep: jax.Array, counts: jax.Array, cfg: AmperConfig) -> jax.Array:
    """Eqn. 1: N_i = round(lambda * V(g_i) * C(g_i))."""
    return jnp.round(cfg.lam * v_rep * counts.astype(jnp.float32)).astype(jnp.int32)


def _knn_select_sort(pq: jax.Array, valid: jax.Array, vq: jax.Array,
                     n_i: jax.Array) -> jax.Array:
    """Oracle kNN: per group, mark the N_i nearest valid priorities.

    Returns bool[m, capacity].  Ties at the radius boundary are broken by
    index (stable sort), matching a deterministic hardware scan order.
    """
    big = jnp.int32(2**30)
    dist = jnp.abs(pq[None, :] - vq[:, None])
    dist = jnp.where(valid[None, :], dist, big)
    rank = jnp.argsort(jnp.argsort(dist, axis=1), axis=1)  # rank of each slot
    return (rank < n_i[:, None]) & valid[None, :]


def _knn_select_bisect(pq: jax.Array, valid: jax.Array, vq: jax.Array,
                       n_i: jax.Array, frac_bits: int) -> jax.Array:
    """TPU-native kNN: bisect on radius until count(|p-V|<=r) >= N_i.

    log2(range) parallel count passes replace the paper's N_i sequential
    best-match sensings.  Over-selection at the final radius is trimmed by
    index order so |subset| == N_i exactly.
    """
    big = jnp.int32(2**30)
    dist = jnp.where(valid[None, :], jnp.abs(pq[None, :] - vq[:, None]), big)

    def body(carry, _):
        lo, hi = carry  # int32[m] bounds on radius
        mid = (lo + hi) // 2
        cnt = jnp.sum(dist <= mid[:, None], axis=1)
        lo = jnp.where(cnt < n_i, mid + 1, lo)
        hi = jnp.where(cnt >= n_i, mid, hi)
        return (lo, hi), None

    lo = jnp.zeros_like(n_i)
    hi = jnp.full_like(n_i, 1 << frac_bits)
    (radius, _), _ = jax.lax.scan(body, (lo, hi), None, length=frac_bits + 1)
    within = dist <= radius[:, None]
    # Trim over-selection (ties at the radius): keep the first N_i by index.
    order = jnp.cumsum(within.astype(jnp.int32), axis=1)
    return within & (order <= n_i[:, None])


def fr_intervals(v_rep: jax.Array, cfg: AmperConfig) -> tuple[jax.Array, jax.Array]:
    """The m accepted ranges [lo_i, hi_i] of AMPER-fr (prefix or exact)."""
    vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
    if cfg.exact_radius:
        r = fr_radii(v_rep, cfg)
        return vq - r, vq + r
    _, mask = fr_queries(v_rep, cfg)
    return qz.prefix_range(vq, mask)


def _interval_membership(pq: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Is pq inside the union of [lo_i, hi_i]?  One searchsorted pass.

    Interval-stabbing formulation: sort the 2m boundary events, prefix-sum
    the open/close weights to get coverage depth at each boundary, then a
    single binary search per row reads off whether its depth is > 0.
    O(N log m) compares and exactly one pass over the table — versus the
    (m, N) broadcast compare that materialises m bitmasks.  This is the
    roofline-floor version of the TCAM search for the selection-only
    (AMPER-fr) case.
    """
    m = lo.shape[0]
    # events: +1 at lo, -1 at hi+1
    pts = jnp.concatenate([lo, hi + 1])
    wts = jnp.concatenate([jnp.ones(m, jnp.int32), -jnp.ones(m, jnp.int32)])
    order = jnp.argsort(pts)
    pts, wts = pts[order], wts[order]
    depth = jnp.cumsum(wts)  # coverage depth AFTER each event point
    idx = jnp.searchsorted(pts, pq, side="right") - 1
    return jnp.where(idx >= 0, depth[jnp.clip(idx, 0, 2 * m - 1)] > 0, False)


def _window_membership(pq: jax.Array, lo: jax.Array, hi: jax.Array,
                       cfg: AmperConfig) -> jax.Array:
    """Neighbour-window membership: O(ceil(2*lam')) ops/row, no (m,N) temps.

    Group i's accepted block has width <= 2*Delta_i <= 2*lam'*group_width
    and contains V(g_i) which lies IN group i, so a row in value-group g
    can only be matched by groups within ceil(2*lam') of g.  Gather those
    2c+1 candidate bounds per row and compare — the (m, N) broadcast the
    faithful search materialises never exists.
    """
    m = cfg.m
    width_q = max((1 << cfg.frac_bits) // m, 1)
    g = jnp.clip(pq // width_q, 0, m - 1)
    c = int(-(-2 * cfg.lam_fr // 1))  # ceil(2*lam')
    sel = jnp.zeros(pq.shape, jnp.bool_)
    for j in range(-c, c + 1):
        gi = jnp.clip(g + j, 0, m - 1)
        sel = sel | ((pq >= lo[gi]) & (pq <= hi[gi]))
    return sel


def fr_members_kernel(pq: jax.Array, valid: jax.Array, key: jax.Array,
                      cfg: AmperConfig) -> tuple[jax.Array, jax.Array]:
    """AMPER-fr membership via the fused Pallas multi-query kernel (one
    HBM pass).

    Bit-identical to the broadcast search of :func:`fr_members`: a prefix
    query with don't-care mask M is exactly the inclusive range
    [q & ~M, (q & ~M) | M].
    """
    from repro.kernels import ops as kops  # deferred: kernels are optional

    kv, kroll = jax.random.split(key)
    v_rep = group_representatives(kv, cfg)
    if cfg.exact_radius:
        vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
        radius = fr_radii(v_rep, cfg)
        lo, hi = vq - radius, vq + radius
    else:
        vq, mask = fr_queries(v_rep, cfg)
        lo, hi = qz.prefix_range(vq, mask)
    sel, _counts = kops.multi_query_match(pq, valid, lo, hi)
    return sel, kroll


def build_csp_fr_kernel(pq: jax.Array, valid: jax.Array, key: jax.Array,
                        cfg: AmperConfig) -> CspResult:
    """AMPER-fr CSP: :func:`fr_members_kernel` compacted into the buffer."""
    selected, kroll = fr_members_kernel(pq, valid, key, cfg)
    return _compact(selected, cfg.csp_capacity, kroll)


def _knn_select_hist(pq: jax.Array, valid: jax.Array, vq: jax.Array,
                     n_i: jax.Array, frac_bits: int,
                     hist_bins: int = 4096) -> jax.Array:
    """Histogram kNN: ~2 passes over the table instead of ~26.

    One shared cumulative VALUE histogram F (single pass over pq) turns
    count(|p - V| <= r) into F(V+r) - F(V-r): the per-group radius
    bisection then runs on 4 KiB of histogram instead of re-scanning the
    table per probe.  One final match pass selects; over-selection from
    bin granularity is trimmed by scan order so |subset| == N_i exactly.
    """
    top = 1 << frac_bits
    shift = frac_bits - (hist_bins.bit_length() - 1)
    bucket = jnp.clip(pq >> shift, 0, hist_bins - 1)
    hist = jnp.zeros(hist_bins, jnp.int32).at[bucket].add(valid.astype(jnp.int32))
    cum = jnp.cumsum(hist)  # F(b) = count of pq with bucket <= b

    def count_within(radius):
        # LOWER bound: count only buckets fully inside [V-r, V+r], so the
        # bisected radius can only over-select; the exact trim below then
        # cuts back to N_i precisely.
        binsz = 1 << shift
        lo_b = jnp.clip((vq - radius + binsz - 1) >> shift, 0, hist_bins)
        hi_b = jnp.clip(((vq + radius + 1) >> shift) - 1, -1, hist_bins - 1)
        below = jnp.where(lo_b > 0, cum[jnp.clip(lo_b - 1, 0, hist_bins - 1)], 0)
        inside = cum[jnp.clip(hi_b, 0, hist_bins - 1)] - below
        return jnp.where(hi_b >= lo_b, inside, 0)

    def body(carry, _):
        lo, hi = carry
        mid = (lo + hi) // 2
        cnt = count_within(mid)
        lo = jnp.where(cnt < n_i, mid + 1, lo)
        hi = jnp.where(cnt >= n_i, mid, hi)
        return (lo, hi), None

    lo = jnp.zeros_like(n_i)
    hi = jnp.full_like(n_i, top)
    (radius, _), _ = jax.lax.scan(body, (lo, hi), None, length=frac_bits + 1)
    big = jnp.int32(2**30)
    dist = jnp.where(valid[None, :], jnp.abs(pq[None, :] - vq[:, None]), big)
    within = dist <= radius[:, None]
    order = jnp.cumsum(within.astype(jnp.int32), axis=1)
    return within & (order <= n_i[:, None])


def k_members(pq: jax.Array, valid: jax.Array, key: jax.Array,
              cfg: AmperConfig) -> tuple[jax.Array, jax.Array]:
    """AMPER-k CSP membership (Algorithm 1, lines 2-8): (selected, kroll)
    under the key split of :func:`fr_members`."""
    kv, kroll = jax.random.split(key)
    v_rep = group_representatives(kv, cfg)
    vq = qz.quantize(v_rep, cfg.v_max, cfg.frac_bits)
    counts = group_counts(pq, valid, cfg)
    n_i = knn_sizes(v_rep, counts, cfg)
    if cfg.knn_mode == "bisect":
        sel = _knn_select_bisect(pq, valid, vq, n_i, cfg.frac_bits)
    elif cfg.knn_mode == "hist":
        sel = _knn_select_hist(pq, valid, vq, n_i, cfg.frac_bits)
    else:
        sel = _knn_select_sort(pq, valid, vq, n_i)
    return jnp.any(sel, axis=0) & valid, kroll


def build_csp_k(pq: jax.Array, valid: jax.Array, key: jax.Array,
                cfg: AmperConfig) -> CspResult:
    """AMPER-k CSP: :func:`k_members` compacted into the index buffer."""
    selected, kroll = k_members(pq, valid, key, cfg)
    return _compact(selected, cfg.csp_capacity, kroll)


def pick_uniform(bits: jax.Array, bound) -> jax.Array:
    """Uniform int32 draw in [0, max(bound, 1)) from raw uint32 bits.

    The ONE reduction law shared by the reference sampler and the fused
    Pallas kernel's in-kernel threefry draw, so both paths map identical
    bits to identical indices.  Plain modulo: the bias is bound/2^32
    (< 1e-6 for any real CSP), invisible to the chi-square gates.
    """
    b = jnp.maximum(jnp.asarray(bound, jnp.int32), 1).astype(jnp.uint32)
    return (bits % b).astype(jnp.int32)


def sample_from_csp(csp: CspResult, key: jax.Array, batch: int,
                    fallback_size: jax.Array) -> jax.Array:
    """Algorithm 1 lines 14-17: uniform sample of the CSP.

    If the CSP came up empty (possible early in training when all
    priorities sit in one group and the representative misses), fall back
    to uniform over the live buffer — the same degenerate behaviour a
    hardware CSP buffer underflow would trigger.

    Draws reduce raw ``jax.random.bits`` through :func:`pick_uniform`
    (not ``randint``) so the fused kernel, recomputing the same threefry
    stream in-kernel, reproduces them bit-for-bit.
    """
    k_pick, k_fb = jax.random.split(key)
    u = pick_uniform(jax.random.bits(k_pick, (batch,), jnp.uint32), csp.count)
    picked = csp.indices[u]
    fallback = pick_uniform(jax.random.bits(k_fb, (batch,), jnp.uint32),
                            fallback_size)
    return jnp.where(csp.count > 0, picked, fallback).astype(jnp.int32)


def _rank_block(n: int) -> int:
    """Block length of :func:`rank_pick`: the power of two at or above
    sqrt(n), so the block search and the in-block search cost alike; at
    least one 128-lane row."""
    return max(128, 1 << ((max(n - 1, 1).bit_length() + 1) // 2))


def rank_pick(selected: jax.Array, kroll: jax.Array, key: jax.Array,
              batch: int, csp_capacity: int,
              fallback_size: jax.Array) -> jax.Array:
    """:func:`sample_from_csp` of ``_compact(selected, csp_capacity, kroll)``
    without building the compacted buffer; bit-identical.

    The buffer rotated by ``shift`` holds at slot u the member whose
    index-order rank is ``(u + s_shift) % total`` (``s_shift`` = members
    below ``shift``), also when truncated to ``csp_capacity``, since every
    draw is below the truncated count.  So one pass counts the members of
    each block of the mask, the blocks' running counts locate each drawn
    rank's block, and a running count within that block's row its lane.
    """
    n = selected.shape[0]
    shift = jax.random.randint(kroll, (), 0, n)
    block = _rank_block(n)
    n_blocks = -(-n // block)
    sel = jnp.pad(selected, (0, n_blocks * block - n)).reshape(n_blocks, block)
    pos = jnp.arange(n_blocks * block, dtype=jnp.int32).reshape(n_blocks, block)
    block_counts = jnp.sum(sel, axis=1, dtype=jnp.int32)
    s_shift = jnp.sum(sel & (pos < shift), dtype=jnp.int32)
    total = jnp.sum(block_counts)
    count = jnp.minimum(total, csp_capacity)

    k_pick, k_fb = jax.random.split(key)
    u = pick_uniform(jax.random.bits(k_pick, (batch,), jnp.uint32), count)
    rank = (u + s_shift) % jnp.maximum(total, 1)
    ends = jnp.cumsum(block_counts)
    blk = jnp.minimum(jnp.sum(ends[None, :] <= rank[:, None], axis=1,
                              dtype=jnp.int32), n_blocks - 1)
    in_block = rank - (ends[blk] - block_counts[blk])
    row_ends = jnp.cumsum(sel[blk], axis=1, dtype=jnp.int32)
    lane = jnp.sum(row_ends <= in_block[:, None], axis=1, dtype=jnp.int32)
    picked = blk * block + lane
    fallback = pick_uniform(jax.random.bits(k_fb, (batch,), jnp.uint32),
                            fallback_size)
    return jnp.where(count > 0, picked, fallback).astype(jnp.int32)


class AmperState(NamedTuple):
    """Sampler state: quantized priorities + validity mask."""

    pq: jax.Array     # int32[capacity]
    valid: jax.Array  # bool[capacity]


class AmperSampler:
    """Unified AMPER sampler ('fr' or 'k' variant) with the PER-like API.

    Priorities passed to :meth:`update` are the already-exponentiated
    p = |td|^alpha values, exactly as for the PER baselines, so samplers
    are drop-in interchangeable in the replay buffer and the data pipeline.
    """

    def __init__(self, cfg: AmperConfig, variant: str = "fr"):
        if variant not in ("fr", "k"):
            raise ValueError(f"unknown AMPER variant: {variant!r}")
        check_modes(cfg)
        self.cfg = cfg
        self.variant = variant

    def init(self) -> AmperState:
        return AmperState(
            pq=jnp.zeros(self.cfg.capacity, jnp.int32),
            valid=jnp.zeros(self.cfg.capacity, jnp.bool_),
        )

    def total(self, state: AmperState) -> jax.Array:
        return jnp.sum(
            qz.dequantize(state.pq, self.cfg.v_max, self.cfg.frac_bits)
            * state.valid
        )

    def priorities(self, state: AmperState) -> jax.Array:
        return qz.dequantize(state.pq, self.cfg.v_max, self.cfg.frac_bits) * state.valid

    def update(self, state: AmperState, idx: jax.Array, priority: jax.Array) -> AmperState:
        """Priority write — a single TCAM row write in hardware (Sec. 3.4.3)."""
        pq = state.pq.at[idx].set(qz.quantize(priority, self.cfg.v_max, self.cfg.frac_bits))
        valid = state.valid.at[idx].set(priority > 0)
        return AmperState(pq=pq, valid=valid)

    def members(self, state: AmperState,
                key: jax.Array) -> tuple[jax.Array, jax.Array]:
        """CSP membership mask and compaction rotation key."""
        fn = fr_members if self.variant == "fr" else k_members
        with jax.named_scope("csp_build"):
            return fn(state.pq, state.valid, key, self.cfg)

    def build_csp(self, state: AmperState, key: jax.Array) -> CspResult:
        selected, kroll = self.members(state, key)
        with jax.named_scope("csp_build"):
            return _compact(selected, self.cfg.csp_capacity, kroll)

    def sample(self, state: AmperState, key: jax.Array, batch: int,
               stratified: bool = True) -> jax.Array:
        """``sample_from_csp(build_csp(state, kcsp), kpick, ...)`` with
        ``kcsp, kpick = split(key)``, drawn by :func:`rank_pick` (or the
        fused kernel) without building the CSP buffer."""
        del stratified  # CSP sampling is uniform by construction
        kcsp, kpick = jax.random.split(key)
        if self.variant == "fr" and self.cfg.fr_mode == "fused":
            return self._sample_fused(state, kcsp, kpick, batch)
        selected, kroll = self.members(state, kcsp)
        with jax.named_scope("csp_pick"):
            live = jnp.sum(state.valid.astype(jnp.int32))
            return rank_pick(selected, kroll, kpick, batch,
                             self.cfg.csp_capacity, live)

    def _sample_fused(self, state: AmperState, kcsp: jax.Array,
                      kpick: jax.Array, batch: int) -> jax.Array:
        """One Pallas dispatch for the whole draw (fr_mode="fused").

        The key tree mirrors the reference exactly — kcsp -> (kv, kroll)
        for representatives and the compaction rotation; kpick goes to the
        kernel whole, which performs the reference's (k_pick, k_fb) split
        in-kernel — so the in-kernel threefry consumes the very streams
        the reference would, and indices come out bit-identical.
        """
        from repro.kernels import ops as kops  # deferred: kernels are optional
        from repro.kernels.amper_sample import MAX_FRAC_BITS

        cfg = self.cfg
        if cfg.frac_bits > MAX_FRAC_BITS:
            raise ValueError(
                f"fr_mode='fused' needs frac_bits <= {MAX_FRAC_BITS} "
                f"(one-hot f32 gathers are exact below 2^24), got "
                f"{cfg.frac_bits}")
        kv, kroll = jax.random.split(kcsp)
        v_rep = group_representatives(kv, cfg)
        lo, hi = fr_intervals(v_rep, cfg)
        shift = jax.random.randint(kroll, (), 0, cfg.capacity)
        idx, _stats = kops.amper_sample(
            state.pq, state.valid, lo, hi, shift, kpick,
            batch=batch, csp_capacity=cfg.csp_capacity)
        return idx


def make_sampler(kind: str, capacity: int, **kw):
    """Deprecated alias for :func:`repro.core.samplers.make_sampler`."""
    from repro.core import samplers  # local import to avoid cycles

    return samplers.make_sampler(kind, capacity, **kw)


class UniformState(NamedTuple):
    priorities: jax.Array  # kept so the API is uniform; ignored for sampling
    valid: jax.Array


class UniformSampler:
    """Uniform ER — the paper's weak baseline."""

    def __init__(self, capacity: int):
        self.capacity = capacity

    def init(self) -> UniformState:
        return UniformState(
            priorities=jnp.zeros(self.capacity, jnp.float32),
            valid=jnp.zeros(self.capacity, jnp.bool_),
        )

    def total(self, state: UniformState) -> jax.Array:
        return jnp.sum(state.priorities * state.valid)

    def priorities(self, state: UniformState) -> jax.Array:
        return state.priorities * state.valid

    def update(self, state: UniformState, idx, priority) -> UniformState:
        return UniformState(
            priorities=state.priorities.at[idx].set(priority),
            valid=state.valid.at[idx].set(priority > 0),
        )

    def sample(self, state: UniformState, key, batch: int, stratified: bool = True):
        del stratified
        live = jnp.maximum(jnp.sum(state.valid.astype(jnp.int32)), 1)
        return jax.random.randint(key, (batch,), 0, live).astype(jnp.int32)
