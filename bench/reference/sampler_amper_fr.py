"""AMPER-fr law (arXiv:2207.07791, Algorithm 1 with the prefix queries of
Fig. 6(b2)) on one device, in NumPy.

Given the quantized priority table, its validity and a draw key:

1. key -> (kcsp, kpick); kcsp -> (kv, kroll).
2. m group representatives V_i = v_max*i/m + (v_max/m)*U_i, U ~ kv.
3. Each V_i is quantized to fixed point (``frac_bits`` fraction bits of
   ``v_max``, round half to even, top code 2^frac_bits - 1); its radius is
   Delta_i = round(lam'/m * V_i) and its don't-care mask every bit at and
   below Delta_i's leading one.  A row matches when all other bits equal.
4. The candidate set is the valid matching rows, read in index order from
   a rotation drawn from kroll, truncated to ``csp_capacity``.
5. kpick -> (k_pick, k_fb): each of ``batch`` draws takes the CSP entry
   ``bits(k_pick) mod |CSP|``; an empty CSP draws ``bits(k_fb) mod live``.

Importance weights follow PER's formula from the dequantized table (see
``weights``).
"""
from __future__ import annotations

import numpy as np

from bench.reference import rng


def _cast(x, dtype):
    """Round through ``dtype`` (bfloat16 for the control) and back."""
    if dtype == np.float32:
        return np.asarray(x, np.float32)
    import ml_dtypes

    return np.asarray(np.asarray(x, ml_dtypes.bfloat16), np.float32)


def quantize(p, v_max: float, frac_bits: int, dtype=np.float32) -> np.ndarray:
    top = (1 << frac_bits) - 1
    scale = _cast(np.float32(top / v_max), dtype)
    q = np.round(_cast(np.clip(_cast(p, dtype), 0.0, v_max) * scale, dtype))
    return np.minimum(q, top).astype(np.int64)


def membership(pq, valid, kv, *, m, lam_fr, v_max, frac_bits,
               dtype=np.float32) -> np.ndarray:
    """bool[n]: valid rows matched by any of the m prefix queries."""
    i = np.arange(m, dtype=np.float32)
    lo = _cast(np.float32(v_max) * i / np.float32(m), dtype)
    v_rep = _cast(lo + _cast(np.float32(v_max / m), dtype)
                  * _cast(rng.uniform(kv, (m,)), dtype), dtype)
    vq = quantize(v_rep, v_max, frac_bits, dtype)
    delta = np.round(_cast(np.float32(lam_fr / m) * vq.astype(np.float32),
                           dtype)).astype(np.int64)
    mask = np.array([(1 << int(d).bit_length()) - 1 if d > 0 else 0
                     for d in delta], np.int64)
    pq = np.asarray(pq, np.int64)
    sel = np.zeros(pq.shape, bool)
    for q, mk in zip(vq, mask):
        sel |= ((pq ^ q) & ~mk) == 0
    return sel & np.asarray(valid, bool)


def draw(pq, valid, key, batch: int, *, m, lam_fr, v_max, frac_bits,
         csp_capacity, dtype=np.float32) -> np.ndarray:
    """int64[batch] row indices the law draws with ``key``."""
    n = len(pq)
    kcsp, kpick = rng.split(key)
    kv, kroll = rng.split(kcsp)
    sel = membership(pq, valid, kv, m=m, lam_fr=lam_fr, v_max=v_max,
                     frac_bits=frac_bits, dtype=dtype)
    shift = int(rng.randint(kroll, (), 0, n))
    order = (np.arange(n) + shift) % n
    csp = order[sel[order]][:csp_capacity]
    k_pick, k_fb = rng.split(kpick)
    if len(csp):
        return csp[rng.bits(k_pick, (batch,)) % np.uint32(len(csp))]
    live = max(int(np.sum(valid)), 1)
    return (rng.bits(k_fb, (batch,)) % np.uint32(live)).astype(np.int64)


def priorities(pq, valid, *, v_max, frac_bits) -> np.ndarray:
    """float64 priorities the quantized table stands for."""
    return (np.asarray(pq, np.float64) * (v_max / ((1 << frac_bits) - 1))
            * np.asarray(valid, bool))


def weights(prios, idx, size: int, beta: float, dtype=np.float64):
    """PER importance weights (N * P(i))^-beta / max, over one draw.
    Shared by every sampler's reference: the formula is PER's."""
    p = np.asarray(prios, np.float64)
    total = max(p.sum(), 1e-12)
    share = np.maximum(p[idx], 1e-12) / total
    if dtype == np.float64:
        w = (size * share) ** (-beta)
        return w / max(w.max(), 1e-12)
    share = _cast(share, dtype)
    w = _cast(_cast(size * share, dtype) ** (-beta), dtype)
    return np.asarray(_cast(w / max(w.max(), 1e-12), dtype), np.float64)
