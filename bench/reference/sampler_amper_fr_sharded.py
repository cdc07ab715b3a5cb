"""AMPER-fr law over a table split into equal contiguous shards (the
mesh-sharded sampler), computed on one host.

The queries are those of the one-device law (``sampler_amper_fr``), drawn
from ``kq`` of ``key -> (kq, kpick)``, with ``kpick -> (kpick, kfb)``.
Each shard keeps its first ``csp_capacity // shards`` matching rows in
index order (no rotation).  The shards' kept counts, concatenated in
shard order, form one candidate list; draw j takes its entry
``randint(kpick, [0, total))``; an empty list draws
``randint(kfb, [0, n))``.
"""
from __future__ import annotations

import numpy as np

from bench.reference import rng
from bench.reference.sampler_amper_fr import membership, priorities, weights

__all__ = ["draw", "priorities", "weights"]


def draw(pq, valid, key, batch: int, *, m, lam_fr, v_max, frac_bits,
         csp_capacity, shards: int, dtype=np.float32) -> np.ndarray:
    n = len(pq)
    local = n // shards
    cap = max(csp_capacity // shards, 1)
    kq, kpick = rng.split(key)
    kpick, kfb = rng.split(kpick)
    sel = membership(pq, valid, kq, m=m, lam_fr=lam_fr, v_max=v_max,
                     frac_bits=frac_bits, dtype=dtype)
    kept = [s * local + np.flatnonzero(sel[s * local:(s + 1) * local])[:cap]
            for s in range(shards)]
    csp = np.concatenate(kept)
    if len(csp):
        return csp[rng.randint(kpick, (batch,), 0, len(csp))]
    return rng.randint(kfb, (batch,), 0, n)
