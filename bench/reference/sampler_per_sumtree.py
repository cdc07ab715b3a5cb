"""Proportional PER law, stratified (Schaul et al., arXiv:1511.05952,
Sec. 3.3 and Appendix B.2.1), in float64.

Draw j of ``batch`` aims at the mass ``(j + U_j) * total / batch`` with
U ~ uniform(key), and takes the row whose cumulative-priority interval
holds it.  A sum tree in float32 rounds its partial sums, so the
comparison is how far, as a share of the total mass, each drawn row's
interval lies from its target (``mass_gap``), not index equality.
"""
from __future__ import annotations

import numpy as np

from bench.reference import rng
from bench.reference.sampler_amper_fr import weights

__all__ = ["draw", "mass_gap", "weights"]


def draw(prios, key, batch: int, dtype=np.float64) -> np.ndarray:
    """Rows the law draws, with cumulative sums kept in ``dtype``."""
    from bench.reference.sampler_amper_fr import _cast

    p = np.asarray(prios, np.float64)
    if dtype == np.float64:
        c = np.cumsum(p)
    else:
        # Partial sums held in dtype (the rounding of each stored sum).
        c = _cast(np.cumsum(_cast(p, dtype).astype(np.float64)), dtype)
    total = c[-1]
    t = (np.arange(batch) + rng.uniform(key, (batch,))) * (total / batch)
    return np.clip(np.searchsorted(c, t, side="right"), 0, len(p) - 1)


def mass_gap(prios, key, idx) -> float:
    """Largest distance, as a share of the total, between a drawn row's
    cumulative interval and the mass its draw aimed at."""
    p = np.asarray(prios, np.float64)
    idx = np.asarray(idx, np.int64)
    batch = len(idx)
    c = np.cumsum(p)
    total = max(c[-1], 1e-300)
    t = (np.arange(batch) + rng.uniform(key, (batch,))) * (total / batch)
    hi = c[idx]
    lo = hi - p[idx]
    gap = np.maximum(np.maximum(lo - t, t - hi), 0.0)
    return float(gap.max() / total)
