"""Priority write-back law (PER's p = (|delta| + eps)^alpha, written over
the sampled rows).

For a deferred write carrying sample-time stamps, a row whose current
(counter, generation) stamp differs was recycled and keeps its value;
among repeated rows the last valid occurrence wins.  Without stamps every
row is written, and a row repeated within one write may take any of its
occurrences' values (``allowed``).
"""
from __future__ import annotations

import numpy as np


def new_priorities(before, idx, abs_td, *, alpha: float, eps: float,
                   live=None, dtype=np.float64):
    """(values [n], allowed) after the write: ``allowed`` maps a row
    repeated without stamps to the set of values it may take."""
    p = (np.abs(np.asarray(abs_td, np.float64)) + eps) ** alpha
    if dtype != np.float64:
        from bench.reference.sampler_amper_fr import _cast

        p = np.asarray(_cast(p, dtype), np.float64)
    out = np.asarray(before, np.float64).copy()
    idx = np.asarray(idx, np.int64)
    allowed = {}
    if live is None:
        for r, v in zip(idx, p):
            allowed.setdefault(int(r), set()).add(float(v))
        for r, vs in allowed.items():
            out[r] = max(vs)
        allowed = {r: vs for r, vs in allowed.items() if len(vs) > 1}
    else:
        for r, v, ok in zip(idx, p, np.asarray(live, bool)):
            if ok:
                out[r] = v
    return out, allowed


def gap(got, want, allowed, scale: float) -> float:
    """Largest difference between the written table and the law's, as a
    share of ``scale``; rows with several allowed values take the nearest."""
    got = np.asarray(got, np.float64)
    d = np.abs(got - want)
    for r, vs in allowed.items():
        d[r] = min(abs(got[r] - v) for v in vs)
    return float(d.max() / scale)
