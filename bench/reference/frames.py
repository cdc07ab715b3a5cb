"""Frame-stack replay on the host: the observation stacks, n-step return
and bootstrap a frame-deduplicated ring stands for.

Rows are written in ``stride``-wide steps, so the previous timestep of the
stream at slot ``i`` sits at ``i - stride``.  For an anchor row ``a`` with
write stamp ``s``:

* ``obs`` stacks ``history_len`` frames ending at ``a``, oldest first.  The
  frame ``j`` steps back counts only while every link back to it holds: its
  slot carries stamp ``s - j*stride``, lies below ``size`` and does not end
  an episode; a broken link zeroes it and every older frame.
* the n-step return sums ``gamma^k * reward`` over the rows ``a + k*stride``
  whose stamps are ``s + k*stride`` (written, in sequence), stopping after
  the first ``done``;
* ``next_obs`` is the stack ending ``n_step*stride`` after ``a`` when the
  window ran through without an end and that row is in sequence; otherwise
  the transition is terminal (``next_obs`` zero, ``terminated`` 1).

Frames scale to float as ``uint8 * scale``.
"""
from __future__ import annotations

import numpy as np


def materialize(ring: dict, stamp, size: int, idx, *, history_len: int,
                stride: int, n_step: int, gamma: float, scale: float,
                dtype=np.float32) -> dict:
    frame = np.asarray(ring["frame"])
    done = np.asarray(ring["done"], np.float32)
    reward = np.asarray(ring["reward"], np.float32)
    stamp = np.asarray(stamp, np.int64)
    cap = len(stamp)
    a = np.asarray(idx, np.int64) % cap
    ref = stamp[a]
    sc = np.float32(scale)

    def cast(x):
        if dtype == np.float32:
            return np.asarray(x, np.float32)
        import ml_dtypes

        return np.asarray(np.asarray(x, ml_dtypes.bfloat16), np.float32)

    def stack(end, end_stamp, ok):
        out = []
        for j in range(history_len):
            slot = (end - j * stride) % cap
            if j:
                ok = (ok & (stamp[slot] - end_stamp == -j * stride)
                      & (slot < size) & (done[slot] < 0.5))
            f = cast(frame[slot].astype(np.float32) * sc)
            out.append(f * ok.reshape(ok.shape + (1,) * (f.ndim - 1)))
        return np.stack(out[::-1], axis=-1)

    written = a < size
    obs = stack(a, ref, written)
    enter = written.copy()
    ret = np.zeros(len(a), np.float32)
    for k in range(n_step):
        slot = (a + k * stride) % cap
        use = enter & (stamp[slot] - ref == k * stride) & (slot < size)
        ret = cast(ret + use * cast(np.float32(gamma ** k) * reward[slot]))
        enter = use & (done[slot] < 0.5)
    boot = (a + n_step * stride) % cap
    has = enter & (stamp[boot] - ref == n_step * stride) & (boot < size)
    nxt = stack(boot, stamp[boot], has)
    term = 1.0 - has.astype(np.float32)
    return {"obs": obs, "action": np.asarray(ring["action"])[a],
            "reward": ret, "next_obs": nxt, "terminated": term}


def gap(got: dict, want: dict) -> float:
    """Largest absolute difference over the stacked batch's leaves."""
    worst = 0.0
    for k, w in want.items():
        g = np.asarray(got[k], np.float64).reshape(np.shape(w))
        worst = max(worst, float(np.max(np.abs(g - np.asarray(w, np.float64)),
                                        initial=0.0)))
    return worst
