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

Every slot these read lies at ``a + k*stride`` (mod the capacity) for ``k``
in :func:`window`, so the law reads the ring's rows there and nothing else
of it.  Frames scale to float as ``uint8 * scale``.
"""
from __future__ import annotations

import numpy as np


def window(history_len: int, n_step: int) -> np.ndarray:
    """The steps ``k`` around an anchor whose slots the law reads: the
    oldest frame of ``obs`` up to the bootstrap row."""
    return np.arange(1 - history_len, n_step + 1)


def materialize(rows: dict, idx, size: int, *, capacity: int,
                history_len: int, stride: int, n_step: int, gamma: float,
                scale: float, dtype=np.float32) -> dict:
    """The reference batch at anchors ``idx``.  ``rows`` holds the ring's
    ``frame``, ``done``, ``reward``, ``action`` and ``write_stamp`` at the
    slots ``(idx + k*stride) % capacity``, one column per ``k`` of
    :func:`window`: ``[len(idx), len(window), ...]``."""
    frame = np.asarray(rows["frame"])
    done = np.asarray(rows["done"], np.float32)
    reward = np.asarray(rows["reward"], np.float32)
    stamp = np.asarray(rows["write_stamp"], np.int64)
    a = np.asarray(idx, np.int64) % capacity
    col = lambda k: k + history_len - 1  # noqa: E731
    slot = lambda k: (a + k * stride) % capacity  # noqa: E731
    ref = stamp[:, col(0)]
    sc = np.float32(scale)

    def cast(x):
        if dtype == np.float32:
            return np.asarray(x, np.float32)
        import ml_dtypes

        return np.asarray(np.asarray(x, ml_dtypes.bfloat16), np.float32)

    def stack(end, ok):
        end_stamp = stamp[:, col(end)]
        out = []
        for j in range(history_len):
            k = col(end - j)
            if j:
                ok = (ok & (stamp[:, k] - end_stamp == -j * stride)
                      & (slot(end - j) < size) & (done[:, k] < 0.5))
            f = cast(frame[:, k].astype(np.float32) * sc)
            out.append(f * ok.reshape(ok.shape + (1,) * (f.ndim - 1)))
        return np.stack(out[::-1], axis=-1)

    written = a < size
    obs = stack(0, written)
    enter = written.copy()
    ret = np.zeros(len(a), np.float32)
    for k in range(n_step):
        use = enter & (stamp[:, col(k)] - ref == k * stride) & (slot(k) < size)
        ret = cast(ret + use * cast(np.float32(gamma ** k)
                                    * reward[:, col(k)]))
        enter = use & (done[:, col(k)] < 0.5)
    has = (enter & (stamp[:, col(n_step)] - ref == n_step * stride)
           & (slot(n_step) < size))
    nxt = stack(n_step, has)
    term = 1.0 - has.astype(np.float32)
    return {"obs": obs, "action": np.asarray(rows["action"])[:, col(0)],
            "reward": ret, "next_obs": nxt, "terminated": term}


def gap(got: dict, want: dict) -> float:
    """Largest absolute difference over the stacked batch's leaves."""
    worst = 0.0
    for k, w in want.items():
        g = np.asarray(got[k], np.float64).reshape(np.shape(w))
        worst = max(worst, float(np.max(np.abs(g - np.asarray(w, np.float64)),
                                        initial=0.0)))
    return worst
