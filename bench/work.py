"""Work a step must do, from its shapes: bytes a draw must move and the
Q-network's FLOPs per learner update.  Independent of how the program
implements either, so a faster implementation reads as a larger share
of the same work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks of ``device_kind``; unknown is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r} "
                       f"(bench/peaks.json knows {sorted(table)})")
    return table[device_kind]


def gathered_row_bytes(frame_bytes: int, history_len: int, n_step: int,
                       scalars: int = 4, scalar_bytes: int = 4,
                       out_bytes: int = 4) -> int:
    """Bytes one drawn row moves: the ``history_len + n_step`` distinct
    frames its two stacks span, read once; its scalars (action, reward,
    done, write stamp) along the same ``history_len + n_step`` slots;
    and the float stacks, return and flags it writes."""
    span = history_len + n_step
    reads = span * (frame_bytes + scalars * scalar_bytes)
    writes = 2 * history_len * frame_bytes * out_bytes + 3 * out_bytes
    return reads + writes


def amper_fr_draw_bytes(n: int, m: int, batch: int, *, row_bytes: int,
                        pq_bytes: int = 4, valid_bytes: int = 1) -> int:
    """Least bytes of one AMPER-fr draw: one pass over the quantized table
    and its validity (the m queries' bounds ride along), then the drawn
    rows."""
    return n * (pq_bytes + valid_bytes) + m * 2 * pq_bytes + batch * row_bytes


def sumtree_draw_bytes(n: int, batch: int, *, row_bytes: int,
                       node_bytes: int = 4) -> int:
    """Least bytes of one stratified sum-tree draw: each draw reads the two
    children at every level of a tree over ``n`` leaves, plus its leaf for
    the weight and the root, then the drawn rows."""
    depth = max(n - 1, 0).bit_length()
    return batch * (2 * depth * node_bytes + node_bytes) + node_bytes \
        + batch * row_bytes


def conv_qnet_forward_flops(h: int, w: int, channels: int, *, hidden: int,
                            n_actions: int, conv_out: int = 16,
                            k: int = 3) -> int:
    """Multiply-adds x 2 of one forward pass of the conv Q-network on one
    observation: a k x k VALID conv to ``conv_out`` channels, a dense
    layer to ``hidden`` and one to ``n_actions``."""
    oh, ow = h - k + 1, w - k + 1
    conv = oh * ow * conv_out * k * k * channels
    flat = oh * ow * conv_out
    return 2 * (conv + flat * hidden + hidden * n_actions)


def qnet_update_flops(batch: int, forward: int, *, double: bool = False
                      ) -> int:
    """FLOPs of one learner update: the online network's forward and
    backward (backward = 2 forwards) on ``obs`` and the target network's
    forward on ``next_obs`` (plus the online forward on ``next_obs`` for
    Double DQN), per row of the batch."""
    passes = 3 + 1 + (1 if double else 0)
    return batch * passes * forward
