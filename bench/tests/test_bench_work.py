"""Peaks, draw bytes and Q-network FLOPs, tied to the shapes (CPU)."""
import jax
import jax.numpy as jnp
import pytest

from bench import work
from bench.reference import td_loss


def test_peaks_known_and_unknown():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_amper_draw_bytes_is_one_table_pass_plus_rows():
    row = work.gathered_row_bytes(100, 4, 1)
    # 5 distinct frames + their 4 scalars, then two float32 stacks and
    # three float32 scalars written.
    assert row == 5 * (100 + 16) + 2 * 4 * 100 * 4 + 12
    b = work.amper_fr_draw_bytes(1_000_000, 20, 32, row_bytes=row)
    assert b == 1_000_000 * 5 + 20 * 8 + 32 * row
    # Twice the rows or the table: the terms grow as the shapes say.
    assert work.amper_fr_draw_bytes(2_000_000, 20, 32, row_bytes=row) \
        - b == 5_000_000
    assert work.amper_fr_draw_bytes(1_000_000, 20, 64, row_bytes=row) \
        - b == 32 * row


def test_sumtree_draw_bytes_follow_depth():
    b = work.sumtree_draw_bytes(1 << 20, 32, row_bytes=0)
    assert b == 32 * (2 * 20 * 4 + 4) + 4
    assert work.sumtree_draw_bytes((1 << 20) + 1, 32, row_bytes=0) - b \
        == 32 * 2 * 4


def test_qnet_flops_match_xla_count_of_the_reference_forward():
    fwd = work.conv_qnet_forward_flops(10, 10, 4, hidden=128, n_actions=3)
    assert fwd == 2 * (8 * 8 * 16 * 36 + 1024 * 128 + 128 * 3)
    params = td_loss.init(jax.random.key(0), 4, (10, 10), 128, 3)
    x = jnp.zeros((1, 10, 10, 4))
    cost = jax.jit(lambda p, x: td_loss.q_values(
        p, x, jax.lax.Precision.DEFAULT)).lower(params, x).compile()
    flops = cost.cost_analysis()["flops"]
    # XLA also counts the bias adds and ReLUs (a few thousand).
    assert fwd <= flops <= fwd * 1.02
    assert work.qnet_update_flops(32, fwd) == 32 * 4 * fwd
    assert work.qnet_update_flops(32, fwd, double=True) == 32 * 5 * fwd
