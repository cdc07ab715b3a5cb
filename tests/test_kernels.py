"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.quantize as qz
from repro.kernels import ops, ref


@pytest.mark.parametrize("n", [128, 4096, 5000, 16384])
@pytest.mark.parametrize("maskbits", [0, 7, 12, 20])
def test_tcam_match_sweep(n, maskbits):
    pq = jax.random.randint(jax.random.key(n + maskbits), (n,), 0, 1 << 24,
                            dtype=jnp.int32)
    query = pq[n // 2]  # guarantee at least one hit
    mask = jnp.int32((1 << maskbits) - 1)
    out = ops.tcam_match(pq, query, mask)
    expected = ref.tcam_match_ref(pq, query, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expected))
    assert bool(out[n // 2])


@pytest.mark.parametrize("n,m", [(1024, 1), (4096, 8), (9000, 20)])
def test_multi_query_sweep(n, m):
    key = jax.random.key(n * m)
    pq = jax.random.randint(key, (n,), 0, 1 << 24, dtype=jnp.int32)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.85, (n,))
    centers = jax.random.randint(jax.random.fold_in(key, 2), (m,), 0, 1 << 24,
                                 dtype=jnp.int32)
    radius = jax.random.randint(jax.random.fold_in(key, 3), (m,), 0, 1 << 20,
                                dtype=jnp.int32)
    lo, hi = centers - radius, centers + radius
    sel, cnt = ops.multi_query_match(pq, valid, lo, hi)
    sel_r, cnt_r = ref.multi_query_match_ref(pq, valid, lo, hi)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel_r))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hq,hkv,s,d,causal,window",
    [
        (2, 4, 2, 256, 64, True, None),    # GQA
        (1, 8, 1, 256, 128, True, None),   # MQA
        (2, 4, 4, 256, 128, True, 64),     # MHA + sliding window
        (1, 2, 2, 256, 256, False, None),  # bidirectional (encoder)
        (1, 4, 2, 300, 64, True, None),    # non-tile-aligned seq
    ])
def test_flash_attention_sweep(dtype, b, hq, hkv, s, d, causal, window):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    expected = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        atol=tol, rtol=tol)


def test_flash_matches_model_chunked_attention():
    """Pallas kernel == the jnp blockwise training path."""
    from repro.models.attention import chunked_attention, make_mask_fn
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 64))
    k = jax.random.normal(ks[1], (2, 2, 256, 64))
    v = jax.random.normal(ks[2], (2, 2, 256, 64))
    a = ops.flash_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, make_mask_fn(True, None, None),
                          bq=64, bkv=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_kernel_amper_parity_large():
    """Fused kernel path drives the same CSP as XLA on a big table."""
    from repro.core.amper import AmperConfig, build_csp_fr, build_csp_fr_kernel
    n = 1 << 15
    p = jax.random.uniform(jax.random.key(4), (n,))
    pq = qz.quantize(p, 1.0)
    valid = jnp.ones(n, bool)
    cfg = AmperConfig(capacity=n, m=20, lam_fr=2.0, csp_capacity=4096)
    key = jax.random.key(5)
    a = build_csp_fr(pq, valid, key, cfg)
    b = build_csp_fr_kernel(pq, valid, key, cfg)
    np.testing.assert_array_equal(np.asarray(a.selected), np.asarray(b.selected))


# --- fused amper_sample: in-kernel PRNG ---------------------------------------


@pytest.mark.parametrize("n", [1, 2, 5, 64, 127, 257])
@pytest.mark.parametrize("seed", [0, 1, 7])
def test_counter_bits_matches_jax_random_bits(n, seed):
    """The kernel's per-lane threefry recomputation is bit-exact with
    jax.random.bits at every size, odd sizes included, and needs no n."""
    from repro.kernels.amper_sample import counter_bits
    key = jax.random.key(seed)
    kd = jax.random.key_data(key).astype(jnp.uint32)
    expect = jax.random.bits(key, (n,), jnp.uint32)
    got = counter_bits(kd, jnp.arange(n, dtype=jnp.uint32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_counter_bits_split_identity():
    """split(key)[i].key_data == threefry2x32(key, (0, i)) — the identity
    the kernel uses to derive its pick/fallback subkeys in-kernel — and
    the subkeys' draws match jax.random.bits."""
    from repro.kernels.amper_sample import counter_bits, split_key
    key = jax.random.key(11)
    kd = jax.random.key_data(key).astype(jnp.uint32)
    ks = jax.random.split(key)
    got = np.asarray([split_key(kd, i) for i in range(2)], np.uint32)
    np.testing.assert_array_equal(
        got, np.asarray(jax.random.key_data(ks)).astype(np.uint32))
    j = jnp.arange(33, dtype=jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(counter_bits(split_key(kd, 1), j)),
        np.asarray(jax.random.bits(ks[1], (33,), jnp.uint32)))


# --- fused amper_sample: whole-draw bit-identity + edge cases -----------------


def _fused_vs_reference(n, csp_capacity, batch, seed=0, empty=False):
    """Assert fr_mode='fused' draws the exact indices of the reference."""
    from repro.core.amper import AmperConfig, AmperSampler
    cfg = AmperConfig(capacity=n, m=20, lam_fr=2.0, v_max=1.0,
                      csp_capacity=csp_capacity)
    ref_s = AmperSampler(cfg, "fr")
    fus_s = AmperSampler(cfg._replace(fr_mode="fused"), "fr")
    if empty:
        s_r, s_f = ref_s.init(), fus_s.init()
    else:
        prio = jax.random.uniform(jax.random.key(seed), (n,)) + 0.01
        s_r = ref_s.update(ref_s.init(), jnp.arange(n), prio)
        s_f = fus_s.update(fus_s.init(), jnp.arange(n), prio)
    key = jax.random.key(seed + 100)
    np.testing.assert_array_equal(
        np.asarray(ref_s.sample(s_r, key, batch)),
        np.asarray(fus_s.sample(s_f, key, batch)))


def test_fused_all_invalid_table():
    """Empty table -> both paths take the uniform fallback draw."""
    _fused_vs_reference(2048, 256, 64, empty=True)


def test_fused_csp_saturated_at_capacity():
    """Far more members than csp_capacity: the truncated count governs
    the draw on both paths (cyclic-rank identity under truncation)."""
    _fused_vs_reference(20_000, 64, 32, seed=1)


def test_fused_batch_larger_than_csp():
    _fused_vs_reference(4096, 16, 128, seed=2)


@pytest.mark.parametrize("n", [127, 130, 5000, 10_001])
def test_fused_non_block_multiple_sizes(n):
    """Table sizes that are not multiples of block_rows*128 exercise the
    -1/invalid padding rows."""
    _fused_vs_reference(n, max(8, n // 8), 33, seed=3)


def test_fused_explicit_interpret_flag():
    """ops.amper_sample(interpret=True) == the reference XLA pipeline:
    pins the interpret-mode escape hatch independently of the backend
    default."""
    from repro.core.amper import (AmperConfig, build_csp_fr, fr_intervals,
                                  group_representatives, sample_from_csp)
    n, batch = 5000, 64
    cfg = AmperConfig(capacity=n, m=20, lam_fr=2.0, v_max=1.0,
                      csp_capacity=512)
    p = jax.random.uniform(jax.random.key(6), (n,))
    pq = qz.quantize(p, cfg.v_max)
    valid = jnp.ones(n, bool)
    kcsp, kpick = jax.random.split(jax.random.key(7))
    csp = build_csp_fr(pq, valid, kcsp, cfg)
    live = jnp.sum(valid.astype(jnp.int32))
    expect = sample_from_csp(csp, kpick, batch, live)

    kv, kroll = jax.random.split(kcsp)
    v_rep = group_representatives(kv, cfg)
    lo, hi = fr_intervals(v_rep, cfg)
    shift = jax.random.randint(kroll, (), 0, cfg.capacity)
    idx, stats = ops.amper_sample(pq, valid, lo, hi, shift, kpick,
                                  batch=batch, csp_capacity=cfg.csp_capacity,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(expect))
    assert int(stats[2]) == n  # live rows


def test_fused_rejects_wide_frac_bits():
    """frac_bits > 24 would overflow the f32 one-hot gathers — refused."""
    from repro.core.amper import AmperConfig, AmperSampler
    cfg = AmperConfig(capacity=1024, frac_bits=30, fr_mode="fused")
    s = AmperSampler(cfg, "fr")
    st = s.update(s.init(), jnp.arange(64), jnp.ones(64) * 0.5)
    with pytest.raises(ValueError, match="frac_bits"):
        s.sample(st, jax.random.key(0), 8)


def test_rank_select_matches_nonzero_oracle():
    """rank_select returns nonzero(selected)[rank] for in-range ranks and
    0 past the member count."""
    n, m = 9000, 12
    key = jax.random.key(21)
    pq = jax.random.randint(key, (n,), 0, 1 << 20, dtype=jnp.int32)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.9, (n,))
    centers = jax.random.randint(jax.random.fold_in(key, 2), (m,), 0, 1 << 20,
                                 dtype=jnp.int32)
    radius = jax.random.randint(jax.random.fold_in(key, 3), (m,), 0, 1 << 16,
                                dtype=jnp.int32)
    lo, hi = centers - radius, centers + radius
    sel = np.asarray(((pq[None, :] >= lo[:, None])
                      & (pq[None, :] <= hi[:, None])).any(0) & valid)
    members = np.nonzero(sel)[0]
    assert len(members) > 2, "degenerate oracle"
    ranks = jnp.asarray([0, 1, len(members) // 2, len(members) - 1,
                         len(members), len(members) + 5], jnp.int32)
    idx, cnt = ops.rank_select(pq, valid, lo, hi, ranks)
    assert int(cnt) == len(members)
    idx = np.asarray(idx)
    for r, i in zip(np.asarray(ranks), idx):
        assert i == (members[r] if r < len(members) else 0), (r, i)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hkv,group,s,d,cur", [
    (2, 2, 4, 1024, 64, 700),    # GQA
    (1, 1, 8, 512, 128, 512),    # MQA, full cache
    (2, 4, 1, 300, 96, 37),      # MHA, ragged S and D
])
def test_decode_attention_sweep(dtype, b, hkv, group, s, d, cur):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, hkv, group, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = ops.decode_attention(q, k, v, cur, bkv=256)
    expected = ref.decode_attention_ref(q, k, v, cur)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=tol, rtol=tol)


def test_decode_kernel_matches_model_path():
    """Pallas decode kernel == models.attention.decode_attention."""
    from repro.models.attention import decode_attention as model_decode, \
        make_mask_fn
    ks = jax.random.split(jax.random.key(2), 3)
    b, hkv, group, s, d = 2, 2, 3, 256, 64
    q4 = jax.random.normal(ks[0], (b, hkv * group, 1, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    cur = jnp.int32(100)
    a = model_decode(q4, k, v, cur, make_mask_fn(True, None, None))
    qg = q4.reshape(b, hkv, group, d)
    b_out = ops.decode_attention(qg, k, v, cur, bkv=128)
    np.testing.assert_allclose(
        np.asarray(a[:, :, 0]).reshape(b, hkv, group, d),
        np.asarray(b_out), atol=3e-5)
