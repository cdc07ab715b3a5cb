"""AMPER algorithm: CSP construction, variants, kernel parity, sampling law."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.quantize as qz
from repro.core.amper import (AmperConfig, AmperSampler, build_csp_fr,
                              build_csp_fr_kernel, build_csp_k, fr_queries,
                              group_counts, group_representatives, knn_sizes,
                              sample_from_csp)

N = 4096


@pytest.fixture(scope="module")
def table():
    p = jax.random.uniform(jax.random.key(1), (N,))
    return qz.quantize(p, 1.0), jnp.ones(N, jnp.bool_), p


def cfg(**kw):
    base = dict(capacity=N, m=8, lam=0.15, lam_fr=2.0, v_max=1.0,
                csp_capacity=2048)
    base.update(kw)
    return AmperConfig(**base)


def test_group_counts_partition(table):
    pq, valid, _ = table
    counts = group_counts(pq, valid, cfg())
    assert int(counts.sum()) == N


def test_representatives_in_group_range():
    c = cfg(m=16)
    v = group_representatives(jax.random.key(0), c)
    edges = np.arange(17) / 16.0
    v = np.asarray(v)
    assert (v >= edges[:-1]).all() and (v <= edges[1:] + 1e-6).all()


def test_fr_prefix_queries_cover_radius(table):
    """Prefix block always contains V(g_i) and has width >= Delta_i."""
    c = cfg()
    v = group_representatives(jax.random.key(3), c)
    vq, mask = fr_queries(v, c)
    lo, hi = qz.prefix_range(vq, mask)
    delta = jnp.round((c.lam_fr / c.m) * vq.astype(jnp.float32)).astype(jnp.int32)
    assert bool(jnp.all((vq >= lo) & (vq <= hi)))
    assert bool(jnp.all((hi - lo + 1) >= delta)), "block narrower than Delta"
    assert bool(jnp.all((hi - lo + 1) <= 2 * jnp.maximum(delta, 1))), \
        "block wider than 2*Delta (power-of-2 bound)"


def test_fr_selected_matches_semantics(table):
    pq, valid, _ = table
    c = cfg()
    key = jax.random.key(5)
    res = build_csp_fr(pq, valid, key, c)
    v = group_representatives(jax.random.split(key)[0], c)
    vq, mask = fr_queries(v, c)
    lo, hi = qz.prefix_range(vq, mask)
    expect = ((pq[None, :] >= lo[:, None]) & (pq[None, :] <= hi[:, None])).any(0)
    np.testing.assert_array_equal(np.asarray(res.selected), np.asarray(expect))
    # compacted indices are a subset of the selected ones (rotation-start
    # compaction permutes which survive truncation, not membership)
    sel_idx = set(np.nonzero(np.asarray(expect))[0].tolist())
    got = np.asarray(res.indices[:int(res.count)])
    assert set(got.tolist()) <= sel_idx
    assert len(set(got.tolist())) == int(res.count)


def test_fr_kernel_parity(table):
    pq, valid, _ = table
    c = cfg()
    key = jax.random.key(6)
    a = build_csp_fr(pq, valid, key, c)
    b = build_csp_fr_kernel(pq, valid, key, c)
    np.testing.assert_array_equal(np.asarray(a.selected), np.asarray(b.selected))
    assert int(a.count) == int(b.count)


def test_knn_sort_bisect_equivalence(table):
    pq, valid, _ = table
    key = jax.random.key(7)
    a = build_csp_k(pq, valid, key, cfg(knn_mode="sort"))
    b = build_csp_k(pq, valid, key, cfg(knn_mode="bisect"))
    assert int(a.count) == int(b.count)
    # same multiset of selected slots up to distance ties
    sa = np.asarray(a.selected)
    sb = np.asarray(b.selected)
    assert (sa == sb).mean() > 0.99


def test_knn_sizes_eqn1(table):
    """Per-group kNN subset size follows Eqn 1 within rounding."""
    pq, valid, p = table
    c = cfg(knn_mode="sort", csp_capacity=N)
    key = jax.random.key(8)
    v = group_representatives(jax.random.split(key)[0], c)
    counts = group_counts(pq, valid, c)
    n_i = knn_sizes(v, counts, c)
    res = build_csp_k(pq, valid, key, c)
    # total selected <= sum N_i (union can dedup overlapping groups)
    assert int(res.count) <= int(n_i.sum())
    assert int(res.count) >= int(n_i.sum()) * 0.8


def test_exact_radius_superset_quality(table):
    """Beyond-paper mode: |p-V|<=Delta exactly (no power-of-2 error)."""
    pq, valid, _ = table
    c = cfg(exact_radius=True)
    key = jax.random.key(9)
    res = build_csp_fr(pq, valid, key, c)
    v = group_representatives(jax.random.split(key)[0], c)
    vq = qz.quantize(v, 1.0)
    delta = jnp.round((c.lam_fr / c.m) * vq.astype(jnp.float32)).astype(jnp.int32)
    within = (jnp.abs(pq[None, :] - vq[:, None]) <= delta[:, None]).any(0)
    np.testing.assert_array_equal(np.asarray(res.selected), np.asarray(within))


def test_sampler_prioritizes(table):
    """Sampled mean priority must exceed the buffer mean (and approach
    the ideal E_p[p] = 2/3 for uniform priorities)."""
    _, _, p = table
    for variant in ("fr", "k"):
        s = AmperSampler(cfg(knn_mode="bisect"), variant)
        st = s.update(s.init(), jnp.arange(N), p)
        idx = jax.jit(lambda k: s.sample(st, k, 8192))(jax.random.key(10))
        got = float(p[idx].mean())
        assert got > float(p.mean()) + 0.03, (variant, got)


def test_empty_csp_fallback():
    s = AmperSampler(cfg(), "fr")
    st = s.init()  # nothing valid
    idx = s.sample(st, jax.random.key(0), 64)
    assert idx.shape == (64,)
    assert bool(jnp.all((idx >= 0) & (idx < N)))


def test_update_is_plain_write(table):
    """Sec 3.4.3: update = one row write; value round-trips to quantization."""
    _, _, p = table
    s = AmperSampler(cfg(), "fr")
    st = s.update(s.init(), jnp.arange(N), p)
    st = s.update(st, jnp.array([5]), jnp.array([0.123]))
    got = float(s.priorities(st)[5])
    assert abs(got - 0.123) < 1e-5


@pytest.mark.parametrize("field", ["fr_mode", "knn_mode"])
def test_unknown_search_mode_raises(field):
    """A misspelled mode must fail loudly, not fall through to another
    search path — both through the registry and the free function."""
    from repro.core.samplers import make_sampler

    with pytest.raises(ValueError, match=f"unknown {field}"):
        make_sampler("amper-fr", 256, **{field: "fussed"})
    cfg = AmperConfig(capacity=256)._replace(**{field: "fussed"})
    with pytest.raises(ValueError, match=f"unknown {field}"):
        build_csp_fr(jnp.zeros(256, jnp.int32), jnp.ones(256, bool),
                     jax.random.key(0), cfg)
