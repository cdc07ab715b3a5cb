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


_SAMPLE_MODES = ([("fr", {"fr_mode": m})
                  for m in ("broadcast", "interval", "window", "kernel")]
                 + [("k", {"knn_mode": m})
                    for m in ("sort", "bisect", "hist")])
_SAMPLE_CASES = ("plain", "truncated", "empty", "all_valid", "shift_first",
                 "shift_last", "batch_over_count", "ragged")


def _key_with_shift(n: int, target: int) -> jax.Array:
    """A draw key whose compaction rotation (kcsp -> kroll) is ``target``."""
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(11), i))(
        jnp.arange(40_000))

    def shift(k):
        kroll = jax.random.split(jax.random.split(k)[0])[1]
        return jax.random.randint(kroll, (), 0, n)

    hits = np.flatnonzero(np.asarray(jax.vmap(shift)(keys)) == target)
    assert hits.size, f"no key among 40,000 rotates by {target}"
    return keys[int(hits[0])]


@pytest.mark.parametrize("case", _SAMPLE_CASES)
@pytest.mark.parametrize("variant,mode", _SAMPLE_MODES,
                         ids=[m for _, d in _SAMPLE_MODES for m in d.values()])
def test_sample_equals_compacted_csp(variant, mode, case):
    """The draw rank-selects members from the mask; it must equal the
    compacted-buffer draw ``sample_from_csp(build_csp(...))`` under the
    same key tree, in every search mode and at every edge of the pick."""
    from repro.core.amper import _rank_block

    n = 1000 if case == "ragged" else 1024
    assert (n % _rank_block(n) != 0) == (case == "ragged")
    batch = 64
    csp_capacity = {"truncated": 16, "batch_over_count": 3}.get(case, n)
    c = AmperConfig(capacity=n, m=8, lam=0.15, lam_fr=2.0, v_max=1.0,
                    csp_capacity=csp_capacity, **mode)
    s = AmperSampler(c, variant)
    p = jax.random.uniform(jax.random.key(2), (n,)) + 0.01
    valid = {"empty": jnp.zeros(n, bool), "all_valid": jnp.ones(n, bool)}.get(
        case, jax.random.bernoulli(jax.random.key(3), 0.7, (n,)))
    st = s.update(s.init(), jnp.arange(n), jnp.where(valid, p, 0.0))
    key = {"shift_first": lambda: _key_with_shift(n, 0),
           "shift_last": lambda: _key_with_shift(n, n - 1)}.get(
        case, lambda: jax.random.key(4))()

    @jax.jit
    def compacted(state, k):
        kcsp, kpick = jax.random.split(k)
        csp = s.build_csp(state, kcsp)
        live = jnp.sum(state.valid.astype(jnp.int32))
        return (sample_from_csp(csp, kpick, batch, live), csp.count,
                jnp.sum(csp.selected))

    got = jax.jit(lambda state, k: s.sample(state, k, batch))(st, key)
    want, count, members = compacted(st, key)
    count, members = int(count), int(members)
    assert {"empty": count == 0,
            "truncated": members > count == csp_capacity,
            "batch_over_count": 0 < count < batch}.get(
        case, 0 < count == members), (case, count, members)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_draw_lowers_without_csp_buffer():
    """At the benchmark's size (1M rows, CSP capacity 150,000, a slab of
    256 draws) the draw holds no scatter and no CSP-sized buffer: the
    ``nonzero`` compaction it replaced was a 1M-update scatter."""
    import re

    n, csp_capacity, batch = 1_000_000, 150_000, 256
    s = AmperSampler(AmperConfig(capacity=n, m=20, lam_fr=2.0, v_max=8.0,
                                 csp_capacity=csp_capacity), "fr")
    state = type(s.init())(jax.ShapeDtypeStruct((n,), jnp.int32),
                           jax.ShapeDtypeStruct((n,), jnp.bool_))
    key = jax.eval_shape(lambda: jax.random.key(0))
    buffer = re.compile(rf"tensor<(\d+x)*{csp_capacity}[x>]")

    def lowered(fn):
        return jax.jit(fn).lower(state, key).as_text()

    draw = lowered(lambda st, k: s.sample(st, k, batch))
    assert "scatter" not in draw
    assert not buffer.search(draw)
    # the pattern does find the buffer where it is built
    assert buffer.search(lowered(lambda st, k: s.build_csp(st, k).indices))
