"""The trace reduction on a small synthetic trace (CPU)."""
import pytest

from bench import trace as T


def _trace():
    dev0 = T.Device(
        modules=[("jit_sample_slab(3)", 100, 300), ("jit_learn_slab(7)", 400, 500),
                 ("jit_sample_slab(3)", 600, 700), ("jit_apply_feedback(9)", 900, 950)],
        ops=[("fusion.1", 100, 250), ("all-gather.2", 240, 300),
             ("convolution", 400, 500), ("all-reduce-start", 600, 700),
             ("scatter", 900, 950)])
    dev1 = T.Device(modules=[("jit_sample_slab(3)", 110, 290)],
                    ops=[("fusion.1", 110, 290)])
    spans = [("bench_window", 100, 1000), ("learn", 290, 420),
             ("apply_feedback", 700, 900), ("slab_draw", 650, 1000)]
    return T.Trace(devices={0: dev0, 1: dev1}, spans=spans)


def test_union_clip_and_idle_share():
    tr = _trace()
    w = T.window_of(tr)
    assert w == (100, 1000)
    assert T.merge([(5, 9), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 9)]
    assert T.length(T.busy(tr.devices[0], w)) == 200 + 100 + 100 + 50
    assert T.idle_share(tr.devices[0], w) == pytest.approx(1 - 450 / 900)


def test_program_times_strip_jit_and_id():
    tr = _trace()
    w = T.window_of(tr)
    assert T.program_name("jit_learn_slab(7)") == "learn_slab"
    assert T.program_times(tr.devices[0], "sample_slab", w) == [200, 100]
    assert T.program_times(tr.devices[0], "apply_feedback", (0, 800)) == []


def test_exposed_collective_subtracts_overlap():
    tr = _trace()
    w = T.window_of(tr)
    # all-gather 240-300 overlaps fusion until 250: 50 exposed; the
    # all-reduce 600-700 runs alone: 100 exposed.
    assert T.exposed_collective(tr.devices[0], w) == 150


def test_top_ops_and_idle_gaps_named_by_host_span():
    tr = _trace()
    w = T.window_of(tr)
    top = T.top_ops(tr.devices[0], w, n=2)
    assert [n for n, _ in top] == ["fusion.1", "convolution"]
    assert top[0][1] == pytest.approx(150e-9)
    names = {"learn", "apply_feedback", "slab_draw"}
    gaps = T.idle_gaps(tr.devices[0], tr.spans, w, names, n=3)
    # gaps: 700-900 (200, inside apply_feedback and slab_draw: the
    # innermost is apply_feedback), 300-400 (100, learn), 500-600.
    assert gaps[0] == ["apply_feedback", pytest.approx(200e-9)]
    assert gaps[1] == ["learn", pytest.approx(100e-9)]
    assert len(gaps) == 3
    assert T.idle_gaps(tr.devices[0], tr.spans, w, set(), n=1)[0][0] \
        == "other"


def test_window_missing_raises():
    with pytest.raises(ValueError):
        T.window_of(T.Trace())
