"""The benchmark's traffic generator reproduces the program's trace classes
byte for byte, and continues past them from the same object universe."""

import json

import numpy as np
import pytest

from conftest import BENCH

from harness import load_module

gen = load_module(BENCH / "traffic" / "paper_class.py", "paper_class_under_test")


def _class(traffic):
    return json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())["class"]


@pytest.mark.parametrize("traffic,n", [("msr2", 900_000), ("tencent1", 1_200_000)])
@pytest.mark.parametrize("seed", [0, 2**31 + 977])
def test_segment0_is_the_class_trace(traffic, n, seed):
    from repro.traces import make_trace

    cls = _class(traffic)
    stream = gen.Stream(cls, seed)
    keys, sizes = stream.take(n)
    tr = make_trace(cls["name"], seed=seed, scale=1)
    assert len(tr) == n
    assert keys.dtype == tr.keys.dtype and sizes.dtype == tr.sizes.dtype
    assert np.array_equal(keys, tr.keys)
    assert np.array_equal(sizes, tr.sizes)
    assert stream.class_trace_stats() == (tr.total_object_bytes, tr.mean_object_size)


@pytest.mark.parametrize("traffic", ["msr2", "tencent1"])
def test_stream_continues_from_the_same_universe(traffic):
    cls = _class(traffic)
    stream = gen.Stream(cls, 7)
    n0 = cls["n_accesses"]
    keys, sizes = stream.take(3 * n0 + 5)
    assert len(keys) == 3 * n0 + 5
    universe = stream.ids * gen._ID_MULT % gen._ID_SPACE
    size_of = dict(zip(universe.tolist(), stream.rank_sizes.tolist()))
    later = keys[n0:]
    assert np.isin(later, universe).all()
    assert all(size_of[k] == s for k, s in zip(later[:50_000].tolist(),
                                                sizes[n0:n0 + 50_000].tolist()))
    # a new draw, not the class trace again, with the class's popularity
    assert not np.array_equal(keys[n0:2 * n0], keys[:n0])
    top0 = np.unique(keys[:n0], return_counts=True)
    top1 = np.unique(keys[n0:2 * n0], return_counts=True)
    hot0 = set(top0[0][np.argsort(-top0[1])[:100]].tolist())
    hot1 = set(top1[0][np.argsort(-top1[1])[:100]].tolist())
    assert len(hot0 & hot1) > 80


def test_same_seed_same_stream_other_seed_other_stream():
    cls = _class("msr2")
    a = gen.Stream(cls, 5).take(1_000_000)
    b = gen.Stream(cls, 5).take(1_000_000)
    c = gen.Stream(cls, 6).take(1_000_000)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("traffic", ["msr2", "tencent1"])
def test_a_seed_labels_one_universe(traffic):
    """Under a fixed universe every seed drives the same arrivals and sizes
    under keys of its own: a one-to-one relabelling of the same key set."""
    cls = _class(traffic)
    n = cls["n_accesses"] + 1000  # into the second segment
    base = gen.Stream(cls, 0).take(n)
    for seed in (0, 2**31 + 977):
        keys, sizes = gen.Stream(cls, seed, universe=0).take(n)
        assert np.array_equal(sizes, base[1])
        fwd = dict(zip(base[0].tolist(), keys.tolist()))
        assert all(fwd[a] == b for a, b in zip(base[0].tolist(), keys.tolist()))
        assert len(set(fwd.values())) == len(fwd)
        assert (seed == 0) == np.array_equal(keys, base[0])
    assert gen.Stream(cls, 9, universe=0).class_trace_stats() == \
        gen.Stream(cls, 0).class_trace_stats()
