"""Seeded access streams of one paper trace class, for as long as a run needs.

The benchmark's own copy of the synthetic trace generator (paper Table 1
classes, scaled): a class is a Zipf popularity over a fixed object
universe, a one-hit-wonder tail, a recency process that repeats a recent
access at a geometric backward distance, and per-object sizes drawn once.

Segment 0 is the class trace itself, access for access and byte for byte.
Later segments draw again from the same universe (same ids, same sizes,
same popular set and one-hit objects), each from a random generator of its
own, so a stream can continue past the class trace's length without the
yardstick moving. A run's seed labels the objects of one fixed universe
(:class:`Stream`), so that seeds change the keys and not the work.

Every parameter comes from the traffic file (``chipbench/traffic/<name>.json``,
key ``"class"``); this module holds no class of its own.
"""

from __future__ import annotations

import zlib

import numpy as np

_ID_MULT = np.int64(2654435761)  # odd: x -> x*c mod 2^40 is a bijection
_ID_SPACE = np.int64(1 << 40)


def _sample_sizes(cls: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    kind, p = cls["size_kind"], cls["size_params"]
    if kind == "clustered":
        centers = np.array([c for c, _ in p], dtype=np.float64)
        weights = np.array([w for _, w in p], dtype=np.float64)
        weights /= weights.sum()
        idx = rng.choice(len(centers), size=n, p=weights)
        jitter = rng.lognormal(0.0, 0.08, size=n)
        return np.maximum(64, centers[idx] * jitter).astype(np.int64)
    if kind == "lognormal":
        mu, sigma, lo, hi = p
        return np.clip(rng.lognormal(mu, sigma, size=n), lo, hi).astype(np.int64)
    if kind == "heavytail":
        mu, sigma, lo, hi, pareto_a, tail_frac = p
        body = rng.lognormal(mu, sigma, size=n)
        tail = lo * 1024 * (1.0 + rng.pareto(pareto_a, size=n))
        sizes = np.where(rng.random(n) < tail_frac, tail, body)
        return np.clip(sizes, lo, hi).astype(np.int64)
    raise ValueError(f"unknown size kind {kind!r}")


def _zipf_pmf(n: int, alpha: float) -> np.ndarray:
    pmf = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    return pmf / pmf.sum()


def _index_stream(cls: dict, rng: np.random.Generator, n_acc: int,
                  n_obj: int, n_popular: int) -> np.ndarray:
    """Zipf + one-hit-wonder + recency stream of object indexes in
    ``[0, n_obj)``, drawing from ``rng`` in the class generator's order."""
    base = rng.choice(n_popular, size=n_acc, p=_zipf_pmf(n_popular, cls["zipf_alpha"]))
    n_ohw = n_obj - n_popular
    if n_ohw > 0:
        pos = rng.choice(n_acc, size=min(n_ohw, n_acc // 4), replace=False)
        base[pos] = n_popular + np.arange(len(pos))
    rec_mask = rng.random(n_acc) < cls["p_recency"]
    back = rng.geometric(1.0 / cls["recency_scale"], size=n_acc)
    src = np.arange(n_acc) - back
    # access i repeats access src[i] (which may itself repeat an earlier
    # one): follow each chain to its root by pointer jumping
    root = np.where(rec_mask & (src >= 0), src, np.arange(n_acc))
    while True:
        nxt = root[root]
        if np.array_equal(nxt, root):
            break
        root = nxt
    return base[root]


class Stream:
    """The object universe of one class, its access segments, and the keys
    that ``seed`` gives its objects.

    The universe (each object's popularity rank and size) and the arrivals
    (the ranks accessed, in order) are drawn from ``universe``; ``seed``
    only labels the objects, deciding which key plays which part. So every
    seed drives the same work under other keys. Where ``universe`` is
    ``seed`` (the default), segment 0 is the class trace of that seed."""

    def __init__(self, cls: dict, seed: int, universe: int | None = None):
        self.cls = cls
        self.seed = int(seed)
        self.universe = self.seed if universe is None else int(universe)
        self._tag = zlib.crc32(cls["name"].encode()) & 0x7FFFFFFF
        rng = np.random.default_rng([self.universe, self._tag])
        self.n_acc = int(cls["n_accesses"])
        self.n_obj = int(cls["n_objects"])
        self.n_popular = max(10, int(self.n_obj * (1.0 - cls["one_hit_frac"])))
        labels = rng.permutation(self.n_obj).astype(np.int64)
        self._first = _index_stream(cls, rng, self.n_acc, self.n_obj, self.n_popular)
        #: size by rank: the class generator sizes objects by their label
        self.rank_sizes = _sample_sizes(cls, self.n_obj, rng)[labels]
        #: label by rank, from the seed (the universe's own where they agree)
        self.ids = np.random.default_rng([self.seed, self._tag]).permutation(
            self.n_obj).astype(np.int64)

    def _ranks_of_segment(self, k: int) -> np.ndarray:
        if k == 0:
            return self._first
        rng = np.random.default_rng([self.universe, self._tag, k])
        return _index_stream(self.cls, rng, self.n_acc, self.n_obj, self.n_popular)

    def segment(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """Keys and sizes (int64) of segment ``k``; segment 0 is the class
        trace."""
        ranks = self._ranks_of_segment(k)
        return self.ids[ranks] * _ID_MULT % _ID_SPACE, self.rank_sizes[ranks]

    def take(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """The first ``n`` accesses of the stream."""
        keys, sizes, have, k = [], [], 0, 0
        while have < n:
            kk, ss = self.segment(k)
            keys.append(kk)
            sizes.append(ss)
            have += len(kk)
            k += 1
        return np.concatenate(keys)[:n], np.concatenate(sizes)[:n]

    def class_trace_stats(self) -> tuple[int, float]:
        """Total bytes and mean size of the objects the class trace
        (segment 0) touches: what the capacity rule sizes against."""
        keys, sizes = self.segment(0)
        _, first = np.unique(keys, return_index=True)
        seen = sizes[first]
        return int(seen.sum()), float(seen.mean())
