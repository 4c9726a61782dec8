"""Plain reference of a size-aware W-TinyLFU cache, independent of the program.

Written from the paper (arXiv:2105.08770, Sections 3-5, Algorithms 1-4) and
the configuration file alone; it imports nothing of the code under test and
takes nothing it made. One access at a time, in plain Python:

* a count-min sketch of ``rows`` rows, each the power of two at or above
  ``counters_per_entry * entries`` wide, double hashing of the key's low 32
  bits with the murmur3 32-bit finalizer, non-conservative saturating
  increments at ``cap``, and aging: every ``sample_per_entry * entries``
  increments each counter is halved and the increment count halves too;
* a Window LRU of ``window_frac`` of the capacity in front of a Main cache;
* Algorithm 1's miss cascade: an object larger than the cache is rejected,
  one larger than the Window goes straight to Main, and every Window victim
  becomes a Main candidate; a candidate that fits Main's free bytes is
  admitted without a decision;
* admission by IV (first victim), QV (queue of victims, evictions stick)
  or AV (aggregated victims, with early pruning), each against the Main
  eviction order;
* Main eviction by SLRU (probation LRU, then protected LRU; a probation hit
  moves to protected, which demotes its LRU entries past its byte share)
  or by sampling (Ristretto-style: five slots drawn per step from a
  splitmix64 counter stream keyed on the seed and the decision number, the
  lowest score taken; a slot-ordered key list with swap-remove).

``run(keys, sizes)`` returns the hit stream; :meth:`Cache.state` the
counters, sketch and contents that the benchmark compares.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

_M64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15
_GAMMA = 0xD2B74407B1CE6E93
_MIX1 = 0xBF58476D1CE4E5B9
_MIX2 = 0x94D049BB133111EB

STAT_FIELDS = ("accesses", "hits", "bytes_hit", "bytes_requested",
               "victims_examined", "admissions", "rejections", "evictions")


def mix64(x: int) -> int:
    """splitmix64's finalizer (Stafford mix13)."""
    x ^= x >> 30
    x = (x * _MIX1) & _M64
    x ^= x >> 27
    x = (x * _MIX2) & _M64
    return x ^ (x >> 31)


def mix64_array(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x.astype(np.uint64, copy=True)
        x ^= x >> np.uint64(30)
        x *= np.uint64(_MIX1)
        x ^= x >> np.uint64(27)
        x *= np.uint64(_MIX2)
        x ^= x >> np.uint64(31)
    return x


def shard_of(keys: np.ndarray, shards: int, salt: int = 0) -> np.ndarray:
    """Hash partition: splitmix64 of the key plus ``salt * golden``, mod the
    shard count."""
    u = np.asarray(keys, np.int64).view(np.uint64)
    with np.errstate(over="ignore"):
        u = u + np.uint64((salt * _GOLDEN) & _M64)
    return (mix64_array(u) % np.uint64(shards)).astype(np.int64)


def _fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        x ^= x >> np.uint32(16)
    return x


class Sketch:
    """Count-min sketch as one flat list of ``rows * width`` counters."""

    def __init__(self, entries: int, *, rows: int, cap: int, counters_per_entry: int,
                 sample_per_entry: int, min_width: int):
        width = min_width
        while width < counters_per_entry * entries:
            width <<= 1
        self.rows, self.width, self.cap = rows, width, cap
        self.sample_size = sample_per_entry * entries
        self.table = [0] * (rows * width)
        self.ops = 0
        self.resets = 0

    def cells(self, keys: np.ndarray) -> np.ndarray:
        """``[n, rows]`` flat counter indexes of each key."""
        lo = (np.asarray(keys, np.int64).view(np.uint64)
              & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        h1 = _fmix32(lo)
        h2 = _fmix32(lo ^ np.uint32(0x9E3779B9)) | np.uint32(1)
        r = np.arange(self.rows, dtype=np.uint32)
        with np.errstate(over="ignore"):
            col = (h1[:, None] + r[None, :] * h2[:, None]) & np.uint32(self.width - 1)
        return col.astype(np.int64) + (r.astype(np.int64) * self.width)[None, :]

    def increment(self, cells) -> None:
        t, cap = self.table, self.cap
        for c in cells:
            v = t[c]
            if v < cap:
                t[c] = v + 1
        self.ops += 1
        if self.ops == self.sample_size:
            self.table = [v >> 1 for v in t]
            self.ops //= 2
            self.resets += 1

    def estimate(self, cells) -> int:
        t = self.table
        return min(t[c] for c in cells)


class SLRU:
    def __init__(self, capacity: int, protected_frac: float):
        self.protected_cap = int(capacity * protected_frac)
        self.probation: OrderedDict = OrderedDict()
        self.protected: OrderedDict = OrderedDict()
        self.sizes: dict = {}
        self.used = 0
        self.protected_bytes = 0

    def insert(self, key, size):
        self.sizes[key] = size
        self.used += size
        self.probation[key] = None

    def evict(self, key):
        size = self.sizes.pop(key)
        self.used -= size
        if key in self.probation:
            del self.probation[key]
        else:
            del self.protected[key]
            self.protected_bytes -= size

    def on_hit(self, key):
        if key in self.protected:
            self.protected.move_to_end(key)
            return
        del self.probation[key]
        self.protected[key] = None
        self.protected_bytes += self.sizes[key]
        while self.protected_bytes > self.protected_cap and len(self.protected) > 1:
            old, _ = self.protected.popitem(last=False)
            self.protected_bytes -= self.sizes[old]
            self.probation[old] = None

    def promote(self, key):
        if key in self.protected:
            self.protected.move_to_end(key)
        else:
            self.probation.move_to_end(key)

    def begin_decision(self):
        pass

    def victims(self, needed, score_of):
        yield from self.probation
        yield from self.protected

    def contents(self):
        """``(key, size, segment)`` rows: probation then protected, each
        LRU first; segment 1 is protected."""
        return ([(k, self.sizes[k], 0) for k in self.probation]
                + [(k, self.sizes[k], 1) for k in self.protected])


class Sampled:
    """Sampling main over a slot-ordered key list (swap-remove on evict)."""

    def __init__(self, rule: str, sample: int, seed: int):
        self.rule, self.sample, self.seed = rule, sample, seed
        self.keys: list = []
        self.pos: dict = {}
        self.sizes: dict = {}
        self.used = 0
        self.decision = 0

    def insert(self, key, size):
        self.sizes[key] = size
        self.used += size
        self.pos[key] = len(self.keys)
        self.keys.append(key)

    def evict(self, key):
        self.used -= self.sizes.pop(key)
        i = self.pos.pop(key)
        last = self.keys.pop()
        if last != key:
            self.keys[i] = last
            self.pos[last] = i

    def on_hit(self, key):
        pass

    def promote(self, key):
        pass

    def begin_decision(self):
        self.decision += 1

    def _score(self, key, needed, freq_of):
        size = self.sizes[key]
        rule = self.rule
        if rule == "frequency":
            return freq_of(key)
        if rule == "size":
            return -size
        if rule == "frequency_size":
            return freq_of(key) / size
        if rule == "needed_size":
            return abs(size - needed)
        raise ValueError(f"unknown sampling rule {rule!r}")

    def victims(self, needed, freq_of):
        """Distinct victims of the current decision, drawing its counter
        stream from index 0 over the key list as it stands (callers evict
        only after the walk)."""
        keys = self.keys
        n = len(keys)
        base = mix64((self.seed * _GOLDEN + self.decision * _GAMMA) & _M64)
        sample = self.sample
        taken: set = set()
        step = 0
        while len(taken) < n:
            start = step * sample
            raw = [keys[mix64(base ^ (((start + j) * _GAMMA) & _M64)) % n]
                   for j in range(sample)]
            step += 1
            pool = [k for k in raw if k not in taken]
            if not pool:  # every draw already taken: scan in slot order
                pool = [k for k in keys if k not in taken]
            best = min(pool, key=lambda k: self._score(k, needed, freq_of))
            taken.add(best)
            yield best

    def contents(self):
        """``(key, size, 0)`` rows in slot order."""
        return [(k, self.sizes[k], 0) for k in self.keys]


class Cache:
    """One size-aware W-TinyLFU cache, per the configuration's ``reference``
    block."""

    def __init__(self, ref: dict, capacity: int, entries: int):
        sk = ref["sketch"]
        self.sketch = Sketch(entries, **sk)
        if ref.get("adaptive_window"):
            raise NotImplementedError("the reference has no adaptive window")
        self.capacity = int(capacity)
        self.window_cap = max(1, int(capacity * ref["window_frac"]))
        self.main_cap = self.capacity - self.window_cap
        self.admission = ref["admission"]
        self.early_pruning = bool(ref.get("early_pruning", True))
        main = ref["main"]
        if main == "slru":
            self.main = SLRU(self.main_cap, ref["protected_frac"])
        elif main.startswith("sampled_"):
            self.main = Sampled(main.removeprefix("sampled_"), ref["sample"], ref["seed"])
        else:
            raise NotImplementedError(f"the reference has no main {main!r}")
        self.window: OrderedDict = OrderedDict()
        self.window_bytes = 0
        self.stats = dict.fromkeys(STAT_FIELDS, 0)
        self._cells: dict = {}  # key -> its counter indexes, while resident

    # -- sketch reads of resident keys ---------------------------------
    def _freq(self, key) -> int:
        return self.sketch.estimate(self._cells[key])

    def run(self, keys: np.ndarray, sizes: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.int64)
        cells = self.sketch.cells(keys).tolist()
        hits = np.zeros(len(keys), bool)
        st = self.stats
        window, main = self.window, self.main
        increment = self.sketch.increment
        for i, (key, size) in enumerate(zip(keys.tolist(), np.asarray(sizes).tolist())):
            c = cells[i]
            st["accesses"] += 1
            st["bytes_requested"] += size
            increment(c)
            if key in window:
                window.move_to_end(key)
            elif key in main.sizes:
                main.on_hit(key)
            else:
                self._miss(key, size, c)
                continue
            hits[i] = True
            st["hits"] += 1
            st["bytes_hit"] += size
        return hits

    def _miss(self, key, size, cells) -> None:
        if size > self.capacity:
            self.stats["rejections"] += 1
            return
        self._cells.setdefault(key, cells)
        if size > self.window_cap:
            self._offer(key, size)
            return
        self.window[key] = size
        self.window_bytes += size
        out = []
        while self.window_bytes > self.window_cap:
            vk, vs = self.window.popitem(last=False)
            self.window_bytes -= vs
            out.append((vk, vs))
        for vk, vs in out:
            self._offer(vk, vs)

    def _offer(self, key, size) -> None:
        st, main = self.stats, self.main
        if size > self.main_cap:
            st["rejections"] += 1
            self._forget(key)
            return
        free = self.main_cap - main.used
        if free >= size:
            main.insert(key, size)
            st["admissions"] += 1
            return
        main.begin_decision()
        needed = size - free
        admitted = getattr(self, "_" + self.admission)(key, needed)
        if admitted:
            main.insert(key, size)
            st["admissions"] += 1
        else:
            st["rejections"] += 1
            self._forget(key)

    def _forget(self, key) -> None:
        if key not in self.window and key not in self.main.sizes:
            self._cells.pop(key, None)

    def _evict(self, victims) -> None:
        for v in victims:
            self.main.evict(v)
            self.stats["evictions"] += 1
            self._forget(v)

    def _iv(self, key, needed) -> bool:
        main, st = self.main, self.stats
        walk = main.victims(needed, self._freq)
        first = next(walk)
        st["victims_examined"] += 1
        if self._freq(key) < self._freq(first):
            main.promote(first)
            return False
        gathered, covered = [first], main.sizes[first]
        for v in walk:
            if covered >= needed:
                break
            gathered.append(v)
            covered += main.sizes[v]
        self._evict(gathered)
        return True

    def _qv(self, key, needed) -> bool:
        main, st = self.main, self.stats
        cand = self._freq(key)
        freed, beaten, loser = 0, [], None
        for v in main.victims(needed, self._freq):
            if freed >= needed:
                break
            st["victims_examined"] += 1
            if cand >= self._freq(v):
                beaten.append(v)
                freed += main.sizes[v]
            else:
                loser = v
                break
        self._evict(beaten)  # QV's evictions stick, admitted or not
        if loser is not None:
            main.promote(loser)
        return freed >= needed

    def _av(self, key, needed) -> bool:
        main, st = self.main, self.stats
        cand = self._freq(key)
        gathered, vbytes, vfreq, pruned = [], 0, 0, True
        for v in main.victims(needed, self._freq):
            gathered.append(v)
            vbytes += main.sizes[v]
            vfreq += self._freq(v)
            st["victims_examined"] += 1
            if self.early_pruning and cand < vfreq:
                break
            if vbytes >= needed:
                pruned = False
                break
        if not pruned and cand >= vfreq:
            self._evict(gathered)
            return True
        for v in gathered:
            main.promote(v)
        return False

    def state(self) -> dict:
        """Counters, sketch and contents, in the form the benchmark compares."""
        return {"stats": dict(self.stats),
                "table": np.asarray(self.sketch.table, np.int32).reshape(
                    self.sketch.rows, self.sketch.width),
                "resets": self.sketch.resets,
                "window": list(self.window.items()),
                "main": self.main.contents()}
