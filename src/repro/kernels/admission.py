"""Admission decisions on the device: victim walks, verdict replay, and
the per-decision plane.

Every device program that decides which victims to evict reaches the
same pieces here:

* :func:`_freq_of` — a frequency estimate as a plain gather of the
  flushed CMS table;
* :func:`_sample_picker` — the sampled mains' next victim: the
  counter-RNG pool draw (``repro.core.crng``'s splitmix64 stream,
  reproduced with the uint32-limb helpers behind
  ``kernels.cms.ops.counter_draws``), the eviction rule's score, and the
  deterministic already-taken fallback scan;
* :func:`_recency_select` — the LRU/SLRU mains' next victim: the oldest
  live stamp, probation first for SLRU.

On them sit the two walks, :func:`_walk_sampled_sel` and
:func:`_walk_prefix_sel`, which replay the IV/QV/AV verdict with the
per-discipline stop rule inside one ``lax.while_loop`` and record the
eviction order as ``sel[slot] = order``. The chunk program of
``data_plane="device_full"`` (:mod:`repro.kernels.device_full`) runs them
in its scan body, its adaptive-window drain runs the picker and the
selector, and the per-decision kernels below run the walks too.

:class:`DeviceAdmissionPlane` decides one admission at a time, as one
jitted call that returns only the verdict; the host applies it to the
(authoritative) eviction-policy structures. It is ``device_full``'s host
path: scalar ``access`` and the boundary access of every sketch aging
reset decide through it. Two decision kernels cover the admission x
eviction grid:

* ``_decide_sampled`` — sampling mains (``SampledEviction``/``Random``).
  The plane keeps a :class:`DeviceMirror` of the policy's slot-addressed
  ``keys``/``sizes`` swap-remove table, maintained incrementally by the
  policy's insert/evict hooks (dirty slots land as a masked scatter inside
  the next decision call; the arrays themselves stay device-resident
  between decisions). The sample walk's ``sel`` becomes the ordered
  victim slots with one scatter.
* ``_decide_prefix`` — deterministic-order mains (LRU/SLRU). Their victim
  order lives in host order dicts (control plane), so the host hands the
  covering victim prefix (``EvictionPolicy.peek_victims``) to the kernel,
  which scores candidate + prefix against the freshly flushed table and
  replays the IV/QV/AV verdict with masked prefix scans (cumulative sizes
  for QV's first-loss stop, cumulative frequencies for AV's early-pruning
  stop) — still one jitted call, no per-victim host round-trips.

Byte-identity with the scalar walk rests on the same arguments as the
batched plane (see :mod:`repro.core.admission`): estimates are pure reads
of the flushed table, victim order is a peek-stable replay, and exactly one
flush (split at aging-reset boundaries) precedes the first estimate of a
decision. Score comparisons that the host performs in Python arithmetic
are done with **exact integer cross-multiplication** on device (``a/b <
c/d  <=>  a*d < c*b``): float32 division could reorder near-equal
``frequency_size`` ratios, int32 products cannot (exact while
``freq * size < 2**31``, i.e. any realistic counter cap x object size).

Limits (each raises ``ValueError``, never silently wrong): object sizes
and ``needed`` are checked against the exact-arithmetic bound
``(2**31 - 1) // sketch.cap``; the entry count must stay below
:data:`MAX_MIRROR_ENTRIES` (the 8-bit-Horner ``draw mod n`` is exact for
``n < 2**24``). Keys of any width are accepted — they reach the sketch
through the same int32 hash-input truncation as ``CMSSketch``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import crng

from .cms.ops import _mix64_u32, _mul64_const, flush_scores
from .cms.ref import row_indexes

__all__ = ["DeviceAdmissionPlane", "DeviceMirror", "MAX_MIRROR_ENTRIES"]

#: ``draw mod n`` is computed in uint32 8-bit Horner steps — exact for
#: entry counts below 2**24 (16M cached objects).
MAX_MIRROR_ENTRIES = 1 << 24
#: Dirty-slot scatter budget per decision call; a burstier mutation window
#: re-uploads the whole mirror instead (still one decision call).
_WRITE_PAD = 64
_I32_MAX = np.int32(2**31 - 1)


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def _key32(key: int) -> np.int32:
    """The CMS hash-input truncation (identical to ``CMSSketch``'s
    ``int64 -> int32`` cast) for arbitrary python ints."""
    return np.asarray(key & 0xFFFFFFFF, np.uint32).astype(np.int32)[()]


# -- in-kernel building blocks ----------------------------------------------

def _mod_u64(hi, lo, n):
    """``(hi, lo)`` uint64 mod ``n`` for ``1 <= n < 2**24``, exact in uint32.

    8-bit Horner over the limbs: the running remainder stays below ``n``,
    so ``(r << 8) | limb`` never overflows uint32.
    """
    r = jnp.zeros_like(lo)
    for word, shift in ((hi, 24), (hi, 16), (hi, 8), (hi, 0),
                        (lo, 24), (lo, 16), (lo, 8), (lo, 0)):
        r = ((r << jnp.uint32(8)) | ((word >> jnp.uint32(shift)) & jnp.uint32(0xFF))) % n
    return r


def _step_slots(base_hi, base_lo, start, sample: int, n):
    """Slots drawn at stream indexes ``start .. start+sample-1`` — the
    device twin of ``crng.draws(seed, decision, start, sample) % n``."""
    i = jnp.uint32(start) + jnp.arange(sample, dtype=jnp.uint32)
    mhi, mlo = _mul64_const(jnp.zeros_like(i), i, crng.GAMMA)
    hi, lo = _mix64_u32(mhi ^ base_hi, mlo ^ base_lo)
    return _mod_u64(hi, lo, n).astype(jnp.int32)


def _argmin_frac(num, den, pos, valid):
    """Position of the minimal ``num/den`` among ``valid`` entries, ties to
    the smallest ``pos`` — a power-of-two tournament using exact int32
    cross-multiplication (valid ``den`` > 0; invalid entries become the
    ``1/0`` = +inf sentinel, so an all-invalid input returns the sentinel
    ``pos`` — callers guard with ``valid.any()``)."""
    num = jnp.where(valid, num, jnp.int32(1))
    den = jnp.where(valid, den, jnp.int32(0))
    pos = jnp.where(valid, pos, _I32_MAX)
    length = num.shape[0]
    while length > 1:
        half = length // 2
        n1, n2 = num[:half], num[half:length]
        d1, d2 = den[:half], den[half:length]
        p1, p2 = pos[:half], pos[half:length]
        x, y = n1 * d2, n2 * d1
        a_wins = (x < y) | (~(y < x) & (p1 <= p2))
        num = jnp.where(a_wins, n1, n2)
        den = jnp.where(a_wins, d1, d2)
        pos = jnp.where(a_wins, p1, p2)
        length = half
    return pos[0]


def _freq_of(table, keys32):
    """Frequency estimates as pure gathers of the flushed table — value-
    identical to the estimate kernels."""
    idx = row_indexes(keys32, table.shape[1])
    return jnp.take_along_axis(table, idx, axis=1).min(0)


# -- victim walks (pure: record a ``sel`` eviction order, mutate nothing) ----

def _sample_picker(table, mk_lo, msz, n, needed, base_hi, base_lo, *, rule,
                   sample):
    """The sampled mains' victim picker over the first ``n`` slots:
    returns ``next_victim(taken, step, fallbacks) -> (best, step + 1,
    fallbacks')``. Step ``step`` draws its pool of ``sample`` slots from
    the counter-RNG stream ``(base_hi, base_lo)`` (the device twin of
    ``crng.draws``), scores the untaken ones by ``rule`` exactly as the
    host ``SampledEviction._score`` orders them (ascending = evict first)
    and takes the lowest; when every draw hit a taken slot, the
    deterministic linear scan over all live slots picks instead and
    ``fallbacks`` counts it."""
    slots = mk_lo.shape[0]
    # The draw modulus: n >= 1 whenever a walk actually runs (needed > 0
    # implies a non-empty main); the clamp only guards masked-out scan
    # lanes from an integer mod-by-zero.
    n_mod = jnp.maximum(n, 1).astype(jnp.uint32)

    def scores_of(slot_arr):
        """``(num, den)`` fractions for the given slots. Scoring is
        per-pool, not per-table: a decision only ever touches ~SAMPLE x
        steps slots, so the walk must not do O(entries) sketch work (the
        all-slot form runs only under the rare fallback scan)."""
        sz = msz[slot_arr]
        one = jnp.ones_like(sz)
        if rule == "frequency":
            return _freq_of(table, mk_lo[slot_arr]), one
        if rule == "size":
            return -sz, one
        if rule == "frequency_size":
            return _freq_of(table, mk_lo[slot_arr]), sz
        if rule == "needed_size":
            return jnp.abs(sz - needed), one
        return jnp.zeros_like(sz), one  # random: constant, first draw wins

    iota = jnp.arange(slots, dtype=jnp.int32)
    in_use = iota < n
    pool_pad = _next_pow2(sample)
    pool_pos = jnp.arange(pool_pad, dtype=jnp.int32)

    def next_victim(taken, step, fallbacks):
        raw = _step_slots(base_hi, base_lo, step * sample, sample, n_mod)
        if pool_pad > sample:
            raw = jnp.concatenate([raw, jnp.zeros(pool_pad - sample, jnp.int32)])
        free = ~taken[raw] & (pool_pos < sample)
        have = free.any()

        def from_pool():
            num, den = scores_of(raw)
            return raw[_argmin_frac(num, den, pool_pos, free)]

        def from_scan():
            num, den = scores_of(iota)
            return _argmin_frac(num, den, iota, in_use & ~taken)

        best = jax.lax.cond(have, from_pool, from_scan)
        return best, step + jnp.int32(1), fallbacks + jnp.int32(~have)

    return next_victim


def _recency_select(mstamp, mseg, n, *, slru):
    """The recency mains' victim order over the first ``n`` slots: returns
    ``select(taken)``, the untaken slot with the oldest stamp — for SLRU
    the probation segment (``mseg == 0``) first, then protected."""
    live = jnp.arange(mstamp.shape[0], dtype=jnp.int32) < n

    def select(taken):
        cand_mask = live & ~taken
        if slru:
            prob = cand_mask & (mseg == 0)
            mask = jnp.where(prob.any(), prob, cand_mask)
        else:
            mask = cand_mask
        return jnp.argmin(jnp.where(mask, mstamp, _I32_MAX)).astype(jnp.int32)

    return select


def _walk_sampled_sel(table, mk_lo, msz, n, cand_f, needed, base_hi, base_lo,
                      *, discipline, rule, sample, early_pruning):
    """The counter-RNG sample walk + IV/QV/AV verdict replay over a
    sampling main, recording selections into a full-width ``sel`` array
    (``sel[slot] = selection order``). Returns ``(admit, sel, n_evict,
    examined, fallbacks)``."""
    slots = mk_lo.shape[0]
    next_victim = _sample_picker(table, mk_lo, msz, n, needed, base_hi,
                                 base_lo, rule=rule, sample=sample)
    z = jnp.int32(0)
    taken0 = jnp.zeros(slots, bool)
    sel0 = jnp.full(slots, -1, jnp.int32)
    if discipline == "iv":
        # IV compares against the FIRST victim only: draw it up front and
        # gate the covering walk on a win, mirroring the scalar plane's RNG
        # pattern (no draws — hence no fallback scans — on a loss).
        first, step0, fb0 = next_victim(taken0, z, z)
        win = cand_f >= _freq_of(table, mk_lo[first][None])[0]
        init = (taken0.at[first].set(True), sel0.at[first].set(0),
                jnp.int32(1), jnp.int32(1), msz[first], z, z,
                jnp.bool_(False), z, fb0, step0)
    else:
        win = None
        init = (taken0, sel0, z, z, z, z, z, jnp.bool_(False), z, z, z)

    def cond(st):
        taken, sel, g, count, covered, freed, vfreq, stopped, examined, fallbacks, step = st
        more = count < n
        if discipline == "iv":
            return more & win & (covered < needed)
        if discipline == "qv":
            return more & ~stopped & (freed < needed)
        return more & ~stopped & (covered < needed)

    def body(st):
        taken, sel, g, count, covered, freed, vfreq, stopped, examined, fallbacks, step = st
        best, step, fallbacks = next_victim(taken, step, fallbacks)
        taken = taken.at[best].set(True)
        count = count + 1
        s = msz[best]
        if discipline != "iv":  # IV scores only its first victim (pre-loop)
            f = _freq_of(table, mk_lo[best][None])[0]
        if discipline == "iv":
            sel = sel.at[best].set(g)
            g = g + 1
            covered = covered + s
        elif discipline == "qv":
            examined = examined + 1
            win_q = cand_f >= f
            sel = jnp.where(win_q, sel.at[best].set(g), sel)
            g = g + jnp.int32(win_q)
            freed = freed + jnp.where(win_q, s, 0)
            stopped = ~win_q
        else:
            sel = sel.at[best].set(g)
            g = g + 1
            covered = covered + s
            vfreq = vfreq + f
            examined = examined + 1
            if early_pruning:
                stopped = cand_f < vfreq
        return (taken, sel, g, count, covered, freed, vfreq, stopped,
                examined, fallbacks, step)

    (taken, sel, g, count, covered, freed, vfreq, stopped,
     examined, fallbacks, step) = jax.lax.while_loop(cond, body, init)

    if discipline == "iv":
        admit = win
        n_evict = jnp.where(admit, g, 0)
        examined = jnp.int32(1)
    elif discipline == "qv":
        admit = freed >= needed
        n_evict = g
    else:
        pruned = stopped | (covered < needed)
        admit = ~pruned & (cand_f >= vfreq)
        n_evict = jnp.where(admit, g, 0)
    return admit, sel, n_evict, examined, fallbacks


def _walk_prefix_sel(table, mk_lo, msz, mstamp, mseg, n, cand_f, needed, tick,
                     *, discipline, early_pruning, slru):
    """IV/QV/AV verdict replay over the recency-order (LRU / SLRU
    probation-first) victim walk — the device twin of
    ``EvictionPolicy.peek_victims`` + ``_decide_prefix``, selecting by
    ``argmin`` over live stamps instead of a host-gathered prefix.
    Rejected-candidate promotions are applied to ``mstamp`` here, BEFORE
    the eviction apply (safe: stamps travel with rows through swap-removes
    and promoted entries are never evicted). Returns ``(admit, sel,
    n_evict, examined, mstamp, tick)``."""
    slots = mk_lo.shape[0]
    select = _recency_select(mstamp, mseg, n, slru=slru)
    z = jnp.int32(0)
    taken0 = jnp.zeros(slots, bool)
    sel0 = jnp.full(slots, -1, jnp.int32)

    if discipline == "iv":
        first = select(taken0)
        admit = cand_f >= _freq_of(table, mk_lo[first][None])[0]

        # gather the covering prefix unconditionally, mirroring the host's
        # peek_victims (which gathers before the verdict); zero RNG/tick use
        def cond(st):
            taken, sel, g, covered = st
            return (g < n) & (covered < needed)

        def body(st):
            taken, sel, g, covered = st
            v = select(taken)
            return (taken.at[v].set(True), sel.at[v].set(g), g + 1,
                    covered + msz[v])

        taken, sel, g, covered = jax.lax.while_loop(
            cond, body, (taken0, sel0, z, z))
        n_evict = jnp.where(admit, g, 0)
        examined = jnp.int32(1)
        # loss: promote the first victim (Alg. 4 line 14)
        mstamp = mstamp.at[jnp.where(admit, slots, first)].set(tick, mode="drop")
        tick = tick + jnp.int32(~admit)
        return admit, sel, n_evict, examined, mstamp, tick

    if discipline == "qv":
        def cond(st):
            taken, sel, g, count, freed, examined, stopped, loser = st
            return (count < n) & ~stopped & (freed < needed)

        def body(st):
            taken, sel, g, count, freed, examined, stopped, loser = st
            v = select(taken)
            taken = taken.at[v].set(True)
            f = _freq_of(table, mk_lo[v][None])[0]
            win = cand_f >= f
            sel = jnp.where(win, sel.at[v].set(g), sel)
            g = g + jnp.int32(win)
            freed = freed + jnp.where(win, msz[v], 0)
            examined = examined + 1
            loser = jnp.where(win, loser, v)
            return (taken, sel, g, count + 1, freed, examined, ~win, loser)

        init = (taken0, sel0, z, z, z, z, jnp.bool_(False), jnp.int32(slots))
        (taken, sel, g, count, freed, examined, stopped,
         loser) = jax.lax.while_loop(cond, body, init)
        admit = freed >= needed
        n_evict = g  # QV evictions stick on a reject
        # reject: promote the loser (never evicted — it lost, so it was
        # never selected)
        mstamp = mstamp.at[jnp.where(admit, slots, loser)].set(tick, mode="drop")
        tick = tick + jnp.int32(~admit)
        return admit, sel, n_evict, examined, mstamp, tick

    # AV: gather victims (and their frequency sum) until covered or pruned
    def cond(st):
        taken, sel, g, covered, vfreq, stopped = st
        return (g < n) & ~stopped & (covered < needed)

    def body(st):
        taken, sel, g, covered, vfreq, stopped = st
        v = select(taken)
        taken = taken.at[v].set(True)
        f = _freq_of(table, mk_lo[v][None])[0]
        sel = sel.at[v].set(g)
        g = g + 1
        covered = covered + msz[v]
        vfreq = vfreq + f
        if early_pruning:
            stopped = cand_f < vfreq
        return (taken, sel, g, covered, vfreq, stopped)

    init = (taken0, sel0, z, z, z, jnp.bool_(False))
    taken, sel, g, covered, vfreq, stopped = jax.lax.while_loop(cond, body, init)
    pruned = stopped | (covered < needed)
    admit = ~pruned & (cand_f >= vfreq)
    n_evict = jnp.where(admit, g, 0)
    examined = g
    # reject: promote every gathered victim in selection order (the prune
    # point included) — one vectorized stamp write, ticks in sel order
    promote = (~admit) & (sel >= 0)
    mstamp = jnp.where(promote, tick + sel, mstamp)
    tick = tick + jnp.where(admit, 0, g)
    return admit, sel, n_evict, examined, mstamp, tick


# -- decision kernels --------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("discipline", "rule", "sample", "early_pruning", "cap",
                     "use_pallas", "interpret"),
)
def _decide_sampled(table, mkeys, msizes, wr_slots, wr_keys, wr_sizes,
                    upd_keys, n_pend, n, cand_key, needed, base_hi, base_lo,
                    *, discipline, rule, sample, early_pruning, cap,
                    use_pallas, interpret):
    """One whole admission decision over a sampling main, on device.

    Mirror scatter -> fused CMS flush + candidate estimate ->
    :func:`_walk_sampled_sel` (each step gathers and scores only its drawn
    pool) -> verdict, its ``sel`` order scattered into ``victims``. Returns
    ``(table, mkeys, msizes, admit, victims, n_evict, examined,
    fallbacks)``; ``victims[:n_evict]`` are decision-time slots.
    """
    slots = mkeys.shape[0]
    mkeys = mkeys.at[wr_slots].set(wr_keys, mode="drop")
    msizes = msizes.at[wr_slots].set(wr_sizes, mode="drop")
    cand = jnp.asarray(cand_key, jnp.int32).reshape(1)
    table, est = flush_scores(table, upd_keys, n_pend, cand,
                              cap=cap, use_pallas=use_pallas, interpret=interpret)
    admit, sel, n_evict, examined, fallbacks = _walk_sampled_sel(
        table, mkeys, msizes, n, est[0], needed, base_hi, base_lo,
        discipline=discipline, rule=rule, sample=sample,
        early_pruning=early_pruning)
    # sel[slot] = order  ->  victims[order] = slot (unselected slots drop)
    victims = jnp.full(slots, -1, jnp.int32).at[
        jnp.where(sel >= 0, sel, slots)].set(
        jnp.arange(slots, dtype=jnp.int32), mode="drop")
    return table, mkeys, msizes, admit, victims, n_evict, examined, fallbacks


@functools.partial(
    jax.jit,
    static_argnames=("discipline", "early_pruning", "cap", "use_pallas", "interpret"),
)
def _decide_prefix(table, vkeys, vsizes, m, upd_keys, n_pend, cand_key, needed,
                   *, discipline, early_pruning, cap, use_pallas, interpret):
    """One whole admission decision over a host-ordered covering prefix.

    Fused CMS flush + candidate/prefix estimate, then the IV/QV/AV verdict
    replay as masked prefix scans. The prefix is minimal-covering
    (``peek_victims`` truncates at the first cumulative size >= needed), so
    QV admits iff every prefix victim loses to the candidate and AV's
    gather runs the whole prefix unless early pruning stops it. Returns
    ``(table, admit, n_evict, g, examined, has_loser)`` with ``g`` the
    gathered count (AV promotes ``prefix[:g]`` on a reject).
    """
    length = vkeys.shape[0]
    cand = jnp.asarray(cand_key, jnp.int32).reshape(1)
    est_keys = jnp.concatenate([cand, vkeys])
    table, est = flush_scores(table, upd_keys, n_pend, est_keys,
                              cap=cap, use_pallas=use_pallas, interpret=interpret)
    cand_f = est[0]
    vf = est[1:]
    valid = jnp.arange(length, dtype=jnp.int32) < m
    if discipline == "iv":
        admit = cand_f >= vf[0]
        n_evict = jnp.where(admit, m, 0)
        g = m
        examined = jnp.int32(1)
        has_loser = ~admit
    elif discipline == "qv":
        losses = valid & (cand_f < vf)
        first_loss = jnp.where(losses.any(), jnp.argmax(losses), m)
        admit = first_loss >= m  # walked the whole covering prefix unbeaten
        n_evict = jnp.where(admit, m, first_loss)
        g = n_evict
        examined = jnp.where(admit, m, first_loss + 1)
        has_loser = ~admit
    else:
        cvf = jnp.cumsum(jnp.where(valid, vf, 0))
        if early_pruning:
            prunes = valid & (cand_f < cvf)
            jp = jnp.where(prunes.any(), jnp.argmax(prunes).astype(jnp.int32), m)
        else:
            jp = jnp.asarray(m, jnp.int32)
        g = jnp.minimum(m, jp + 1)
        admit = (jp >= m) & (cand_f >= jnp.take(cvf, m - 1))
        n_evict = jnp.where(admit, m, 0)
        examined = g
        has_loser = jnp.bool_(False)
    return table, admit, n_evict, g, examined, has_loser


# -- host-side plane ---------------------------------------------------------

class DeviceMirror:
    """Device twin of a slot-addressed ``(keys, sizes)`` eviction table.

    The owning eviction policy reports every slot write (insert append,
    swap-remove back-fill) through :meth:`record`; the mirror keeps an
    authoritative host copy plus the dirty-slot set, and per decision hands
    the decision kernel either a masked scatter of the dirty slots (common
    case — the device arrays round-trip through the kernel and stay
    resident) or a fresh full upload (first use, growth, or a burst of
    writes past the scatter budget).
    """

    def __init__(self, initial_slots: int = 128, max_size: int = 2**31 - 1):
        self._cap = _next_pow2(max(8, initial_slots))
        self._keys = np.zeros(self._cap, np.int64)
        self._sizes = np.zeros(self._cap, np.int64)
        #: Largest representable object size: int32 on device, and the
        #: owning plane tightens it so ``freq * size`` stays in int32 for
        #: the exact cross-multiply comparisons.
        self.max_size = int(max_size)
        self._dirty: set[int] = set()
        self._dev: "tuple | None" = None
        self.uploads = 0  # full re-uploads (observability for tests)

    def ensure_capacity(self, slots: int) -> None:
        """Grow the slot table to hold at least ``slots`` entries (a shape
        change: full upload next decision)."""
        if slots <= self._cap:
            return
        grow = self._cap
        while slots > grow:
            grow <<= 1
        keys = np.zeros(grow, np.int64)
        sizes = np.zeros(grow, np.int64)
        keys[: self._cap] = self._keys
        sizes[: self._cap] = self._sizes
        self._keys, self._sizes, self._cap = keys, sizes, grow
        self._dev = None

    def load(self, keys, sizes_by_key) -> None:
        """Bulk (re)load of the whole slot table — the batched twin of
        per-slot :meth:`record`, which ``SampledEviction.attach_mirror`` and
        ``load_rows`` call: one vectorized fill + one full upload instead
        of len(keys) dirty-slot records."""
        n = len(keys)
        self.ensure_capacity(n)
        if n:
            arr = np.fromiter((k & 0xFFFFFFFF for k in keys), np.int64, n)
            szs = np.fromiter((sizes_by_key[k] for k in keys), np.int64, n)
            if szs.max(initial=0) > self.max_size:
                raise ValueError(
                    f"device admission plane: object size {int(szs.max())} "
                    f"exceeds the exact-arithmetic bound {self.max_size}"
                )
            self._keys[:n] = arr
            self._sizes[:n] = szs
        self._dirty.clear()
        self._dev = None  # full upload next decision

    def record(self, slot: int, key: int, size: int) -> None:
        if size > self.max_size:
            raise ValueError(
                f"device admission plane: object size {size} exceeds the "
                f"exact-arithmetic bound {self.max_size}"
            )
        if slot >= self._cap:
            self.ensure_capacity(slot + 1)
        self._keys[slot] = key & 0xFFFFFFFF
        self._sizes[slot] = size
        self._dirty.add(slot)

    def device_state(self):
        """``(keys, sizes, wr_slots, wr_keys, wr_sizes)`` for one decision:
        the resident arrays and the dirty-slot scatter (pad slots point
        past the arrays and drop)."""
        if self._dev is None or len(self._dirty) > _WRITE_PAD:
            self._dev = (
                jnp.asarray(self._keys.astype(np.int32)),
                jnp.asarray(self._sizes.astype(np.int32)),
            )
            self._dirty.clear()
            self.uploads += 1
        wr = np.zeros((3, _WRITE_PAD), np.int32)
        wr[0] = self._cap  # pad: dropped
        for j, slot in enumerate(self._dirty):
            wr[0, j] = slot
            wr[1, j] = self._keys[slot].astype(np.int32)
            wr[2, j] = self._sizes[slot]
        self._dirty.clear()
        dk, ds = self._dev
        return dk, ds, jnp.asarray(wr[0]), jnp.asarray(wr[1]), jnp.asarray(wr[2])

    def accept(self, dev_keys, dev_sizes) -> None:
        """Adopt the kernel's post-scatter arrays as the resident copy."""
        self._dev = (dev_keys, dev_sizes)


class DeviceAdmissionPlane:
    """The per-decision device decider behind one admission discipline:
    ``device_full``'s host path.

    Binds a CMS sketch and a Main eviction policy; :meth:`decide` runs the
    closed sample->score->select loop as one jitted call and applies the
    returned verdict to the host policy structures. Sampling mains
    (``mirror_slots``) use the :class:`DeviceMirror` walk kernel; the
    deterministic mains hand their covering prefix to the prefix kernel.

    ``calls`` counts decision-kernel launches (== decisions);
    ``staged_flushes`` counts the rare decisions whose pending-increment
    batch straddled an aging reset (or outgrew ``flush_block``) and was
    flushed through the sketch's boundary-splitting path first — the same
    fused-vs-staged split ``CMSSketch.estimate_batch`` makes, so the table
    state stays byte-identical to the other planes.
    """

    def __init__(self, sketch, main, *, discipline: str, early_pruning: bool = True):
        if not getattr(sketch, "batched_native", False) or not hasattr(sketch, "table"):
            raise ValueError(
                "device admission plane requires the CMS sketch backend "
                "(sketch_backend='cms')"
            )
        if not main.peek_stable:
            raise ValueError(
                "device admission plane requires a peek-stable eviction policy"
            )
        self.sketch = sketch
        self.main = main
        self.discipline = discipline
        self.early_pruning = early_pruning
        self.sampled = bool(getattr(main, "mirror_slots", False))
        #: Sizes (and ``needed``) must fit int32, tightened so the
        #: frequency_size cross-multiplies ``freq * size`` (freq <= cap)
        #: stay exact in int32.
        self.max_size = (2**31 - 1) // max(1, int(getattr(sketch, "cap", 15)))
        self.mirror = None
        if self.sampled:
            self.mirror = DeviceMirror(max_size=self.max_size)
            main.attach_mirror(self.mirror)
        self._interpret = not getattr(sketch, "_on_tpu", False)
        self.calls = 0
        self.staged_flushes = 0

    # -- sketch handoff ---------------------------------------------------
    def _pending_batch(self):
        """Pending increments as a padded int32 batch for the decision
        kernel — or staged through ``sketch.flush()`` first when an aging
        reset would land inside the batch (reset timing must match the
        scalar plane exactly; see ``CMSSketch.flush``)."""
        sk = self.sketch
        npend = len(sk._pending)
        if npend and (npend > sk.flush_block or sk._ops + npend >= sk.sample_size):
            sk.flush()
            self.staged_flushes += 1
            npend = 0
        pad = max(16, _next_pow2(max(1, npend)))
        upd = np.zeros(pad, np.int32)
        if npend:
            upd[:npend] = np.asarray(sk._pending, np.int64).astype(np.int32)
        return jnp.asarray(upd), np.int32(npend)

    def _commit_sketch(self, table, npend) -> None:
        sk = self.sketch
        sk.table = table
        if npend:
            sk._ops += int(npend)
            sk._pending = []

    # -- the decision -----------------------------------------------------
    def decide(self, key: int, size: int, needed: int, main, stats) -> bool:
        sk = self.sketch
        if needed > 2**31 - 1:
            raise ValueError(
                f"device admission plane: needed={needed} exceeds int32"
            )
        upd, npend = self._pending_batch()
        cand32 = _key32(key)
        if self.sampled:
            n = len(main.keys)
            if n >= MAX_MIRROR_ENTRIES:
                raise ValueError(
                    f"device plane supports < {MAX_MIRROR_ENTRIES} entries, got {n}"
                )
            base = crng.stream_key(main.seed, main.decision)
            mkeys, msizes, wr_slots, wr_keys, wr_sizes = self.mirror.device_state()
            (table, mkeys, msizes, admit, victims, n_evict, examined,
             fallbacks) = _decide_sampled(
                sk.table, mkeys, msizes, wr_slots, wr_keys, wr_sizes,
                upd, npend, np.int32(n), cand32, np.int32(needed),
                np.uint32(base >> 32), np.uint32(base & 0xFFFFFFFF),
                discipline=self.discipline, rule=main.rule, sample=main.SAMPLE,
                early_pruning=self.early_pruning, cap=sk.cap,
                use_pallas=sk.use_pallas, interpret=self._interpret)
            self.calls += 1
            self.mirror.accept(mkeys, msizes)
            self._commit_sketch(table, npend)
            admit = bool(admit)
            n_evict = int(n_evict)
            stats.victims_examined += int(examined)
            main.fallback_scans += int(fallbacks)
            if n_evict:
                # slots -> keys BEFORE evicting: swap-remove shifts slots
                evict_keys = [main.keys[s] for s in
                              np.asarray(victims[:n_evict]).tolist()]
                for v in evict_keys:
                    main.evict(v)
                    stats.evictions += 1
            # sampling policies keep no order: promote is a no-op, skip it
        else:
            vkeys, vsizes = main.peek_victims(needed)
            m = len(vkeys)
            if m and int(vsizes.max()) > self.max_size:
                raise ValueError(
                    f"device admission plane: victim size {int(vsizes.max())} "
                    f"exceeds the exact-arithmetic bound {self.max_size}"
                )
            pad = max(8, _next_pow2(max(1, m)))
            vk32 = np.zeros(pad, np.int32)
            vs32 = np.zeros(pad, np.int32)
            vk32[:m] = vkeys.astype(np.int32)
            vs32[:m] = vsizes
            table, admit, n_evict, g, examined, has_loser = _decide_prefix(
                sk.table, jnp.asarray(vk32), jnp.asarray(vs32), np.int32(m),
                upd, npend, cand32, np.int32(needed),
                discipline=self.discipline, early_pruning=self.early_pruning,
                cap=sk.cap, use_pallas=sk.use_pallas, interpret=self._interpret)
            self.calls += 1
            self._commit_sketch(table, npend)
            admit = bool(admit)
            n_evict = int(n_evict)
            keys_list = vkeys.tolist()
            stats.victims_examined += int(examined)
            for v in keys_list[:n_evict]:
                main.evict(v)
                stats.evictions += 1
            if self.discipline == "iv":
                if not admit:
                    main.promote(keys_list[0])
            elif self.discipline == "qv":
                if bool(has_loser):
                    main.promote(keys_list[n_evict])
            elif not admit:
                for v in keys_list[: int(g)]:
                    main.promote(v)
        if admit:
            main.insert(key, size)
            stats.admissions += 1
            return True
        stats.rejections += 1
        return False
