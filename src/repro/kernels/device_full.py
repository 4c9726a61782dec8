"""Whole-simulation-on-device data plane: ``data_plane="device_full"``.

The host planes walk every access in Python: window occupancy,
window-LRU order, the adaptive-window climber and the LRU/SLRU recency
dicts, so a main-cache hit (the common case on a warm cache) costs host
work per access. This module moves the **entire simulation step** into one
jitted ``lax.scan``:

    per access — fused CMS increment -> window membership + LRU stamp ->
    main membership + LRU/SLRU promotion (with protected-overflow
    demotion) -> Alg. 1 miss cascade (window insert, window-LRU drain,
    per-candidate IV/QV/AV decision with sampled or recency-order victim
    walks, swap-remove eviction apply) -> adaptive-window hill-climber

all inside the scan body, so a whole trace chunk resolves in ONE device
launch. The host only streams the chunk's key/size arrays in and collects
stats and the hit bitmap out. The remaining host-resync reasons are
exactly two (both counted in ``resync_reasons``):

* ``aging`` — the chunk would cross the sketch's reset boundary; the
  boundary access runs through the host path (whose staged
  ``CMSSketch.flush`` splits at the reset exactly like the other planes)
  and the device state re-uploads after;
* ``mirror_grow`` — the chunk's worst-case inserts outgrow the device
  slot arrays; the arrays are zero-padded **on device** (no host
  round-trip of the contents, but counted for observability).

The victim walks, the sample picker, the recency selector and the
estimate gather come from :mod:`repro.kernels.admission`, which the
per-decision kernels of the host path share. Byte-identity with the host
planes rests on the same arguments as that module (commuting saturating
increments, peek-stable victim replay, exact int32 cross-multiplied score
comparisons) plus two of its own:

* **recency as stamps** — an int32 tick counter stamps every
  insert/touch/promote; victim order is ``argmin`` over live stamps
  (probation-first for SLRU), which replays the host order dicts exactly
  because every host reorder (``move_to_end``, demote-to-probation-MRU)
  maps to a fresh-stamp write and evictions never consume ticks. Stamps
  travel with rows through swap-removes, so deferred eviction apply
  (gather a ``sel`` order, then swap-remove in that order) preserves it.
* **integer climber compare** — the adaptive window compares hit *ratios*
  whose denominators are always ``adapt_every``; with equal denominators
  the float compare the host performs reduces to an exact int32
  comparison of hit deltas (correctly-rounded f64 quotients of equal
  denominators order identically to their numerators).

Keys must be int64-representable (the same bound the CMS sketch backend
already imposes); they ride as uint32 limb pairs so 64-bit identity
compares and the int32 sketch hash-input truncation both hold.
"""

from __future__ import annotations

import contextlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import crng

from .admission import (
    _I32_MAX,
    MAX_MIRROR_ENTRIES,
    _freq_of,
    _next_pow2,
    _recency_select,
    _sample_picker,
    _walk_prefix_sel,
    _walk_sampled_sel,
)
from .cms.ops import _mix64_u32, flush_scores

__all__ = ["DeviceFullSimulationPlane", "OrderedDeviceMirror", "PLANE_SPANS"]

#: Renormalize recency stamps (via a host download/re-upload) before the
#: int32 tick counter could overflow mid-chunk.
_TICK_RENORM = 1 << 30

#: Scan-carried scalar state (relative counters last); the host packs them
#: into one int32 vector per launch and unpacks the returned vector.
_CARRY_FIELDS = (
    "n", "used", "pbytes", "wn", "wbytes", "tick", "window_cap", "main_cap",
    "hits", "acc", "prev_hits", "prev_num", "dir",
    "admissions", "rejections", "evictions", "vexam", "fallbacks", "bumps",
)
#: Launch constants appended after the carried fields in the same vector.
_CONST_FIELDS = (
    "capacity", "protected_cap", "adapt_every", "adapt_step",
    "win_min", "win_max", "a_n",
)
_SCAL_IDX = {name: i for i, name in enumerate(_CARRY_FIELDS + _CONST_FIELDS)}
#: Packed fields that may wrap: the climber only reads their int32 delta.
_WRAP_FIELDS = ("hits", "prev_hits")

#: ``crng.GAMMA`` as uint32 limbs: ``begin_decision`` adds it to the
#: unmixed stream key (``bump_decision``, a 64-bit limb add).
_GAMMA_HI = jnp.uint32(crng.GAMMA >> 32)
_GAMMA_LO = jnp.uint32(crng.GAMMA & 0xFFFFFFFF)

#: The plane's host spans: a launch's phases in the order it opens them,
#: then the resync span (``reason=`` as counted in ``resync_reasons``).
#: Each carries ``launch=<chunk_calls>``, the launch's index, so one
#: launch's spans share it; a profiler trace records them on its host
#: plane, on the device lines' clock.
PLANE_SPANS = (_PREPARE, _PACK, _ENQUEUE, _WAIT, _FETCH, _COMMIT, _RESYNC) = (
    "plane.prepare", "plane.pack", "plane.enqueue",
    "plane.wait", "plane.fetch", "plane.commit", "plane.resync")
#: Opens every span above.
_span = jax.profiler.TraceAnnotation


def _apply_evictions(mk_hi, mk_lo, msz, mstamp, mseg, sel, n, used, pbytes,
                     n_evict):
    """Replay a recorded eviction order onto the live arrays: for each
    selection index in order, locate the row carrying it and swap-remove
    (back-fill from the last live slot) — exactly the host's per-victim
    ``evict`` sequence, including the implicit slot remap of the sampled
    policies' ``pos`` dict (``sel`` travels with the moved row)."""
    slots = mk_hi.shape[0]
    iota = jnp.arange(slots, dtype=jnp.int32)

    def cond(st):
        return st[0] < n_evict

    def body(st):
        j, mk_hi, mk_lo, msz, mstamp, mseg, sel, n, used, pbytes = st
        v = jnp.argmax((sel == j) & (iota < n)).astype(jnp.int32)
        vsz = msz[v]
        vseg = mseg[v]
        last = n - 1
        mk_hi = mk_hi.at[v].set(mk_hi[last])
        mk_lo = mk_lo.at[v].set(mk_lo[last])
        msz = msz.at[v].set(msz[last])
        mstamp = mstamp.at[v].set(mstamp[last])
        mseg = mseg.at[v].set(mseg[last])
        sel = sel.at[v].set(sel[last])
        used = used - vsz
        pbytes = pbytes - jnp.where(vseg == 1, vsz, 0)
        return (j + 1, mk_hi, mk_lo, msz, mstamp, mseg, sel, last, used, pbytes)

    st = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), mk_hi, mk_lo, msz, mstamp, mseg, sel, n, used, pbytes))
    return st[1:]


# -- the whole-simulation scan kernel -----------------------------------------

def _simulate_chunk_impl(table, mk_hi, mk_lo, msz, mstamp, mseg,
                         wk_hi, wk_lo, wsz, wstamp,
                         xs_hi, xs_lo, xs_sz, scal, key_limbs,
                         *, discipline, rule, sample, early_pruning, adaptive,
                         main_kind, cap, use_pallas, interpret):
    """One whole trace chunk as a single ``lax.scan`` launch.

    State buffers (donated — steady-state chunks alias instead of
    double-allocating): the CMS ``table``, the Main slot arrays (key limb
    pairs, sizes, recency stamps, SLRU segments) and the Window slot
    arrays (key limbs, sizes, stamps). ``xs_*`` are the chunk's access
    key-limb/size arrays, ``scal`` the packed scalar state
    (:data:`_CARRY_FIELDS` + :data:`_CONST_FIELDS`), ``key_limbs`` the
    unmixed counter-RNG stream key. Returns the post-chunk buffers, the
    packed carried scalars, the advanced stream key, and the per-access
    hit bitmap.
    """
    c = {name: scal[_SCAL_IDX[name]] for name in _CARRY_FIELDS}
    capacity = scal[_SCAL_IDX["capacity"]]
    protected_cap = scal[_SCAL_IDX["protected_cap"]]
    adapt_every = scal[_SCAL_IDX["adapt_every"]]
    adapt_step = scal[_SCAL_IDX["adapt_step"]]
    win_min = scal[_SCAL_IDX["win_min"]]
    win_max = scal[_SCAL_IDX["win_max"]]
    a_n = scal[_SCAL_IDX["a_n"]]

    sampled = main_kind == "sampled"
    slru = main_kind == "slru"
    ordered = not sampled
    slots = mk_hi.shape[0]
    wslots = wk_hi.shape[0]
    miota = jnp.arange(slots, dtype=jnp.int32)
    wiota = jnp.arange(wslots, dtype=jnp.int32)
    z = jnp.int32(0)

    def bump_decision(st):
        """``begin_decision``: a no-op for the ordered mains; the sampling
        mains advance the unmixed stream key by GAMMA (64-bit limb add)."""
        if not sampled:
            return st
        st = dict(st)
        nlo = st["klo"] + _GAMMA_LO
        nhi = st["khi"] + _GAMMA_HI + (nlo < st["klo"]).astype(jnp.uint32)
        st["khi"], st["klo"] = nhi, nlo
        st["bumps"] = st["bumps"] + 1
        return st

    def insert_main(st, ck_hi, ck_lo, cs):
        st = dict(st)
        nn = st["n"]
        st["mk_hi"] = st["mk_hi"].at[nn].set(ck_hi)
        st["mk_lo"] = st["mk_lo"].at[nn].set(ck_lo)
        st["msz"] = st["msz"].at[nn].set(cs)
        if ordered:
            st["mstamp"] = st["mstamp"].at[nn].set(st["tick"])
            st["tick"] = st["tick"] + 1
        if slru:
            st["mseg"] = st["mseg"].at[nn].set(0)  # insert into probation
        st["n"] = nn + 1
        st["used"] = st["used"] + cs
        st["admissions"] = st["admissions"] + 1
        return st

    def apply_sel(st, sel, n_evict):
        st = dict(st)
        (st["mk_hi"], st["mk_lo"], st["msz"], st["mstamp"], st["mseg"], _sel,
         st["n"], st["used"], st["pbytes"]) = _apply_evictions(
            st["mk_hi"], st["mk_lo"], st["msz"], st["mstamp"], st["mseg"],
            sel, st["n"], st["used"], st["pbytes"], n_evict)
        st["evictions"] = st["evictions"] + n_evict
        return st

    def decide(st, ck_hi, ck_lo, cs):
        """``_evict_or_admit`` replay for one Main candidate."""

        def too_big(st):
            st = dict(st)
            st["rejections"] = st["rejections"] + 1
            return st

        def fits(st):
            needed = cs - (st["main_cap"] - st["used"])

            def free_insert(st):
                return insert_main(st, ck_hi, ck_lo, cs)

            def contested(st):
                st = bump_decision(st)
                cand_f = _freq_of(st["table"], ck_lo[None])[0]
                if sampled:
                    base_hi, base_lo = _mix64_u32(st["khi"], st["klo"])
                    admit, sel, n_evict, examined, fb = _walk_sampled_sel(
                        st["table"], st["mk_lo"], st["msz"], st["n"], cand_f,
                        needed, base_hi, base_lo, discipline=discipline,
                        rule=rule, sample=sample, early_pruning=early_pruning)
                    st = dict(st)
                    st["fallbacks"] = st["fallbacks"] + fb
                else:
                    (admit, sel, n_evict, examined, new_stamp,
                     new_tick) = _walk_prefix_sel(
                        st["table"], st["mk_lo"], st["msz"], st["mstamp"],
                        st["mseg"], st["n"], cand_f, needed, st["tick"],
                        discipline=discipline, early_pruning=early_pruning,
                        slru=slru)
                    st = dict(st)
                    st["mstamp"], st["tick"] = new_stamp, new_tick
                st["vexam"] = st["vexam"] + examined
                st = apply_sel(st, sel, n_evict)

                def adm(st):
                    return insert_main(st, ck_hi, ck_lo, cs)

                def rej(st):
                    st = dict(st)
                    st["rejections"] = st["rejections"] + 1
                    return st

                return jax.lax.cond(admit, adm, rej, st)

            return jax.lax.cond(needed <= z, free_insert, contested, st)

        return jax.lax.cond(cs > st["main_cap"], too_big, fits, st)

    def window_drain(st):
        """Pop window-LRU victims while the window overflows, deciding each
        inline (equivalent to the host's gather-then-decide: decisions
        never touch the window)."""

        def cond(st):
            return (st["wbytes"] > st["window_cap"]) & (st["wn"] > z)

        def body(st):
            v = jnp.argmin(
                jnp.where(wiota < st["wn"], st["wstamp"], _I32_MAX)
            ).astype(jnp.int32)
            vhi = st["wk_hi"][v]
            vlo = st["wk_lo"][v]
            vs = st["wsz"][v]
            last = st["wn"] - 1
            st = dict(st)
            st["wk_hi"] = st["wk_hi"].at[v].set(st["wk_hi"][last])
            st["wk_lo"] = st["wk_lo"].at[v].set(st["wk_lo"][last])
            st["wsz"] = st["wsz"].at[v].set(st["wsz"][last])
            st["wstamp"] = st["wstamp"].at[v].set(st["wstamp"][last])
            st["wn"] = last
            st["wbytes"] = st["wbytes"] - vs
            return decide(st, vhi, vlo, vs)

        return jax.lax.while_loop(cond, body, st)

    def slru_demote(st):
        """``_demote_overflow``: demote protected-LRU entries back to
        probation MRU while the protected segment overflows (keeping one)."""

        def cond(st):
            prot = (miota < st["n"]) & (st["mseg"] == 1)
            return (st["pbytes"] > protected_cap) & (prot.sum() > 1)

        def body(st):
            st = dict(st)
            prot = (miota < st["n"]) & (st["mseg"] == 1)
            v = jnp.argmin(jnp.where(prot, st["mstamp"], _I32_MAX)).astype(jnp.int32)
            st["mseg"] = st["mseg"].at[v].set(0)
            st["mstamp"] = st["mstamp"].at[v].set(st["tick"])
            st["tick"] = st["tick"] + 1
            st["pbytes"] = st["pbytes"] - st["msz"][v]
            return st

        return jax.lax.while_loop(cond, body, st)

    def drain_main(st):
        """The adaptive climber's Main drain: gather victims over the
        current snapshot until the overflow clears, then apply (the host
        walks a snapshot iterator and evicts per yield — identical victims,
        because peeking never consumes state)."""
        overflow = st["used"] - st["main_cap"]
        needed0 = jnp.maximum(z, overflow)
        msz_a = st["msz"]
        if sampled:
            base_hi, base_lo = _mix64_u32(st["khi"], st["klo"])
            next_victim = _sample_picker(
                st["table"], st["mk_lo"], msz_a, st["n"], needed0, base_hi,
                base_lo, rule=rule, sample=sample)

            def wcond(wst):
                taken, sel, g, freed, step, fb = wst
                return (g < st["n"]) & (freed < overflow)

            def wbody(wst):
                taken, sel, g, freed, step, fb = wst
                best, step, fb = next_victim(taken, step, fb)
                return (taken.at[best].set(True), sel.at[best].set(g), g + 1,
                        freed + msz_a[best], step, fb)

            init = (jnp.zeros(slots, bool), jnp.full(slots, -1, jnp.int32),
                    z, z, z, z)
            taken, sel, g, freed, step, fb = jax.lax.while_loop(
                wcond, wbody, init)
            st = dict(st)
            st["fallbacks"] = st["fallbacks"] + fb
        else:
            select = _recency_select(st["mstamp"], st["mseg"], st["n"],
                                     slru=slru)

            def wcond(wst):
                taken, sel, g, freed = wst
                return (g < st["n"]) & (freed < overflow)

            def wbody(wst):
                taken, sel, g, freed = wst
                v = select(taken)
                return (taken.at[v].set(True), sel.at[v].set(g), g + 1,
                        freed + msz_a[v])

            init = (jnp.zeros(slots, bool), jnp.full(slots, -1, jnp.int32), z, z)
            taken, sel, g, freed = jax.lax.while_loop(wcond, wbody, init)
        return apply_sel(st, sel, g)

    def maybe_adapt(st):
        """``_maybe_adapt`` (fires every ``adapt_every`` misses): integer
        hit-delta compare (equal denominators), window re-size, window
        drain with inline decisions, one drain-stream ``begin_decision``,
        then the Main drain."""
        st = dict(st)
        st["acc"] = st["acc"] + 1

        def fire(st):
            st = dict(st)
            num = st["hits"] - st["prev_hits"]  # int32 wrap-safe delta
            worse = (st["prev_num"] >= z) & (num < st["prev_num"])
            st["dir"] = jnp.where(worse, -st["dir"], st["dir"])
            nw = st["window_cap"] + st["dir"] * adapt_step
            nw = jnp.maximum(win_min, jnp.minimum(win_max, nw))
            st["window_cap"] = nw
            st["main_cap"] = capacity - nw
            st = window_drain(st)
            st = bump_decision(st)  # the drain walk's own RNG stream
            st = drain_main(st)
            st["prev_num"] = num
            st["prev_hits"] = st["hits"]
            st["acc"] = z
            return st

        return jax.lax.cond(st["acc"] >= adapt_every, fire, lambda s: s, st)

    def step(st, x):
        khi_x, klo_x, sz_x = x
        valid = st["i"] < a_n

        # every access increments the sketch (the flush step's estimate
        # output is unused here; candidate estimates happen per decision)
        st = dict(st)
        new_table, _ = flush_scores(
            st["table"], klo_x[None], jnp.where(valid, 1, 0), klo_x[None],
            cap=cap, use_pallas=use_pallas, interpret=interpret)
        st["table"] = new_table

        # window hit: stamp refresh (move_to_end)
        whm = (wiota < st["wn"]) & (st["wk_hi"] == khi_x) & (st["wk_lo"] == klo_x)
        whit = valid & whm.any()
        wslot = jnp.argmax(whm).astype(jnp.int32)
        st["wstamp"] = st["wstamp"].at[
            jnp.where(whit, wslot, wslots)].set(st["tick"], mode="drop")
        st["tick"] = st["tick"] + whit.astype(jnp.int32)

        # main hit: per-policy promotion
        mhm = (miota < st["n"]) & (st["mk_hi"] == khi_x) & (st["mk_lo"] == klo_x)
        mhit = valid & ~whit & mhm.any()
        mslot = jnp.argmax(mhm).astype(jnp.int32)
        if main_kind == "lru":
            st["mstamp"] = st["mstamp"].at[
                jnp.where(mhit, mslot, slots)].set(st["tick"], mode="drop")
            st["tick"] = st["tick"] + mhit.astype(jnp.int32)
        elif slru:
            def on_access(st):
                def prot(st):
                    st = dict(st)
                    st["mstamp"] = st["mstamp"].at[mslot].set(st["tick"])
                    st["tick"] = st["tick"] + 1
                    return st

                def prob(st):
                    st = dict(st)
                    st["mseg"] = st["mseg"].at[mslot].set(1)
                    st["mstamp"] = st["mstamp"].at[mslot].set(st["tick"])
                    st["tick"] = st["tick"] + 1
                    st["pbytes"] = st["pbytes"] + st["msz"][mslot]
                    return slru_demote(st)

                return jax.lax.cond(st["mseg"][mslot] == 1, prot, prob, st)

            st = jax.lax.cond(mhit, on_access, lambda s: s, st)

        hit = whit | mhit
        st["hits"] = st["hits"] + hit.astype(jnp.int32)

        def miss(st):
            def reject(st):
                st = dict(st)
                st["rejections"] = st["rejections"] + 1
                return st

            def direct(st):
                return decide(st, khi_x, klo_x, sz_x)

            def via_window(st):
                st = dict(st)
                wn0 = st["wn"]
                st["wk_hi"] = st["wk_hi"].at[wn0].set(khi_x)
                st["wk_lo"] = st["wk_lo"].at[wn0].set(klo_x)
                st["wsz"] = st["wsz"].at[wn0].set(sz_x)
                st["wstamp"] = st["wstamp"].at[wn0].set(st["tick"])
                st["tick"] = st["tick"] + 1
                st["wn"] = wn0 + 1
                st["wbytes"] = st["wbytes"] + sz_x
                return window_drain(st)

            branch = jnp.where(sz_x > capacity, 0,
                               jnp.where(sz_x > st["window_cap"], 1, 2))
            st = jax.lax.switch(branch, [reject, direct, via_window], st)
            if adaptive:
                st = maybe_adapt(st)
            return st

        st = jax.lax.cond(valid & ~hit, miss, lambda s: s, st)
        st["i"] = st["i"] + 1
        return st, hit

    st0 = dict(
        table=table, mk_hi=mk_hi, mk_lo=mk_lo, msz=msz, mstamp=mstamp,
        mseg=mseg, wk_hi=wk_hi, wk_lo=wk_lo, wsz=wsz, wstamp=wstamp,
        khi=key_limbs[0], klo=key_limbs[1], i=z, **c)
    st, hits = jax.lax.scan(step, st0, (xs_hi, xs_lo, xs_sz))
    scal_out = jnp.stack([st[name] for name in _CARRY_FIELDS])
    limbs_out = jnp.stack([st["khi"], st["klo"]])
    return (st["table"], st["mk_hi"], st["mk_lo"], st["msz"], st["mstamp"],
            st["mseg"], st["wk_hi"], st["wk_lo"], st["wsz"], st["wstamp"],
            scal_out, limbs_out, hits)


#: single-instance entry point — the un-jitted ``_simulate_chunk_impl`` stays
#: importable so :mod:`repro.kernels.fleet` can map it over stacked
#: instances under its own jit.
_simulate_chunk = jax.jit(
    _simulate_chunk_impl,
    static_argnames=("discipline", "rule", "sample", "early_pruning",
                     "adaptive", "main_kind", "cap", "use_pallas", "interpret"),
    donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9),
)


# -- host-side plane ----------------------------------------------------------

def _limbs_of(arr_i64: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 keys -> (hi, lo) int32 bit-pattern limb arrays."""
    u = arr_i64.view(np.uint64)
    hi = (u >> np.uint64(32)).astype(np.uint32).view(np.int32)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
    return hi, lo


def _keys_of(hi: np.ndarray, lo: np.ndarray) -> list:
    """(hi, lo) int32 limb arrays -> python int keys (int64 semantics)."""
    u = (hi.view(np.uint32).astype(np.uint64) << np.uint64(32)) | \
        lo.view(np.uint32).astype(np.uint64)
    return u.view(np.int64).tolist()


class OrderedDeviceMirror:
    """Device twin of the WHOLE cache state (Main + Window) for the
    ``device_full`` plane: key limb pairs, sizes, recency stamps, SLRU
    segments. Unlike :class:`~repro.kernels.admission.DeviceMirror` (a
    slot-scatter twin of the sampled mains' key/size table), this mirror
    uploads from / downloads to the policy's ``export_rows``/``load_rows``
    snapshot contract, carries the recency order as age stamps, and grows
    **on device** (zero-pad + copy, no host round-trip of the contents)."""

    def __init__(self):
        self.main = None  # (mk_hi, mk_lo, msz, mstamp, mseg)
        self.window = None  # (wk_hi, wk_lo, wsz, wstamp)
        self.slots = 0
        self.wslots = 0
        self.stale = True  # host is (or may have gone) ahead: re-upload
        self.uploads = 0  # full host->device uploads
        self.grows = 0  # on-device capacity growths
        # high-water marks: once the mirror has grown, later re-uploads
        # (e.g. after an aging resync) must not shrink below the grown
        # size, or a capacity hovering at the slot boundary re-triggers a
        # mirror_grow resync every upload cycle
        self.hiwater = 0
        self.whiwater = 0

    def upload(self, rows, window_items, sampled: bool, take: int):
        """Build the device arrays from the policy snapshot. ``rows`` are
        ``export_rows()`` tuples, ``window_items`` the window's
        ``(key, size)`` pairs in LRU->MRU order; ``take`` is the upcoming
        launch length (slack so no in-scan insert can overflow)."""
        n0 = len(rows)
        wn0 = len(window_items)
        slots = max(_next_pow2(max(64, n0 + wn0 + take)), self.hiwater)
        wslots = max(_next_pow2(max(64, wn0 + take)), self.whiwater)
        self.hiwater = slots
        self.whiwater = wslots
        keys = np.asarray([r[0] for r in rows], np.int64)
        mk_hi = np.zeros(slots, np.int32)
        mk_lo = np.zeros(slots, np.int32)
        msz = np.zeros(slots, np.int32)
        mstamp = np.zeros(slots, np.int32)
        mseg = np.zeros(slots, np.int32)
        if n0:
            hi, lo = _limbs_of(keys)
            mk_hi[:n0] = hi
            mk_lo[:n0] = lo
            msz[:n0] = np.asarray([r[1] for r in rows], np.int64)
            # export order IS the within-segment recency order; stamps only
            # ever compare within a segment (or window-wide), so a plain
            # arange stamps both mains and the window consistently
            mstamp[:n0] = np.arange(n0, dtype=np.int32)
            mseg[:n0] = np.asarray([r[2] for r in rows], np.int64)
        wk_hi = np.zeros(wslots, np.int32)
        wk_lo = np.zeros(wslots, np.int32)
        wsz = np.zeros(wslots, np.int32)
        wstamp = np.zeros(wslots, np.int32)
        if wn0:
            wkeys = np.asarray([k for k, _ in window_items], np.int64)
            hi, lo = _limbs_of(wkeys)
            wk_hi[:wn0] = hi
            wk_lo[:wn0] = lo
            wsz[:wn0] = np.asarray([s for _, s in window_items], np.int64)
            wstamp[:wn0] = np.arange(n0, n0 + wn0, dtype=np.int32)
        self.main = tuple(jnp.asarray(a) for a in (mk_hi, mk_lo, msz, mstamp, mseg))
        self.window = tuple(jnp.asarray(a) for a in (wk_hi, wk_lo, wsz, wstamp))
        self.slots = slots
        self.wslots = wslots
        self.stale = False
        self.uploads += 1
        return n0, wn0, n0 + wn0  # n, wn, tick0

    def grow(self, slots: int, wslots: int) -> None:
        """Zero-pad the device arrays in place (device-side copy only)."""
        slots = _next_pow2(max(self.slots, slots))
        wslots = _next_pow2(max(self.wslots, wslots))
        self.hiwater = max(self.hiwater, slots)
        self.whiwater = max(self.whiwater, wslots)
        if slots > self.slots:
            self.main = tuple(
                jnp.zeros(slots, a.dtype).at[: self.slots].set(a)
                for a in self.main)
            self.slots = slots
        if wslots > self.wslots:
            self.window = tuple(
                jnp.zeros(wslots, a.dtype).at[: self.wslots].set(a)
                for a in self.window)
            self.wslots = wslots
        self.grows += 1

    def adopt(self, main_arrays, window_arrays) -> None:
        """Take the post-launch buffers as the resident copy (the inputs
        were donated to the kernel and must not be reused)."""
        self.main = main_arrays
        self.window = window_arrays

    def resident_keys(self, n: int, wn: int) -> set:
        """The keys in the first ``n`` Main and ``wn`` Window slots: a read
        of the four key-limb arrays only, for membership between launches."""
        mk_hi, mk_lo = (np.asarray(a) for a in self.main[:2])
        wk_hi, wk_lo = (np.asarray(a) for a in self.window[:2])
        return (set(_keys_of(mk_hi[:n], mk_lo[:n]))
                | set(_keys_of(wk_hi[:wn], wk_lo[:wn])))

    def download(self, n: int, wn: int, sampled: bool):
        """Materialize ``(rows, window_items)`` in the host contract order:
        slot order for the sampled mains (draws address slots), stamp order
        for the recency mains; the window is always stamp-ordered."""
        mk_hi, mk_lo, msz, mstamp, mseg = (np.asarray(a) for a in self.main)
        wk_hi, wk_lo, wsz, wstamp = (np.asarray(a) for a in self.window)
        order = np.arange(n) if sampled else np.argsort(mstamp[:n], kind="stable")
        keys = _keys_of(mk_hi[:n][order], mk_lo[:n][order])
        sizes = msz[:n][order].tolist()
        segs = mseg[:n][order].tolist()
        rows = list(zip(keys, sizes, segs))
        worder = np.argsort(wstamp[:wn], kind="stable")
        wkeys = _keys_of(wk_hi[:wn][worder], wk_lo[:wn][worder])
        wsizes = wsz[:wn][worder].tolist()
        window_items = list(zip(wkeys, wsizes))
        return rows, window_items


class _InFlightSim:
    """A dispatched-but-uncollected ``_simulate_chunk`` launch."""

    __slots__ = ("outs", "a_n", "sizes", "stats_obj")

    def __init__(self, outs, a_n, sizes, stats_obj):
        self.outs = outs
        self.a_n = a_n
        self.sizes = sizes  # np.int64 sizes of the launched accesses
        self.stats_obj = stats_obj  # pol.stats at dispatch time


class DeviceFullSimulationPlane:
    """``data_plane="device_full"``: the whole simulation step on device.

    Drives access chunks through :func:`_simulate_chunk` — ONE jitted
    ``lax.scan`` launch per chunk, window hits and LRU/SLRU main hits
    included — keeping the cache state device-resident between launches.
    Host structures (the window dict, the eviction policy's dicts) go
    stale while the device is authoritative; any host-path read
    (:meth:`ensure_host` via the owning policy's ``needs_host_sync``
    guards) downloads and rebuilds them through the
    ``export_rows``/``load_rows`` snapshot contract.

    The ONLY host resyncs are ``aging`` (a sketch reset boundary falls
    inside the chunk: the boundary access replays through the host path,
    whose staged flush splits at the reset exactly like the other planes)
    and ``mirror_grow`` (device arrays zero-padded on device). Both are
    counted in ``resyncs`` / ``resync_reasons`` and forced in tests.

    The serving-layer async pipeline drives it through its
    deferred-collection surface (``defer_collect``, ``sync``,
    ``has_deferred_work``, ``chunk``, ``deferred_dispatches``,
    ``chunk_calls``, ``decisions``): with ``defer_collect`` a whole-chunk
    drive leaves its launch in flight until the next drive or ``sync``,
    and a launch scans only its take's power-of-two bucket
    (:meth:`scan_len`), since each resolve waits on a few accesses.
    """

    def __init__(self, device, *, chunk: int = 64):
        if chunk < 1:
            raise ValueError("device_full chunk must be >= 1")
        from repro.core.eviction import LRUEviction, SLRUEviction

        self.device = device  # per-decision plane: the host-resync path
        self.sketch = device.sketch
        self.main = device.main
        self.sampled = device.sampled
        if self.sampled:
            self.main_kind = "sampled"
        elif isinstance(device.main, SLRUEviction):
            self.main_kind = "slru"
        elif isinstance(device.main, LRUEviction):
            self.main_kind = "lru"
        else:
            raise ValueError(
                "device_full requires a sampled, LRU, or SLRU main policy")
        self.chunk = int(chunk)
        self.mirror = OrderedDeviceMirror()
        self.chunk_calls = 0  # simulation-kernel launches
        self.decisions = 0  # admission decisions resolved (all on device)
        self.resyncs = 0
        self.resync_reasons = {"aging": 0, "mirror_grow": 0}
        self.defer_collect = False
        self.deferred_dispatches = 0
        self._inflight: "_InFlightSim | None" = None
        self._host_auth = True  # host structures current?
        #: set by repro.kernels.fleet while this instance is enrolled in a
        #: fleet: lane-materialization callback run by ensure_host
        self._fleet_restore = None
        # device-side shadows (committed scalars the host can't derive
        # without a download)
        self._n = 0
        self._wn = 0
        self._tick = 0
        self._pbytes = 0
        #: the resident keys after the last launch, read off the device by
        #: :meth:`contains`; kept across launches whose keys are all
        #: resident (all hits), dropped by any other launch and whenever
        #: the host takes authority back (a fleet's release included)
        self._resident = None

    # -- plane surface ------------------------------------------------------
    @property
    def has_deferred_work(self) -> bool:
        return self._inflight is not None or not self._host_auth

    #: the owning policy consults this before any host-structure read
    needs_host_sync = has_deferred_work

    @property
    def uploads(self) -> int:
        return self.mirror.uploads

    def sync(self, pol) -> None:
        """Collect any in-flight launch AND restore host authority —
        after this, host structures, membership, and stats are exact."""
        self.ensure_host(pol)

    def collect(self, pol) -> None:
        """Collect any in-flight launch: stats and the committed shadows
        are exact after this, while the cache state stays on the device."""
        self._collect(pol)

    def contains(self, pol, key) -> bool:
        """Membership without restoring host authority. It answers from
        the resident keys read off the device (the key limbs only), which
        are read after collecting the launch in flight and then kept while
        every later launch only hits: such a launch cannot change
        residency, so it is not waited for. A caller that reads membership
        between launches, as the serving pipeline does at every resolve,
        pays neither the download and host rebuild of :meth:`ensure_host`
        nor the re-upload at the next launch."""
        if self._resident is None or self._fleet_restore is not None:
            self._collect(pol)
            if self._host_auth or self._fleet_restore is not None:
                self.ensure_host(pol)
                return key in pol.window or key in self.main
            self._resident = self.mirror.resident_keys(self._n, self._wn)
        return key in self._resident

    # -- chunk drive --------------------------------------------------------
    def drive_chunk(self, pol, keys, sizes):
        """Drive one access chunk — observationally identical to the
        scalar ``access`` loop. Returns the hit bitmap (an un-materialized
        device array when the whole chunk was one deferred launch)."""
        with contextlib.ExitStack() as prepare:  # closed at the first launch
            prepare.enter_context(_span(_PREPARE, launch=self.chunk_calls))
            arr = np.asarray(keys, np.int64)
            szs = np.asarray(sizes, np.int64)
            n = len(arr)
            if n and int(szs.max()) > self.device.max_size:
                raise ValueError(
                    f"device_full plane: object size {int(szs.max())} exceeds "
                    f"the exact-arithmetic bound {self.device.max_size}")
            khi, klo = _limbs_of(arr)
            self._collect(pol)  # resolve any launch left in flight
            sk = self.sketch
            hits = np.empty(n, dtype=bool)
            i = 0
            while i < n:
                if sk._pending:
                    # host-path increments (boundary accesses) flush first so
                    # the in-scan increments land on the settled table
                    sk.flush()
                safe = sk.sample_size - sk._ops - 1
                if safe <= 0:
                    # the next access's estimates would straddle the aging
                    # reset: replay it through the host path (staged flush
                    # splits at the boundary), then re-upload
                    with _span(_RESYNC, launch=self.chunk_calls, reason="aging"):
                        self.ensure_host(pol)
                        self.resyncs += 1
                        self.resync_reasons["aging"] += 1
                        hits[i] = pol.access(int(arr[i]), int(szs[i]))
                    i += 1
                    continue
                take = min(n - i, self.chunk, safe)
                prepare.close()
                inf = self._dispatch(pol, khi[i: i + take], klo[i: i + take],
                                     szs[i: i + take], take)
                if self.defer_collect and i == 0 and take == n:
                    # the whole chunk resolved in one launch: leave it in
                    # flight (double-buffered with the caller's next gather)
                    self._inflight = inf
                    self.deferred_dispatches += 1
                    return inf.outs[12]
                self._inflight = inf
                self._collect(pol)
                hits[i: i + take] = np.asarray(self._last_hits[:take])
                i += take
        return hits

    def _preflight(self, pol, take) -> bool:
        """Upload-or-grow the mirror ahead of a ``take``-access launch.
        Re-uploads (with chunk-width slack and the high-water floor) when
        the host went authoritative; otherwise grows on device when the
        worst case of ``take`` inserts could overflow the slot arrays.
        Returns True when a full upload happened."""
        sk = self.sketch
        main = self.main
        if self.mirror.stale:
            if not self._host_auth:
                raise RuntimeError(
                    "device_full: stale mirror with device-authoritative "
                    "state (internal invariant violation)")
            rows = main.export_rows()
            slack = max(take, self.chunk)
            if self.sampled and len(rows) + len(pol.window) + slack >= MAX_MIRROR_ENTRIES:
                raise ValueError(
                    f"device plane supports < {MAX_MIRROR_ENTRIES} entries")
            n0, wn0, tick0 = self.mirror.upload(
                rows, list(pol.window.items()), self.sampled, slack)
            self._n, self._wn, self._tick = n0, wn0, tick0
            self._pbytes = int(getattr(main, "protected_bytes", 0))
            return True
        if (self._n + self._wn + take > self.mirror.slots
                or self._wn + take > self.mirror.wslots):
            # grow Main straight to the sketch width (sized for the expected
            # resident count): each slot count is its own compiled program,
            # and every access already sweeps that width in the CMS kernel
            with _span(_RESYNC, launch=self.chunk_calls, reason="mirror_grow"):
                self.mirror.grow(
                    max(self._n + self._wn + max(take, self.chunk), sk.width),
                    self._wn + max(take, self.chunk))
                self.resyncs += 1
                self.resync_reasons["mirror_grow"] += 1
        return False

    def _pack_scal(self, pol, take) -> np.ndarray:
        """Pack the scalar carry/const vector for a ``take``-access launch
        from the committed shadows + the policy's adaptive-climber state."""
        main = self.main
        prev_ratio = pol._adapt_prev_ratio
        prev_num = (-1 if prev_ratio < 0
                    else int(round(prev_ratio * pol._adapt_every)))
        vals = [0] * len(_SCAL_IDX)
        for name, v in (
            ("n", self._n), ("used", main.used), ("pbytes", self._pbytes),
            ("wn", self._wn), ("wbytes", pol.window_bytes),
            ("tick", self._tick), ("window_cap", pol.window_cap),
            ("main_cap", pol.main_cap), ("hits", pol.stats.hits),
            ("acc", pol._adapt_accesses),
            ("prev_hits", pol._adapt_prev_hits), ("prev_num", prev_num),
            ("dir", pol._adapt_dir),
            ("capacity", pol.capacity),
            ("protected_cap", int(getattr(main, "protected_cap", 0))),
            ("adapt_every", min(pol._adapt_every, int(_I32_MAX))),
            ("adapt_step", pol._adapt_step),
            ("win_min", max(1, pol.capacity // 100)),
            ("win_max", pol.capacity // 2), ("a_n", take),
        ):
            if name not in _WRAP_FIELDS and not -(2**31) <= v < 2**31:
                raise ValueError(
                    f"device_full plane: {name}={v} does not fit int32 "
                    f"(capacity {pol.capacity}); byte quantities past "
                    "2**31 - 1 are not supported on the device planes")
            vals[_SCAL_IDX[name]] = v
        return np.asarray(vals, np.int64).astype(np.int32)

    def _rng_limbs(self) -> np.ndarray:
        """The unmixed counter-RNG stream key as uint32 limbs (replayable:
        derived from the main's seed + decision counter, never consumed)."""
        main = self.main
        seed = int(getattr(main, "seed", 0))
        decision = int(getattr(main, "decision", 0))
        key0 = (seed * crng.GOLDEN + decision * crng.GAMMA) & ((1 << 64) - 1)
        return np.asarray([key0 >> 32, key0 & 0xFFFFFFFF], np.uint32)

    def _statics(self, pol) -> dict:
        """The kernel's static kwargs — also the fleet's shape-bucket key
        (together with the sketch table shape)."""
        main = self.main
        return dict(
            discipline=self.device.discipline,
            rule=getattr(main, "rule", "frequency"),
            sample=int(getattr(main, "SAMPLE", 5)),
            early_pruning=self.device.early_pruning,
            adaptive=bool(pol.adaptive_window), main_kind=self.main_kind,
            cap=self.sketch.cap, use_pallas=self.sketch.use_pallas,
            interpret=self.device._interpret)

    def scan_len(self, take: "int | None" = None) -> int:
        """The scan length of a launch. A lockstep drive (the engine, the
        fleet) runs one per plane, not per take, so a take cut short (aging
        boundary, trace end) compiles no new program; its padded steps are
        masked but still run. A deferred drive (``defer_collect``, the
        serving pipeline) is waited on at each resolve, a few accesses at
        a time, so there ``take`` runs its own power-of-two bucket: at most
        log2(chunk / 8) + 1 programs."""
        full = _next_pow2(max(8, self.chunk))
        if take is None or not self.defer_collect:
            return full
        return min(full, _next_pow2(max(8, take)))

    def launch_inputs(self, pol, khi, klo, szs, take, pad=None) -> tuple:
        """The host-built inputs of a ``take``-access launch, after the
        state buffers: key limbs and sizes padded to ``pad`` (default
        :meth:`scan_len` of ``take``), the packed scalars, the RNG
        stream-key limbs."""
        xs = np.zeros((3, self.scan_len(take) if pad is None else pad), np.int32)
        xs[0, :take] = khi
        xs[1, :take] = klo
        xs[2, :take] = szs
        return (*xs, self._pack_scal(pol, take), self._rng_limbs())

    def chunk_args(self, pol, khi=(), klo=(), szs=(), take=0) -> tuple:
        """The positional args of one :func:`_simulate_chunk` launch: the
        donated state buffers (sketch table, Main, Window), then
        :meth:`launch_inputs` as the NumPy arrays it builds. Nothing moves
        to the device here: the jitted call transfers those inputs in its
        own dispatch, as the fleet's does. Every launch passes the same
        kinds of argument, so the program has one compiled entry."""
        return (self.sketch.table, *self.mirror.main, *self.mirror.window,
                *self.launch_inputs(pol, khi, klo, szs, take))

    def _dispatch(self, pol, khi, klo, szs, take) -> "_InFlightSim":
        sk = self.sketch
        launch = self.chunk_calls
        with _span(_PACK, launch=launch):
            if (self._resident is not None
                    and not self._resident.issuperset(_keys_of(khi, klo))):
                self._resident = None  # a miss can insert or evict
            self._preflight(pol, take)
            args = self.chunk_args(pol, khi, klo, szs, take)
            statics = self._statics(pol)
        with _span(_ENQUEUE, launch=launch):
            outs = _simulate_chunk(*args, **statics)
            self.chunk_calls += 1
            # adopt the async results immediately: the inputs were donated
            sk.table = outs[0]
            self.mirror.adopt(tuple(outs[1:6]), tuple(outs[6:10]))
            self._host_auth = False
            # queue both readbacks behind the program: the mask's copy then
            # follows the scalars' instead of starting when the host wakes
            outs[10].copy_to_host_async()
            outs[12].copy_to_host_async()
        return _InFlightSim(outs, take, szs, pol.stats)

    def _collect(self, pol) -> None:
        """Materialize the in-flight launch (blocking) and commit stats,
        caps, adaptive-climber state, and the scalar shadows. Both
        readbacks were queued at enqueue (:meth:`_dispatch`): the scalars'
        arrival ends the wait, and the hit mask's copy has run behind it."""
        if self._inflight is None:
            return
        inf, self._inflight = self._inflight, None
        launch = self.chunk_calls - 1  # the in-flight launch is the last one
        with _span(_WAIT, launch=launch):
            # the scalars' arrival ends the wait: a block of its own would
            # only add a host wake-up
            scal = np.asarray(inf.outs[10])
        with _span(_FETCH, launch=launch):
            scal = scal.astype(np.int64)
            hits = np.asarray(inf.outs[12])
        with _span(_COMMIT, launch=launch):
            self._commit(pol, inf.stats_obj, inf.a_n, inf.sizes, scal, hits)
        if self.renorm_due:
            self.ensure_host(pol)  # re-upload next launch with fresh ticks

    @property
    def renorm_due(self) -> bool:
        """Whether the recency stamps must be renormalized (host authority
        restored, fresh ticks on the next upload) before another launch."""
        return self._tick > _TICK_RENORM

    def _commit(self, pol, st, a_n, sizes, scal, hits) -> None:
        """Commit a collected ``a_n``-access launch into ``st`` (the
        policy's stats at dispatch) and the policy: caps, adaptive-climber
        state and scalar shadows; keep its hit mask as ``_last_hits``."""
        sk = self.sketch
        sk._ops += a_n
        main = self.main
        st.accesses += a_n
        st.bytes_requested += int(sizes.sum())
        hit_mask = hits[:a_n]
        st.hits += int(hit_mask.sum())
        st.bytes_hit += int(sizes[hit_mask].sum())

        def rel(name):
            return int(scal[_SCAL_IDX[name]])

        st.admissions += rel("admissions")
        st.rejections += rel("rejections")
        st.evictions += rel("evictions")
        st.victims_examined += rel("vexam")
        if self.sampled:
            main.fallback_scans += rel("fallbacks")
            main.decision += rel("bumps")
        self.decisions += rel("admissions") + rel("rejections")
        self._n = rel("n")
        self._wn = rel("wn")
        self._tick = rel("tick")
        self._pbytes = rel("pbytes")
        main.used = rel("used")
        if self.main_kind == "slru":
            main.protected_bytes = self._pbytes
        pol.window_bytes = rel("wbytes")
        pol.window_cap = rel("window_cap")
        pol.main_cap = rel("main_cap")
        pol._adapt_accesses = rel("acc")
        pol._adapt_dir = rel("dir")
        prev_num = rel("prev_num")
        pol._adapt_prev_ratio = (
            prev_num / pol._adapt_every if prev_num >= 0 else -1.0)
        # absolute prev-hits from the wrap-safe device delta
        delta = (rel("hits") - rel("prev_hits")) & 0xFFFFFFFF
        pol._adapt_prev_hits = st.hits - delta
        self._last_hits = hit_mask

    def ensure_host(self, pol) -> None:
        """Restore host authority: collect any in-flight launch, download
        the device state, and rebuild the window dict + eviction policy
        through ``load_rows``. Marks the mirror stale (the host may mutate
        before the next launch re-uploads)."""
        self._collect(pol)
        if self._host_auth:
            return
        if self._fleet_restore is not None:
            # enrolled in a fleet: the authoritative state lives in
            # the fleet's stacked buffers — materialize this instance's lane
            # into the mirror (and sketch table) before downloading
            self._fleet_restore()
        self._resident = None  # the host may change residency from here
        rows, window_items = self.mirror.download(
            self._n, self._wn, self.sampled)
        self.main.load_rows(rows)
        pol.window = OrderedDict(window_items)
        pol.window_bytes = sum(s for _, s in window_items)
        self._host_auth = True
        self.mirror.stale = True
