"""Jitted public ops for the device-side TinyLFU sketch (and the aging
reset), plus the JAX-native DeviceSketch convenience wrapper used by the
serving data plane."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.crng import GAMMA as _CRNG_GAMMA
from repro.core.crng import MIX_M1 as _MIX_M1
from repro.core.crng import MIX_M2 as _MIX_M2

from .cms import cms_estimate_pallas, cms_update_estimate_pallas, cms_update_pallas
from .ref import ROWS, cms_estimate_ref, cms_update_estimate_ref, cms_update_ref, row_indexes

__all__ = [
    "make_table",
    "update",
    "estimate",
    "update_estimate",
    "update_estimate_segments",
    "flush_scores",
    "flush_scores_jit",
    "reset",
    "counter_draws",
    "DeviceSketch",
]

# -- device-side counter RNG (splitmix64 in uint32 limbs) --------------------
#
# The sampled evictions' victim draws are ``repro.core.crng.draws(seed,
# decision, i)`` — pure splitmix64 of the decision index. This section
# reproduces that stream on device bit-for-bit WITHOUT 64-bit integers
# (device JAX runs without x64; TPUs have no native s64): a 64-bit word is
# carried as (hi, lo) uint32 lanes and the two splitmix64 multiplies are
# done in 16-bit limbs so no partial product or carry chain ever overflows
# uint32. Constants come from repro.core.crng (the single source of truth),
# so host and device streams cannot silently diverge. It is the sampling
# building block of the device-resident admission plane
# (repro.kernels.admission draws victim slots from this stream inside its
# closed decision loop), validated against the host stream in
# tests/test_kernels.py.

_U16 = jnp.uint32(0xFFFF)


def _mul64_const(hi, lo, const: int):
    """(hi, lo) uint32 × 64-bit python ``const``, mod 2**64.

    16-bit limb schoolbook multiply; every partial sum is kept < 2**32
    (the top limb may wrap — harmless, only its low 16 bits are used).
    """
    a0, a1 = lo & _U16, lo >> jnp.uint32(16)
    a2, a3 = hi & _U16, hi >> jnp.uint32(16)
    c0, c1, c2, c3 = (jnp.uint32((const >> s) & 0xFFFF) for s in (0, 16, 32, 48))
    p = a0 * c0
    r0 = p & _U16
    k = p >> jnp.uint32(16)
    t = k + a0 * c1
    k = t >> jnp.uint32(16)
    t = (t & _U16) + a1 * c0
    k = k + (t >> jnp.uint32(16))
    r1 = t & _U16
    t = k + a0 * c2
    k = t >> jnp.uint32(16)
    t = (t & _U16) + a1 * c1
    k = k + (t >> jnp.uint32(16))
    t = (t & _U16) + a2 * c0
    k = k + (t >> jnp.uint32(16))
    r2 = t & _U16
    r3 = (k + a0 * c3 + a1 * c2 + a2 * c1 + a3 * c0) & _U16
    return (r3 << jnp.uint32(16)) | r2, (r1 << jnp.uint32(16)) | r0


def _xorshr64(hi, lo, k: int):
    """x ^= x >> k (0 < k < 32) on (hi, lo) uint32 lanes."""
    return hi ^ (hi >> jnp.uint32(k)), lo ^ ((lo >> jnp.uint32(k)) | (hi << jnp.uint32(32 - k)))


def _mix64_u32(hi, lo):
    """Stafford mix13 on (hi, lo) — the device twin of ``crng.mix64_vec``."""
    hi, lo = _xorshr64(hi, lo, 30)
    hi, lo = _mul64_const(hi, lo, _MIX_M1)
    hi, lo = _xorshr64(hi, lo, 27)
    hi, lo = _mul64_const(hi, lo, _MIX_M2)
    return _xorshr64(hi, lo, 31)


@jax.jit
def _counter_draws_u32(idx_hi, idx_lo, base_hi, base_lo):
    hi, lo = _mul64_const(idx_hi, idx_lo, _CRNG_GAMMA)
    return jnp.stack(_mix64_u32(hi ^ base_hi, lo ^ base_lo))


def counter_draws(seed: int, decision: int, start: int, count: int) -> jax.Array:
    """Device twin of :func:`repro.core.crng.draws`.

    Returns a ``[2, count] uint32`` array — row 0 the high 32 bits, row 1
    the low 32 bits of draws ``start .. start+count-1`` of the given
    decision's stream, bit-identical to the host uint64 values.
    """
    from repro.core import crng

    base = crng.stream_key(seed, decision)
    idx = np.arange(start, start + count, dtype=np.uint64)
    return _counter_draws_u32(
        jnp.asarray((idx >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((idx & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.uint32(base >> 32),
        jnp.uint32(base & 0xFFFFFFFF),
    )


def make_table(width: int) -> jax.Array:
    assert width & (width - 1) == 0, "width must be a power of two"
    return jnp.zeros((ROWS, width), jnp.int32)


@functools.partial(jax.jit, static_argnames=("cap", "use_pallas"))
def update(table, keys, *, cap: int = 15, use_pallas: bool = True):
    idx = row_indexes(keys, table.shape[1])
    if use_pallas:
        return cms_update_pallas(table, idx, cap=cap,
                                 interpret=jax.default_backend() != "tpu")
    return cms_update_ref(table, keys, cap=cap)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def estimate(table, keys, *, use_pallas: bool = True):
    if use_pallas:
        idx = row_indexes(keys, table.shape[1])
        vals = cms_estimate_pallas(table, idx,
                                   interpret=jax.default_backend() != "tpu")
        return vals.min(0)
    return cms_estimate_ref(table, keys)


def flush_scores(table, upd_keys, n_pend, est_keys, *, cap, use_pallas, interpret):
    """Apply the first ``n_pend`` pending increments of ``upd_keys`` (a
    padded int32 batch), then estimate ``est_keys`` on the updated table —
    the fused flush+score step shared by every device decision kernel.

    With ``use_pallas`` this IS the fused ``cms_update_estimate`` Pallas
    launch; otherwise a scatter-add + gather with identical values (the
    same saturating non-conservative semantics as ``cms_update_ref``).
    Padded update lanes are masked to the out-of-range ``width`` sentinel,
    which no width block ever matches. Traceable (``n_pend`` may be
    dynamic), so it composes into ``lax.scan`` decision chunks.
    """
    width = table.shape[1]
    upd_idx = row_indexes(upd_keys, width)
    upd_idx = jnp.where(jnp.arange(upd_keys.shape[0])[None, :] < n_pend, upd_idx, width)
    est_idx = row_indexes(est_keys, width)
    if use_pallas:
        new_table, vals = cms_update_estimate_pallas(
            table, upd_idx, est_idx, cap=cap, interpret=interpret)
        return new_table, vals.min(0)
    rows = table.shape[0]
    counts = jnp.zeros_like(table).at[
        jnp.arange(rows, dtype=jnp.int32)[:, None], upd_idx
    ].add(1, mode="drop")
    new_table = jnp.minimum(table + counts, cap)
    vals = jnp.take_along_axis(new_table, est_idx, axis=1)
    return new_table, vals.min(0)


#: :func:`flush_scores` as one jitted call, for callers outside a trace.
flush_scores_jit = jax.jit(flush_scores,
                           static_argnames=("cap", "use_pallas", "interpret"))


@functools.partial(jax.jit, static_argnames=("cap", "use_pallas"))
def update_estimate_segments(table, upd, n_pend, est, *, cap: int = 15,
                             use_pallas: bool = True):
    """Fused flush + score over B per-decision increment *segments* in ONE
    dispatch: for each decision ``d``, apply ``upd[d, :n_pend[d]]`` to the
    running table, then estimate ``est[d]`` against the just-updated table.

    ``upd`` is ``[B, P]`` int32 (padded), ``n_pend`` ``[B]``, ``est``
    ``[B, K]``. Returns ``(final_table, vals[B, K])``. Segment granularity
    is exactness-preserving (saturating non-conservative increments
    commute, and ``min(min(t+c1, cap)+c2, cap) == min(t+c1+c2, cap)``), so
    estimates observe precisely the increments that precede their decision
    in access order — the decision-batched admission plane's sketch
    primitive, also used standalone by tests and benchmarks.
    """
    interpret = jax.default_backend() != "tpu"  # like the sibling ops

    def step(tab, x):
        u, n, e = x
        tab, vals = flush_scores(tab, u, n, e, cap=cap,
                                 use_pallas=use_pallas, interpret=interpret)
        return tab, vals

    return jax.lax.scan(step, table, (upd, n_pend, est))


@functools.partial(jax.jit, static_argnames=("cap", "use_pallas"))
def update_estimate(table, upd_keys, est_keys, *, cap: int = 15, use_pallas: bool = True):
    """Fused flush + score: apply ``upd_keys`` then estimate ``est_keys`` on
    the updated table in one kernel launch. Returns ``(new_table, vals[N])``
    — the admission data plane's one-call-per-decision primitive."""
    if use_pallas:
        width = table.shape[1]
        upd_idx = row_indexes(upd_keys, width)
        est_idx = row_indexes(est_keys, width)
        new_table, vals = cms_update_estimate_pallas(
            table, upd_idx, est_idx, cap=cap,
            interpret=jax.default_backend() != "tpu")
        return new_table, vals.min(0)
    return cms_update_estimate_ref(table, upd_keys, est_keys, cap=cap)


@jax.jit
def reset(table):
    """TinyLFU aging: halve every counter (paper §3)."""
    return table >> 1


class DeviceSketch:
    """Batched TinyLFU sketch living on device; used by the serving engine's
    data plane for admission decisions over request batches."""

    def __init__(self, expected_entries: int, *, sample_factor: int = 10, cap: int = 15):
        width = 128
        while width < expected_entries:
            width <<= 1
        self.table = make_table(width)
        self.cap = cap
        self.sample_size = sample_factor * expected_entries
        self._ops = 0

    def increment(self, keys) -> None:
        keys = jnp.atleast_1d(jnp.asarray(keys, jnp.int32))
        total = int(keys.shape[0])
        pos = 0
        # Split the batch at aging-reset boundaries (like CMSSketch.flush):
        # applying the whole batch and then resetting at most once would let
        # a batch larger than the remaining sample window skip agings, so
        # batched and scalar driving would diverge.
        while pos < total:
            take = min(total - pos, self.sample_size - self._ops)
            self.table = update(self.table, keys[pos : pos + take], cap=self.cap)
            self._ops += take
            pos += take
            if self._ops >= self.sample_size:
                self.table = reset(self.table)
                self._ops //= 2

    def estimate(self, keys):
        keys = jnp.atleast_1d(jnp.asarray(keys, jnp.int32))
        return estimate(self.table, keys)
