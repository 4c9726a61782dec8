"""Batched device-CMS frequency sketch backend for W-TinyLFU.

Bridges the policy hot path to the Pallas count-min-sketch kernels in
``repro.kernels.cms`` (interpret mode / pure-jnp reference on CPU). The
device sketch is *non-conservative* (no minimal-increment, no doorkeeper),
which buys an exactness property the batching relies on:

    saturating non-conservative increments commute — applying a batch of
    keys in one kernel call yields the same table as applying them one at
    a time, in any order.

So :class:`CMSSketch` buffers ``increment`` calls and flushes them through
one batched kernel update lazily, *just before the next estimate*. Every
estimate therefore observes exactly the increments that precede it in
access order — scalar and batched driving of a policy over the same trace
produce byte-identical admission decisions (asserted in
``tests/test_registry_engine.py``).

Aging follows the TinyLFU reset rule (paper §3): after every
``sample_factor * expected_entries`` increments all counters are halved;
flushes are split at reset boundaries so the halving lands at the same
access index as it would scalar-by-scalar.
"""

from __future__ import annotations

import numpy as np

__all__ = ["CMSSketch"]


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


class CMSSketch:
    """Drop-in ``increment``/``estimate`` sketch backed by the batched CMS
    kernels, plus ``estimate_batch`` for one-call victim-set scoring.

    Parameters
    ----------
    expected_entries: sizing hint; row width is the next power of two
        (min 128 — TPU lane alignment).
    cap: counter saturation value.
    sample_factor: reset period = ``sample_factor * expected_entries``.
    use_pallas: route through the Pallas kernels (interpret mode off-TPU);
        default picks Pallas on TPU and the jnp reference elsewhere.
    flush_block: max keys per kernel update call — both the kernel and the
        jnp reference build an intermediate of shape ``[ROWS, N, width]``,
        so an unbounded N (e.g. a long all-hit run buffering every access)
        would blow up memory; sub-batching keeps it O(flush_block * width)
        without affecting results (increments commute).
    """

    #: One kernel call scores a whole batch: the admission plane's "auto"
    #: mode picks the batched data plane for this backend.
    batched_native = True

    def __init__(
        self,
        expected_entries: int,
        *,
        cap: int = 15,
        sample_factor: int = 10,
        use_pallas: bool | None = None,
        flush_block: int = 512,
    ):
        import jax  # deferred: keep repro.core importable without jax
        import jax.numpy as jnp

        from repro.kernels.cms.ops import flush_scores_jit
        from repro.kernels.cms.ref import (
            ROWS,
            cms_estimate_ref,
            cms_update_estimate_ref,
            cms_update_ref,
        )

        self._jnp = jnp
        self._on_tpu = jax.default_backend() == "tpu"
        self.use_pallas = self._on_tpu if use_pallas is None else use_pallas
        self._flush_scores = flush_scores_jit
        self._update_ref = cms_update_ref
        self._estimate_ref = cms_estimate_ref
        self._update_estimate_ref = cms_update_estimate_ref

        expected_entries = max(16, int(expected_entries))
        width = 128
        while width < expected_entries:
            width <<= 1
        self.width = width
        self.rows = ROWS
        self.cap = int(cap)
        self.flush_block = int(flush_block)
        self.sample_size = sample_factor * expected_entries
        self.table = jnp.zeros((ROWS, width), jnp.int32)
        self.resets = 0
        self._ops = 0  # flushed increments within the current sample
        self._pending: list[int] = []

    # -- batched data plane ------------------------------------------------
    def _pallas(self, upd: np.ndarray, est: np.ndarray):
        """Apply ``upd`` increments, then estimate ``est`` on the updated
        table: one jitted fused Pallas call. Both batches are padded to
        powers of two (padded update lanes masked to the out-of-range
        sentinel, padded estimates dropped), so the batch lengths a run
        meets compile a handful of programs, not one each."""
        u = np.zeros(_pow2(len(upd)), np.int32)
        u[: len(upd)] = upd.astype(np.int32)
        e = np.zeros(_pow2(len(est)), np.int32)
        e[: len(est)] = est.astype(np.int32)
        table, vals = self._flush_scores(
            self.table, u, len(upd), e, cap=self.cap, use_pallas=True,
            interpret=not self._on_tpu)
        return table, np.asarray(vals)[: len(est)]

    def _apply(self, keys_np: np.ndarray) -> None:
        if self.use_pallas:
            self.table, _ = self._pallas(keys_np, keys_np[:0])
        else:
            keys = self._jnp.asarray(keys_np.astype(np.int32))
            self.table = self._update_ref(self.table, keys, cap=self.cap)

    def flush(self) -> None:
        """Apply buffered increments in batched kernel calls, splitting at
        aging-reset boundaries so reset timing matches scalar driving."""
        pending = self._pending
        pos = 0
        while pos < len(pending):
            take = min(len(pending) - pos, self.sample_size - self._ops, self.flush_block)
            self._apply(np.asarray(pending[pos : pos + take], dtype=np.int64))
            pos += take
            self._ops += take
            if self._ops >= self.sample_size:
                self.table = self.table >> 1
                self._ops //= 2
                self.resets += 1
        self._pending = []

    # -- FrequencySketch-compatible control plane --------------------------
    def increment(self, key: int) -> None:
        """Record one occurrence (buffered; flushed before the next estimate)."""
        self._pending.append(key)

    def increment_batch(self, keys) -> None:
        """Record a whole chunk of occurrences (buffered)."""
        self._pending.extend(np.asarray(keys, dtype=np.int64).tolist())

    def estimate(self, key: int) -> int:
        return int(self.estimate_batch(np.asarray([key], dtype=np.int64))[0])

    def estimate_batch(self, keys) -> np.ndarray:
        """Frequency estimates for ``keys`` — the data plane's single scoring
        entry point. When increments are pending and fit one sub-batch with no
        aging reset due, the flush and the scoring run as ONE fused kernel
        call (update + estimate-on-updated-table); otherwise the staged
        ``flush()`` runs first and a plain estimate follows."""
        if not isinstance(keys, (list, tuple, np.ndarray)):
            # e.g. the admission plane's lazy victim-prefix view: a device
            # sketch scores the whole prefix eagerly in its one kernel call
            keys = list(keys)
        pending = self._pending
        n = len(pending)
        if 0 < n <= self.flush_block and self._ops + n < self.sample_size:
            upd = np.asarray(pending, dtype=np.int64).astype(np.int32)
            est = np.asarray(keys, dtype=np.int64).astype(np.int32)
            if self.use_pallas:
                self.table, vals = self._pallas(upd, est)
            else:
                self.table, vals = self._update_estimate_ref(
                    self.table, self._jnp.asarray(upd),
                    self._jnp.asarray(est), cap=self.cap
                )
            self._ops += n
            self._pending = []
            return np.asarray(vals)
        self.flush()
        keys = np.asarray(keys, dtype=np.int64).astype(np.int32)
        if self.use_pallas:
            return self._pallas(keys[:0], keys)[1]
        return np.asarray(self._estimate_ref(self.table, self._jnp.asarray(keys)))
