"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles, executed in interpret mode on CPU (kernels target TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

# ---------------------------------------------------------------------------
# count-min sketch
# ---------------------------------------------------------------------------
from repro.kernels.cms import ops as cms_ops
from repro.kernels.cms import ref as cms_ref


class TestCMSKernel:
    @pytest.mark.parametrize("width", [512, 1024, 4096])
    @pytest.mark.parametrize("n_keys", [1, 64, 300])
    def test_update_matches_ref(self, width, n_keys):
        rng = np.random.default_rng(width + n_keys)
        table = jnp.asarray(rng.integers(0, 10, (cms_ref.ROWS, width)), jnp.int32)
        keys = jnp.asarray(rng.integers(0, 1 << 31, n_keys), jnp.int32)
        a = cms_ops.update(table, keys, use_pallas=True)
        b = cms_ops.update(table, keys, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("width", [512, 2048])
    def test_estimate_matches_ref(self, width):
        rng = np.random.default_rng(width)
        table = jnp.asarray(rng.integers(0, 15, (cms_ref.ROWS, width)), jnp.int32)
        keys = jnp.asarray(rng.integers(0, 1 << 31, 200), jnp.int32)
        a = cms_ops.estimate(table, keys, use_pallas=True)
        b = cms_ops.estimate(table, keys, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_cap_saturation(self):
        table = cms_ops.make_table(512)
        keys = jnp.full((100,), 42, jnp.int32)
        table = cms_ops.update(table, keys, cap=15)
        assert int(cms_ops.estimate(table, jnp.asarray([42], jnp.int32))[0]) == 15

    @pytest.mark.parametrize("width", [512, 2048])
    @pytest.mark.parametrize("n_upd,n_est", [(1, 1), (64, 7), (300, 33)])
    def test_fused_update_estimate_matches_staged(self, width, n_upd, n_est):
        """The fused one-launch op == update followed by estimate, on both
        the Pallas (interpret) and the jnp reference path."""
        rng = np.random.default_rng(width + n_upd + n_est)
        table = jnp.asarray(rng.integers(0, 12, (cms_ref.ROWS, width)), jnp.int32)
        upd = jnp.asarray(rng.integers(0, 1 << 31, n_upd), jnp.int32)
        est = jnp.asarray(rng.integers(0, 1 << 31, n_est), jnp.int32)
        staged_table = cms_ops.update(table, upd, use_pallas=False)
        staged_vals = cms_ops.estimate(staged_table, est, use_pallas=False)
        for use_pallas in (True, False):
            new_table, vals = cms_ops.update_estimate(table, upd, est, use_pallas=use_pallas)
            np.testing.assert_array_equal(np.asarray(new_table), np.asarray(staged_table))
            np.testing.assert_array_equal(np.asarray(vals), np.asarray(staged_vals))

    def test_fused_update_estimate_saturates(self):
        table = cms_ops.make_table(512)
        upd = jnp.full((100,), 42, jnp.int32)
        new_table, vals = cms_ops.update_estimate(table, upd, jnp.asarray([42], jnp.int32), cap=15)
        assert int(vals[0]) == 15

    @pytest.mark.parametrize("B,P,K", [(1, 16, 1), (4, 16, 3), (8, 64, 2)])
    def test_segmented_update_estimate_matches_staged(self, B, P, K):
        """ISSUE 5: the one-dispatch B-decision segmented op — each
        decision's estimates must observe exactly the increment segments
        that precede it (padded lanes masked by n_pend), value-identical
        to B staged update-then-estimate rounds."""
        rng = np.random.default_rng(B * 1000 + P + K)
        width = 512
        table0 = jnp.asarray(rng.integers(0, 12, (cms_ref.ROWS, width)), jnp.int32)
        upd = jnp.asarray(rng.integers(0, 1 << 31, (B, P)), jnp.int32)
        npend = jnp.asarray(rng.integers(0, P + 1, B), jnp.int32)
        est = jnp.asarray(rng.integers(0, 1 << 31, (B, K)), jnp.int32)
        # staged reference: per decision, apply its live segment then score
        table = table0
        want = []
        for d in range(B):
            seg = upd[d, : int(npend[d])]
            if int(npend[d]):
                table = cms_ops.update(table, seg, use_pallas=False)
            want.append(np.asarray(cms_ops.estimate(table, est[d], use_pallas=False)))
        for use_pallas in (True, False):
            new_table, vals = cms_ops.update_estimate_segments(
                table0, upd, npend, est, use_pallas=use_pallas)
            np.testing.assert_array_equal(np.asarray(new_table), np.asarray(table))
            np.testing.assert_array_equal(np.asarray(vals), np.stack(want))

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 100), min_size=1, max_size=128))
    def test_never_underestimates(self, key_list):
        from collections import Counter

        table = cms_ops.make_table(1024)
        keys = jnp.asarray(key_list, jnp.int32)
        table = cms_ops.update(table, keys, cap=255)
        est = np.asarray(cms_ops.estimate(table, keys))
        cnt = Counter(key_list)
        for i, k in enumerate(key_list):
            assert est[i] >= cnt[k]

    def test_reset_halves(self):
        table = cms_ops.make_table(512)
        table = cms_ops.update(table, jnp.asarray([7] * 8, jnp.int32), cap=255)
        before = int(cms_ops.estimate(table, jnp.asarray([7], jnp.int32))[0])
        after = int(cms_ops.estimate(cms_ops.reset(table), jnp.asarray([7], jnp.int32))[0])
        assert after == before // 2

    def test_device_sketch_tracks_frequency(self):
        sk = cms_ops.DeviceSketch(256)
        for _ in range(5):
            sk.increment(jnp.asarray([1, 2, 3], jnp.int32))
        est = np.asarray(sk.estimate(jnp.asarray([1, 99], jnp.int32)))
        assert est[0] >= 5 and est[1] == 0


def _crafted_indexes(width):
    """Row indexes [case, ROWS] that stress the one-key kernel's tiles: all
    four rows in one tile, two rows sharing a tile, lanes 0 and 127, the
    last tile."""
    last = width - 128
    mid = width // 2 // 128 * 128
    return np.asarray([
        [last + 3, last + 3, last + 100, last + 127],  # all rows, one tile
        [0, 127, mid + 5, width - 1],  # rows 0, 1 share tile 0
        [0, mid, mid + 127, last],  # lanes 0 and 127
        [width - 1] * 4,  # the last lane of the last tile
    ], np.int32)


class TestCMSUpdateOne:
    """The one-key in-place increment (``cms_update_one``) == the oracle's
    saturating increment of the key's four counters, whatever the width."""

    CAP = 15

    def _bump(self, table, idx):
        """``cms_update_ref``'s increment, at given row indexes."""
        onehot = jax.nn.one_hot(idx, table.shape[1], dtype=table.dtype)
        return jnp.minimum(table + onehot, self.CAP)

    def _one(self, table, idx, valid):
        from repro.kernels.cms.cms import cms_update_one_pallas

        one = jax.jit(cms_update_one_pallas, static_argnames=("cap",))
        return one(table, jnp.asarray(idx, jnp.int32), valid, cap=self.CAP)

    @pytest.mark.parametrize("case", ["random", "crafted", "invalid", "flush"])
    @pytest.mark.parametrize("width", [128, 1024, 65536, 131072])
    def test_matches_ref(self, width, case):
        rng = np.random.default_rng(width)
        table = jnp.asarray(rng.integers(0, self.CAP + 1, (cms_ref.ROWS, width)), jnp.int32)
        keys = jnp.asarray(rng.integers(0, 1 << 31, 4), jnp.int32)
        if case == "random":
            for k in keys:
                key = k.reshape(1)
                want, _ = cms_ref.cms_update_estimate_ref(table, key, key, cap=self.CAP)
                got = self._one(table, cms_ref.row_indexes(key, width)[:, 0], 1)
                np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        elif case == "crafted":
            for idx in _crafted_indexes(width):
                for at_cap in (False, True):
                    t = table.at[jnp.arange(cms_ref.ROWS), idx].set(self.CAP) if at_cap else table
                    got = self._one(t, idx, 1)
                    np.testing.assert_array_equal(np.asarray(got), np.asarray(self._bump(t, idx)))
        elif case == "invalid":
            for idx in [*_crafted_indexes(width), cms_ref.row_indexes(keys[:1], width)[:, 0]]:
                np.testing.assert_array_equal(np.asarray(self._one(table, idx, 0)),
                                              np.asarray(table))
        else:  # flush_scores on one key == the width-sweep kernel's flush
            from repro.kernels.cms.cms import cms_update_estimate_pallas

            est = jnp.concatenate([keys, jnp.asarray(rng.integers(0, 1 << 31, 3), jnp.int32)])
            est_idx = cms_ref.row_indexes(est, width)
            for n_pend in (1, 0):
                upd_idx = cms_ref.row_indexes(keys[:1], width)
                upd_idx = jnp.where(n_pend > 0, upd_idx, width)
                want_t, want_v = cms_update_estimate_pallas(table, upd_idx, est_idx, cap=self.CAP)
                got_t, got_v = cms_ops.flush_scores_jit(
                    table, keys[:1], n_pend, est, cap=self.CAP, use_pallas=True,
                    interpret=True)
                np.testing.assert_array_equal(np.asarray(got_t), np.asarray(want_t))
                np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v.min(0)))


class TestDeviceSketchAging:
    """Regression (ISSUE 4): ``DeviceSketch.increment`` applied a whole
    batch and then reset at most once, so a 1000-key batch at
    ``sample_size=160`` left ``_ops=500 >= sample_size`` and skipped ~5
    agings. Batches must split at reset boundaries like ``CMSSketch.flush``
    so batched and scalar driving stay identical."""

    def test_batch_matches_scalar_driving(self):
        keys = [(i * 17) % 97 for i in range(1000)]
        batched = cms_ops.DeviceSketch(16, sample_factor=10)  # sample_size=160
        batched.increment(jnp.asarray(keys, jnp.int32))
        scalar = cms_ops.DeviceSketch(16, sample_factor=10)
        for k in keys:
            scalar.increment(jnp.asarray([k], jnp.int32))
        assert batched._ops == scalar._ops
        np.testing.assert_array_equal(
            np.asarray(batched.table), np.asarray(scalar.table))

    def test_ops_counter_stays_inside_sample(self):
        sk = cms_ops.DeviceSketch(16, sample_factor=10)
        sk.increment(jnp.asarray(list(range(1000)), jnp.int32))
        assert sk._ops < sk.sample_size

    def test_split_is_batch_size_invariant(self):
        keys = list(range(500))
        whole = cms_ops.DeviceSketch(16, sample_factor=10)
        whole.increment(jnp.asarray(keys, jnp.int32))
        chunked = cms_ops.DeviceSketch(16, sample_factor=10)
        for lo in range(0, 500, 77):
            chunked.increment(jnp.asarray(keys[lo:lo + 77], jnp.int32))
        assert whole._ops == chunked._ops
        np.testing.assert_array_equal(
            np.asarray(whole.table), np.asarray(chunked.table))


class TestCounterDraws:
    """The device-side counter RNG (uint32 limb splitmix64) must reproduce
    the host victim-sampling stream of repro.core.crng bit-for-bit."""

    @pytest.mark.parametrize("seed,decision,start,count", [
        (0, 0, 0, 1),
        (0x5EED, 1, 0, 64),
        (0xA11CE, 12345, 7, 33),
        (2**63 + 11, 2**31, 1000, 128),
    ])
    def test_matches_host_stream(self, seed, decision, start, count):
        from repro.core import crng

        host = crng.draws(seed, decision, start, count)
        dev = np.asarray(cms_ops.counter_draws(seed, decision, start, count))
        np.testing.assert_array_equal(dev[0], (host >> np.uint64(32)).astype(np.uint32))
        np.testing.assert_array_equal(
            dev[1], (host & np.uint64(0xFFFFFFFF)).astype(np.uint32))

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**64 - 1), st.integers(0, 2**32), st.integers(0, 500))
    def test_matches_host_stream_property(self, seed, decision, start):
        from repro.core import crng

        host = crng.draws(seed, decision, start, 16)
        dev = np.asarray(cms_ops.counter_draws(seed, decision, start, 16))
        combined = dev[0].astype(np.uint64) << np.uint64(32) | dev[1].astype(np.uint64)
        np.testing.assert_array_equal(combined, host)


class TestDeviceAdmissionPrimitives:
    """In-kernel building blocks of the device admission plane must agree
    exactly with their host twins."""

    @pytest.mark.parametrize("n", [1, 2, 5, 7, 127, 1000, 1 << 20, (1 << 24) - 1])
    def test_mod_u64_matches_host(self, n):
        from repro.core import crng
        from repro.kernels.admission import _mod_u64

        draws = crng.draws(3, 7, 0, 256)
        hi = jnp.asarray((draws >> np.uint64(32)).astype(np.uint32))
        lo = jnp.asarray((draws & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        got = np.asarray(jax.jit(_mod_u64)(hi, lo, jnp.uint32(n)))
        np.testing.assert_array_equal(got, (draws % np.uint64(n)).astype(np.uint32))

    def test_step_slots_match_host_draw_stream(self):
        from repro.core import crng
        from repro.kernels.admission import _step_slots

        seed, decision, n = 0xA11CE, 42, 37
        base = crng.stream_key(seed, decision)
        for step in (0, 1, 13):
            host = crng.draws(seed, decision, step * 5, 5) % np.uint64(n)
            dev = np.asarray(_step_slots(
                jnp.uint32(base >> 32), jnp.uint32(base & 0xFFFFFFFF),
                step * 5, 5, jnp.uint32(n)))
            np.testing.assert_array_equal(dev, host.astype(np.int32))

    def test_argmin_frac_exact_ordering(self):
        from repro.kernels.admission import _argmin_frac

        # 3/7 < 5/11 < 1/2 == 2/4: exact cross-multiply ordering with
        # first-position tie-breaking, invalid entries ignored
        num = jnp.asarray([1, 5, 3, 2, 0, 0, 0, 0], jnp.int32)
        den = jnp.asarray([2, 11, 7, 4, 1, 1, 1, 1], jnp.int32)
        pos = jnp.arange(8, dtype=jnp.int32)
        valid = jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], bool)
        assert int(_argmin_frac(num, den, pos, valid)) == 2
        valid = jnp.asarray([1, 0, 0, 1, 0, 0, 0, 0], bool)  # tie 1/2 vs 2/4
        assert int(_argmin_frac(num, den, pos, valid)) == 0

    @pytest.mark.parametrize("eviction", [
        "sampled_frequency", "sampled_size", "sampled_frequency_size",
        "sampled_needed_size", "random"])
    def test_sample_picker_matches_iter_victims(self, eviction):
        """The device sample picker, called until every slot is taken,
        yields the host walk's whole victim order (pool draws, rule
        scores, ties, and the fallback scans of the late steps) from the
        same seed, decision and sketch table."""
        import functools

        from repro.core import crng
        from repro.core.eviction import make_eviction
        from repro.kernels.admission import _sample_picker

        rng = np.random.default_rng(0x51C)
        width, slots, n, needed = 128, 64, 40, 57
        table = rng.integers(0, 16, size=(cms_ref.ROWS, width)).astype(np.int32)

        def freq(key):
            idx = np.asarray(cms_ref.row_indexes(jnp.asarray([key], jnp.int32), width))
            return int(table[np.arange(cms_ref.ROWS), idx[:, 0]].min())

        e = make_eviction(eviction, capacity=10**9, freq_fn=freq, seed=0xA11CE)
        keys = rng.choice(1 << 20, size=n, replace=False).tolist()
        for k, s in zip(keys, rng.integers(1, 9, size=n).tolist()):
            e.insert(k, 8 * s)  # few distinct sizes: score ties happen
        for _ in range(3):
            e.begin_decision()
        host = list(e.iter_victims(needed))
        assert sorted(host) == sorted(keys)

        @functools.partial(jax.jit, static_argnames=("rule", "sample"))
        def order(table, mk_lo, msz, bhi, blo, *, rule, sample):
            next_victim = _sample_picker(table, mk_lo, msz, jnp.int32(n),
                                         jnp.int32(needed), bhi, blo,
                                         rule=rule, sample=sample)

            def body(carry, _):
                taken, step, fb = carry
                best, step, fb = next_victim(taken, step, fb)
                return (taken.at[best].set(True), step, fb), best

            init = (jnp.zeros(slots, bool), jnp.int32(0), jnp.int32(0))
            (_, _, fb), seq = jax.lax.scan(body, init, None, length=n)
            return seq, fb

        mk_lo = np.zeros(slots, np.int32)
        msz = np.zeros(slots, np.int32)
        mk_lo[:n] = e.keys
        msz[:n] = [e.sizes[k] for k in e.keys]
        base = crng.stream_key(e.seed, e.decision)
        seq, fb = order(jnp.asarray(table), jnp.asarray(mk_lo), jnp.asarray(msz),
                        jnp.uint32(base >> 32), jnp.uint32(base & 0xFFFFFFFF),
                        rule=e.rule, sample=e.SAMPLE)
        assert [e.keys[s] for s in np.asarray(seq).tolist()] == host
        assert int(fb) == e.fallback_scans > 0

    @pytest.mark.parametrize("eviction", ["lru", "slru"])
    def test_recency_select_matches_eviction_order(self, eviction):
        """The device recency selector over stamped slots (rows in a
        shuffled slot order, each stamped at its last insert or touch)
        yields the host policy's victim order: LRU first, and for SLRU the
        whole probation segment before protected."""
        from repro.core.eviction import make_eviction
        from repro.kernels.admission import _recency_select

        rng = np.random.default_rng(0x5E1)
        slots, n = 64, 40
        e = make_eviction(eviction, capacity=10**9, freq_fn=lambda k: 0)
        stamp = {}
        tick = 0
        # inserts and touches interleaved: some protected entries are
        # older than some probation ones, so probation-first is not LRU
        fresh = iter(rng.choice(10_000, size=n, replace=False).tolist())
        for touch in rng.permutation(np.r_[np.zeros(n, bool), np.ones(60, bool)]):
            if touch and not stamp:
                continue
            if touch:
                k = int(rng.choice(list(stamp)))
                e.on_access(k)
            else:
                k = next(fresh)
                e.insert(k, 10)
            stamp[k] = tick = tick + 1
        host = list(e.iter_victims())
        protected = getattr(e, "protected", {})
        if eviction == "slru":
            assert 0 < len(protected) < n, "both segments must be populated"

        slot_of = dict(zip(stamp, rng.permutation(n).tolist()))
        mstamp = np.zeros(slots, np.int32)
        mseg = np.zeros(slots, np.int32)
        for k, s in slot_of.items():
            mstamp[s] = stamp[k]
            mseg[s] = int(k in protected)
        select = jax.jit(lambda taken, mstamp, mseg: _recency_select(
            mstamp, mseg, jnp.int32(n), slru=eviction == "slru")(taken))
        taken = np.zeros(slots, bool)
        key_at = {s: k for k, s in slot_of.items()}
        got = []
        for _ in range(n):
            v = int(select(jnp.asarray(taken), jnp.asarray(mstamp), jnp.asarray(mseg)))
            taken[v] = True
            got.append(key_at[v])
        assert got == host


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
from repro.kernels.attention.flash import flash_attention_fwd_pallas
from repro.kernels.attention.ref import attention_dense_ref, flash_attention_ref


def _mk_qkv(rng, B, S, T, nq, nkv, hd, hv=None, dtype=jnp.float32):
    hv = hv or hd
    q = jnp.asarray(rng.normal(size=(B, S, nq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, nkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, nkv, hv)), dtype)
    return q, k, v


class TestFlashKernel:
    @pytest.mark.parametrize("S,T", [(128, 128), (256, 256), (100, 100), (64, 192)])
    @pytest.mark.parametrize("nq,nkv", [(4, 4), (8, 2), (6, 1)])
    def test_fwd_matches_dense(self, S, T, nq, nkv):
        rng = np.random.default_rng(S + T + nq)
        q, k, v = _mk_qkv(rng, 2, S, T, nq, nkv, 32)
        scale = 32 ** -0.5
        out = flash_attention_fwd_pallas(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            scale, causal=(S == T), bq=64, bk=64,
        )
        out = jnp.swapaxes(out, 1, 2)
        ref = attention_dense_ref(q, k, v, scale, causal=(S == T))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-4)

    @pytest.mark.parametrize("window", [None, 32])
    @pytest.mark.parametrize("softcap", [None, 30.0])
    def test_masks_and_softcap(self, window, softcap):
        rng = np.random.default_rng(7)
        q, k, v = _mk_qkv(rng, 1, 160, 160, 4, 2, 16)
        scale = 0.25
        out = flash_attention_fwd_pallas(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            scale, causal=True, window=window, softcap=softcap, bq=32, bk=32,
        )
        out = jnp.swapaxes(out, 1, 2)
        ref = attention_dense_ref(q, k, v, scale, causal=True, window=window, softcap=softcap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-4)

    def test_bf16(self):
        rng = np.random.default_rng(3)
        q, k, v = _mk_qkv(rng, 1, 128, 128, 4, 4, 32, dtype=jnp.bfloat16)
        out = flash_attention_fwd_pallas(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            0.18, causal=True, bq=64, bk=64,
        )
        ref = attention_dense_ref(q, k, v, 0.18, causal=True)
        np.testing.assert_allclose(
            np.asarray(jnp.swapaxes(out, 1, 2), dtype=np.float32),
            np.asarray(ref, dtype=np.float32), atol=3e-2, rtol=3e-2,
        )

    def test_mla_head_dims(self):
        """qk dim != v dim (DeepSeek MLA expanded form)."""
        rng = np.random.default_rng(5)
        q, k, v = _mk_qkv(rng, 1, 128, 128, 4, 4, 48, hv=16)
        out = flash_attention_fwd_pallas(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            0.2, causal=True, bq=64, bk=64,
        )
        ref = attention_dense_ref(q, k, v, 0.2, causal=True)
        np.testing.assert_allclose(
            np.asarray(jnp.swapaxes(out, 1, 2)), np.asarray(ref), atol=2e-5, rtol=2e-4
        )


class TestFlashRefGrads:
    @pytest.mark.parametrize("causal,window,softcap", [
        (True, None, None), (True, 16, None), (True, None, 30.0), (False, None, None),
    ])
    def test_vjp_matches_dense(self, causal, window, softcap):
        rng = np.random.default_rng(11)
        q, k, v = _mk_qkv(rng, 2, 65, 65, 4, 2, 16)
        scale = 0.25

        def f(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        flash = f(lambda q, k, v: flash_attention_ref(q, k, v, scale, causal, window, softcap, 32))
        dense = f(lambda q, k, v: attention_dense_ref(q, k, v, scale, causal, window, softcap))
        ga = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------
from repro.kernels.wkv.ops import wkv6
from repro.kernels.wkv.ref import wkv6_chunked, wkv6_scan


class TestWkv6Kernel:
    @pytest.mark.parametrize("T", [32, 100, 256])
    @pytest.mark.parametrize("K", [16, 64])
    def test_matches_scan(self, T, K):
        rng = np.random.default_rng(T + K)
        B, H, V = 2, 2, K
        r = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32) * 0.5
        k = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32) * 0.5
        v = jnp.asarray(rng.normal(size=(B, T, H, V)), jnp.float32) * 0.5
        w = jnp.asarray(rng.uniform(0.2, 0.999, size=(B, T, H, K)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(H, K)), jnp.float32) * 0.1
        a = wkv6(r, k, v, w, u, chunk=32)
        b = wkv6_scan(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)

    def test_extreme_decay(self):
        rng = np.random.default_rng(0)
        B, T, H, K = 1, 64, 1, 16
        r = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, H, K)), jnp.float32)
        w = jnp.asarray(rng.uniform(1e-7, 1.0, size=(B, T, H, K)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(H, K)), jnp.float32)
        a = wkv6(r, k, v, w, u, chunk=16)
        b = wkv6_scan(r, k, v, w, u)
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_chunked_jnp_matches_scan_bf16(self):
        rng = np.random.default_rng(1)
        B, T, H, K = 1, 96, 2, 16
        mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16) * 0.5
        r, k, v = mk(B, T, H, K), mk(B, T, H, K), mk(B, T, H, K)
        w = jnp.asarray(rng.uniform(0.5, 0.999, size=(B, T, H, K)), jnp.bfloat16)
        u = mk(H, K)
        a = wkv6_chunked(r, k, v, w, u, chunk=32)
        b = wkv6_scan(r, k, v, w, u)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=0.15, rtol=0.1
        )
