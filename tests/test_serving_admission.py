"""Serving admission pipeline tests (ISSUE 6): hook unit behaviour, the
async == sync byte-identity contract at the PrefixCache level, the
lookup/eviction coherence regression (stale-entry guard), scheduler live
block accounting under preemption, and the shared-pool reclaim hook."""

import numpy as np
import pytest

from repro.serving import (
    AsyncAdmissionPipeline,
    BlockPool,
    PrefixCache,
    PrefixCacheConfig,
    Request,
    Scheduler,
    SchedulerConfig,
    SyncAdmission,
    block_hashes,
    make_admission_hook,
)

DEVICE_SPEC = (
    "wtlfu-av-sampled_frequency"
    "?data_plane=device_full&chunk=16&sketch_backend=cms"
)


def make_cache(policy="wtlfu-av", admission="sync", capacity_blocks=16,
               block_size=4, bpt=10, headroom=0, chunk=None):
    return PrefixCache(PrefixCacheConfig(
        capacity_bytes=capacity_blocks * block_size * bpt,
        block_size=block_size, bytes_per_token=bpt, policy=policy,
        admission=admission, admission_chunk=chunk,
        pool_headroom_blocks=headroom))


def drive(cache, n=400, seed=0, key_space=12):
    """Zipf-reused template stream: lookup (with unique suffix) + offer."""
    rng = np.random.default_rng(seed)
    for i in range(n):
        base = int((rng.zipf(1.3) - 1) % key_space)
        length = (1 + base % 4) * cache.cfg.block_size
        prompt = [base * 1000 + j for j in range(length)]
        cache.lookup(prompt + [10**6 + i])
        cache.offer(prompt)
    cache.sync()
    return cache


def assert_caches_identical(sync, a):
    for k in ("request_hit_ratio", "token_hit_ratio", "byte_hit_ratio"):
        assert getattr(sync, k) == getattr(a, k), k
    assert set(sync.entries) == set(a.entries)
    for f in ("accesses", "hits", "bytes_hit", "admissions", "rejections",
              "evictions"):
        assert getattr(sync.policy.stats, f) == getattr(a.policy.stats, f), f
    if hasattr(sync.policy, "window"):  # W-TinyLFU internals
        assert list(sync.policy.window.items()) == list(a.policy.window.items())
        assert sync.policy.main.sizes == a.policy.main.sizes


class TestHooks:
    def test_sync_hook_verdict_inline(self):
        c = make_cache()
        hook = c.admission
        assert isinstance(hook, SyncAdmission) and not hook.is_async
        assert hook.offer(1, 40) is True  # empty cache admits
        assert 1 in hook
        assert hook.sync() == []  # nothing ever pending
        m = hook.metrics()
        assert m["mode"] == "sync" and m["events"] == 1
        assert m["decision_p99_ms"] >= m["decision_p50_ms"] >= 0.0

    def test_async_hook_queues_until_chunk(self):
        c = make_cache(admission="async", chunk=8)
        hook = c.admission
        assert isinstance(hook, AsyncAdmissionPipeline) and hook.is_async
        for i in range(7):
            hook.offer(100 + i, 40)
        assert hook.queue_depth == 7 and hook.pumps == 0
        hook.offer(107, 40)  # eighth event trips the pump
        assert hook.queue_depth == 0 and hook.pumps == 1

    def test_async_verdicts_in_offer_order(self):
        c = make_cache(admission="async")
        hook = c.admission
        for key in (5, 3, 9):
            hook.offer(key, 40)
        verdicts = hook.sync()
        assert [k for k, _ in verdicts] == [5, 3, 9]
        assert all(adm for _, adm in verdicts)  # empty cache admits all
        assert not hook.has_pending_offers
        m = hook.metrics()
        assert m["mode"] == "async" and m["syncs"] == 1
        assert m["max_queue_depth"] == 3

    def test_resident_prefix_offer_forces_no_resolve(self):
        """PrefixCache: re-offering a materialized prefix with no verdict
        pending pends nothing, so the next matching lookup is answered
        without a resolve."""
        c = make_cache(admission="async")
        prompt = list(range(8))  # two blocks
        c.offer(prompt)
        c.sync()
        assert c.lookup(prompt + [99])[0] == 8
        syncs = c.admission.syncs
        assert c.offer(prompt, payload="kv") is None
        assert not c.admission.has_pending_offers and not c._pending_cands
        assert c.entries[block_hashes(prompt, 4)[-1]].payload == "kv"
        assert c.lookup(prompt + [98])[0] == 8
        assert c.admission.syncs == syncs, "a known hit forced a resolve"

    def test_resident_offer_behind_a_pending_eviction(self):
        """A re-offer of a materialized prefix while another offer is
        pending may no longer hit (the pending offer can evict it), so it
        pends and resolves like the synchronous replay."""
        caches = [make_cache(policy="lru", admission=m, capacity_blocks=4)
                  for m in ("sync", "async")]
        for c in caches:
            a, b, new = ([t * 100 + j for j in range(8)] for t in (1, 2, 3))
            for prompt in (a, b):
                c.offer(prompt)
                c.sync()
            c.offer(new)  # evicts a, the LRU entry
            c.offer(a)  # a miss now: re-admitted, evicting b
            c.sync()
        sync, a = caches
        assert len(sync.entries) == 2
        assert_caches_identical(sync, a)

    def test_unknown_mode_raises(self):
        c = make_cache()
        with pytest.raises(ValueError, match="unknown admission mode"):
            make_admission_hook(c.policy, "lazy")


class TestAsyncIdentity:
    """Async pipeline replays byte-identically against the sync hook."""

    @pytest.mark.parametrize("policy", ["wtlfu-av", "wtlfu-qv", "lru"])
    def test_host_plane_identity(self, policy):
        sync = drive(make_cache(policy=policy, admission="sync"))
        a = drive(make_cache(policy=policy, admission="async"))
        assert_caches_identical(sync, a)
        assert sync.request_hit_ratio > 0  # regime sanity

    def test_device_full_identity(self):
        sync = drive(make_cache(policy=DEVICE_SPEC, admission="sync"), n=250)
        a = drive(make_cache(policy=DEVICE_SPEC, admission="async"), n=250)
        assert_caches_identical(sync, a)
        m = a.admission.metrics()
        assert m["deferred_dispatches"] > 0, "pipeline never deferred"
        assert m["chunk_calls"] < m["decisions"], "batching not engaging"

    def test_cold_miss_answered_without_resolve(self):
        """Deep batching: a lookup that cannot match anything pending must
        not drain the pipeline."""
        c = make_cache(admission="async", chunk=64)
        c.offer(list(range(8)))
        pumps_before = c.admission.pumps
        syncs_before = c.admission.syncs
        n, e = c.lookup([9999 + j for j in range(8)])
        assert n == 0 and e is None
        assert c.admission.pumps == pumps_before
        assert c.admission.syncs == syncs_before
        assert c.admission.has_pending_offers

    def test_pending_hash_intersection_resolves(self):
        """A lookup overlapping a pending candidate's hash chain must see
        the admitted entry (the verdict could flip the answer)."""
        c = make_cache(admission="async", chunk=64)
        prompt = list(range(8))
        c.offer(prompt)
        n, e = c.lookup(prompt)
        assert n == 8 and e is not None


class TestLookupEvictionCoherence:
    """Regression (satellite 1): the policy dropping an entry while the
    serving view still holds it must never serve the stale entry."""

    def test_stale_entry_not_served_after_external_eviction(self):
        c = make_cache(policy="lru", capacity_blocks=4, block_size=4)
        prompt = list(range(8))  # 2 blocks
        assert c.offer(prompt)
        key = block_hashes(prompt, 4)[-1]
        # drive the policy from outside the cache: enough foreign objects
        # to evict the entry without the view hearing about it
        for i in range(8):
            c.policy.access(10**9 + i, 2 * c.block_bytes)
        assert key not in c.policy and key in c.entries  # view is stale
        n, e = c.lookup(prompt)
        assert n == 0 and e is None, "stale entry served after eviction"
        assert c.stale_rewalks > 0
        assert key not in c.entries  # guard resynced the view

    def test_stale_guard_releases_blocks(self):
        c = make_cache(policy="lru", capacity_blocks=4, block_size=4)
        c.offer(list(range(8)))
        used = c.pool.num_used
        for i in range(8):
            c.policy.access(10**9 + i, 2 * c.block_bytes)
        c.lookup(list(range(8)))
        assert c.pool.num_used < used
        c.pool.check_invariants()


class TestSchedulerBlockAccounting:
    """Satellite 2: preempt -> resubmit -> finish never double-frees or
    leaks live KV blocks."""

    def _sched(self, num_blocks=8, max_running=4):
        pool = BlockPool(num_blocks)
        return Scheduler(SchedulerConfig(max_running=max_running),
                         pool=pool, block_size=4), pool

    def test_preempt_resubmit_finish_cycle(self):
        sched, pool = self._sched()
        req = Request(0, list(range(6)), 2)  # 2 blocks live
        sched.submit(req)
        pf, _ = sched.schedule()
        assert pf == [req] and pool.num_used == 2
        sched.on_prefilled(req)
        sched.preempt(req)
        assert req.block_ids == [] and pool.num_used == 0
        # double-release is a no-op (idempotent)
        sched._release_blocks(req)
        assert pool.num_used == 0
        pf, _ = sched.schedule()  # resubmitted head reacquires
        assert pf == [req] and pool.num_used == 2
        sched.on_prefilled(req)
        sched.on_token(req, 1)
        sched.on_token(req, 2)
        assert req.done and pool.num_used == 0
        pool.check_invariants()

    def test_alloc_failure_leaves_request_queued(self):
        sched, pool = self._sched(num_blocks=2)
        big = Request(0, list(range(20)), 4)  # needs 6 blocks > pool
        sched.submit(big)
        pf, _ = sched.schedule()
        assert pf == [] and sched.alloc_failures == 1
        assert sched.waiting[0] is big and big.block_ids == []
        pool.check_invariants()

    def test_preemption_storm_never_leaks(self):
        sched, pool = self._sched(num_blocks=6, max_running=2)
        for i in range(4):
            sched.submit(Request(i, list(range(6)), 2))
        rng = np.random.default_rng(3)
        for _ in range(200):
            pf, _ = sched.schedule()
            for r in pf:
                sched.on_prefilled(r)
            if sched.running and rng.random() < 0.3:
                sched.preempt(sched.running[-1])
            for r in list(sched.running):
                sched.on_token(r, 0)
            pool.check_invariants()
            if not sched.has_work:
                break
        assert not sched.has_work
        assert pool.num_used == 0 and len(sched.finished) == 4


class TestSharedPoolReclaim:
    """The BlockPool admission hook: live allocations push cached prefixes
    out instead of failing."""

    def test_shortage_reclaims_cached_entries(self):
        c = make_cache(capacity_blocks=8, block_size=4)
        for i in range(3):
            assert c.offer([i * 100 + j for j in range(8)])  # 2 blocks each
        assert c.pool.num_free == 2 and len(c.entries) == 3
        got = c.pool.alloc(5)  # live demand exceeds free: hook reclaims
        assert got is not None and len(got) == 5
        assert c.pool.reclaims == 1 and len(c.entries) < 3
        # policy byte-accounting followed the discards
        for k in c.entries:
            assert k in c.policy
        c.pool.check_invariants()

    def test_headroom_blocks_extend_pool_not_policy(self):
        flat = make_cache(capacity_blocks=8, block_size=4)
        roomy = make_cache(capacity_blocks=8, block_size=4, headroom=5)
        assert roomy.pool.num_blocks == flat.pool.num_blocks + 5
        assert roomy.policy.capacity == flat.policy.capacity

    def test_shortage_reclaim_follows_policy_victim_order(self):
        """ISSUE 8 satellite (failing before): shortage reclaim used to
        walk ``self.entries`` in FIFO materialization order, evicting the
        oldest-offered entry regardless of its access history. The order
        now comes from the eviction policy's own victim ranking
        (``reclaim_victims``): a recently touched old entry outlives
        never-touched newer ones."""
        c = make_cache(capacity_blocks=8, block_size=4)
        prompts = [[i * 100 + j for j in range(8)] for i in range(3)]
        for p in prompts:
            assert c.offer(p)  # 2 blocks each; FIFO order 0, 1, 2
        fifo_first = next(iter(c.entries))
        # touch the oldest entry: the policy now ranks it last-to-evict
        depth, _ = c.lookup(prompts[0])
        assert depth == 8
        ranked = list(c.policy.reclaim_victims(2 * c.block_bytes))
        assert ranked[-1] == fifo_first and ranked[0] != fifo_first
        got = c.pool.alloc(5)  # shortage: reclaims two entries
        assert got is not None and c.pool.reclaims == 1
        assert list(c.entries) == [fifo_first], \
            "reclaim took the FIFO head instead of the policy's victims"
        c.pool.check_invariants()

    def test_reclaim_keeps_policy_byte_accounting(self):
        """After a shortage reclaim, the policy's resident-byte view must
        match the entries that actually survived — ``policy.discard`` ran
        for every reclaimed entry, none leaked ghost bytes."""
        c = make_cache(capacity_blocks=8, block_size=4)
        for i in range(3):
            assert c.offer([i * 100 + j for j in range(8)])
        assert c.pool.alloc(5) is not None
        assert c.policy.used_bytes() == sum(
            e.n_blocks * c.block_bytes for e in c.entries.values())
        for k in c.entries:
            assert k in c.policy
        c.pool.check_invariants()

    def test_nested_reclaim_reports_zero_honestly(self):
        """ISSUE 8 satellite (failing before): re-entry into
        ``reclaim_blocks`` (``policy.discard`` → pipeline sync → pool
        traffic) used to report the OUTER call's planned blocks as its
        own. A nested call now returns 0 — it freed nothing — and the
        outer call's accounting stays consistent."""
        c = make_cache(capacity_blocks=8, block_size=4)
        for i in range(3):
            assert c.offer([i * 100 + j for j in range(8)])
        nested: list[int] = []
        orig_discard = c.policy.discard

        def reentrant_discard(key):
            nested.append(c.reclaim_blocks(4))  # re-entry mid-reclaim
            return orig_discard(key)

        c.policy.discard = reentrant_discard
        freed = c.reclaim_blocks(2)
        c.policy.discard = orig_discard
        assert nested and all(v == 0 for v in nested), nested
        assert freed >= 2  # the outer call did the actual work
        assert c.policy.used_bytes() == sum(
            e.n_blocks * c.block_bytes for e in c.entries.values())
        c.pool.check_invariants()

    def test_reclaim_resolves_pending_verdicts_first(self):
        c = make_cache(admission="async", capacity_blocks=8, block_size=4)
        c.offer(list(range(8)))
        assert c.admission.has_pending_offers
        c.reclaim_blocks(0)
        assert not c.admission.has_pending_offers
