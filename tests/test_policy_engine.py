"""Tests for the fused on-device policy engine: exact bin-indexed victim
selection (no candidate window), the multi-epoch scan path, and the manager's
on-device state handling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import policy
from repro.core.manager import CentralManager
from repro.core.types import (
    TIER_FAST,
    TIER_SLOW,
    PageState,
    PolicyParams,
    PolicyState,
    TenantState,
)


def _single_tenant(P, tier, counts, F, R):
    pages = PageState.create(P)._replace(
        owner=jnp.zeros((P,), jnp.int32),
        tier=jnp.asarray(tier, jnp.int8),
        count=jnp.asarray(counts, jnp.uint32),
    )
    tenants = TenantState.create(1)._replace(
        active=jnp.ones((1,), bool),
        t_miss=jnp.asarray([0.05], jnp.float32),
        a_miss=jnp.asarray([0.9], jnp.float32),
        arrival=jnp.zeros((1,), jnp.int32),
    )
    params = PolicyParams(
        fast_capacity=jnp.int32(F),
        migration_budget=jnp.int32(R),
        sample_period=jnp.int32(1),
    )
    return pages, tenants, params


class TestExactSelection:
    def test_no_4096_candidate_window(self):
        """>4096 slow candidates per tenant: the true hottest pages win.

        The seed gathered sorted counts through a W=4096 window, silently
        truncating victim selection; the counting-rank engine is exact. Put
        the genuinely hot pages at ids beyond any window position so a
        truncating implementation cannot find them.
        """
        P, F, R = 10000, 256, 128
        tier = np.full(P, TIER_SLOW)
        tier[:64] = TIER_FAST  # a few cold fast pages
        counts = np.zeros(P, np.int64)
        # ~9900 warm slow candidates, then the true hot set at the very end
        counts[64:] = 2
        hot_ids = np.arange(P - 100, P)
        counts[hot_ids] = 30
        pages, tenants, params = _single_tenant(P, tier, counts, F, R)
        sampled = jnp.zeros((P,), jnp.uint32)
        _, _, plan, stats = policy.policy_epoch(
            pages, tenants, sampled, params, max_tenants=1, plan_size=R
        )
        promoted = np.asarray(plan.promote)
        promoted = set(promoted[promoted >= 0].tolist())
        assert len(promoted) >= 32, "expected a substantial promotion quota"
        # every promoted page must come from the true hottest candidates: all
        # 100 hot pages (count 30) rank strictly before any count-2 page, and
        # the quota here is < 100 — a windowed implementation would promote
        # warm low-id pages instead.
        assert promoted <= set(hot_ids.tolist()), (
            "window truncation: promoted warm pages while hotter pages exist"
        )

    def test_tie_break_is_lowest_page_id(self):
        """Within a count bucket the stable (seed lexsort) order holds."""
        P, F, R = 64, 8, 8
        tier = np.full(P, TIER_SLOW)
        tier[:4] = TIER_FAST
        counts = np.zeros(P, np.int64)
        counts[10:30] = 7  # 20 tied candidates, quota smaller
        pages, tenants, params = _single_tenant(P, tier, counts, F, R)
        _, _, plan, _ = policy.policy_epoch(
            pages, tenants, jnp.zeros((P,), jnp.uint32), params, max_tenants=1, plan_size=R
        )
        promoted = np.asarray(plan.promote)
        promoted = sorted(promoted[promoted >= 0].tolist())
        assert promoted == list(range(10, 10 + len(promoted)))

    @pytest.mark.parametrize("P", [700, 2048, 3 * 1024 + 5])
    def test_bucket_cutoffs_match_rank_reference(self, P):
        """The r-th member's page id per tenant (P when it has fewer) equals
        a numpy rank reference, for both member sets: residuals of 0, of
        the member count and one past it, ending on a block's last member,
        and random; members spread over several blocks; an empty slot."""
        rng = np.random.default_rng(P)
        T = 6  # slot 5 holds no page
        for _ in range(4):
            owner = rng.integers(0, T - 1, P)
            mp = rng.random(P) < 0.3
            md = (rng.random(P) < 0.3) & ~mp  # disjoint sides
            rs = []
            for member in (mp, md):
                n = [int((member & (owner == t)).sum()) for t in range(T)]
                first = np.flatnonzero(member & (owner == 2))
                r = rng.integers(-1, max(n) + 2, T)
                r[0], r[1], r[3], r[5] = n[0], n[1] + 1, 0, 1
                r[2] = (first < 1024).sum()  # the first block's last member
                rs.append(r)
            x_p, x_d = policy._bucket_cutoffs(
                jnp.asarray(mp), jnp.asarray(md), jnp.asarray(owner, jnp.int32),
                jnp.asarray(rs[0], jnp.int32), jnp.asarray(rs[1], jnp.int32), T,
            )
            for member, r, x in ((mp, rs[0], x_p), (md, rs[1], x_d)):
                for t in range(T):
                    ids = np.flatnonzero(member & (owner == t))
                    if r[t] >= 1:
                        want = ids[r[t] - 1] if r[t] <= len(ids) else P
                        assert int(x[t]) == want, (t, int(r[t]), len(ids))

    def test_selection_matches_lexsort_reference(self):
        """Promote/demote sets equal a numpy lexsort reference (exact ranks,
        stable tie-break) across random states."""
        rng = np.random.default_rng(3)
        for trial in range(10):
            P, T = int(rng.integers(50, 400)), int(rng.integers(1, 5))
            tier = np.where(rng.random(P) < 0.3, TIER_FAST, TIER_SLOW)
            owner = rng.integers(0, T, P)
            counts = rng.integers(0, 25, P)
            quota_p = rng.integers(0, 30, T)
            quota_d = rng.integers(0, 30, T)
            key = jnp.asarray(counts, jnp.int32)
            ownr = jnp.asarray(owner, jnp.int32)
            slow_cand = jnp.asarray(tier == TIER_SLOW)
            fast_cand = jnp.asarray(tier == TIER_FAST)
            C = 64
            from repro.core import bins

            hist_slow = bins.count_histogram(key, ownr, slow_cand, C, T)
            hist_fast = bins.count_histogram(key, ownr, fast_cand, C, T)
            pm, dm = policy._select_victims(
                key, ownr, slow_cand, fast_cand,
                jnp.cumsum(hist_slow, axis=1), jnp.cumsum(hist_fast, axis=1),
                jnp.asarray(quota_p, jnp.int32), jnp.asarray(quota_d, jnp.int32),
            )
            pm, dm = np.asarray(pm), np.asarray(dm)
            for t in range(T):
                s_ids = np.flatnonzero((owner == t) & (tier == TIER_SLOW))
                order = s_ids[np.lexsort((s_ids, -counts[s_ids]))]
                expect = set(order[: quota_p[t]].tolist())
                assert set(np.flatnonzero(pm & (owner == t)).tolist()) == expect
                f_ids = np.flatnonzero((owner == t) & (tier == TIER_FAST))
                order = f_ids[np.lexsort((f_ids, counts[f_ids]))]
                expect = set(order[: quota_d[t]].tolist())
                assert set(np.flatnonzero(dm & (owner == t)).tolist()) == expect


class TestMultiEpoch:
    def _state(self, P=256, T=4, seed=0):
        rng = np.random.default_rng(seed)
        pages = PageState.create(P)._replace(
            owner=jnp.asarray(rng.integers(0, T, P), jnp.int32),
            tier=jnp.asarray(
                np.where(np.arange(P) < P // 4, TIER_FAST, TIER_SLOW), jnp.int8
            ),
        )
        tenants = TenantState.create(T)._replace(
            active=jnp.ones((T,), bool),
            t_miss=jnp.asarray(rng.uniform(0.05, 1.0, T), jnp.float32),
            arrival=jnp.arange(T, dtype=jnp.int32),
        )
        params = PolicyParams(
            fast_capacity=jnp.int32(P // 4),
            migration_budget=jnp.int32(16),
            sample_period=jnp.int32(1),
        )
        return PolicyState(
            pages=pages, tenants=tenants,
            pending=jnp.zeros((P,), jnp.uint32), rng=jax.random.PRNGKey(1),
        ), params, rng

    def test_scan_equals_k_single_steps_exact(self):
        """multi_epoch(k) is bit-identical to k epoch_step calls (exact
        sampling: no stochastic draws differ between the two paths)."""
        state0, params, rng = self._state()
        counts = jnp.asarray(rng.integers(0, 20, 256), jnp.uint32)
        k = 6
        # both paths donate their state: each starts from its own copy
        st = jax.tree.map(jnp.copy, state0)
        seq_stats = []
        for _ in range(k):
            st = st._replace(pending=st.pending + counts)
            st, plan, stats = policy.epoch_step(
                st, params, max_tenants=4, plan_size=16, exact_sampling=True
            )
            seq_stats.append(stats)
        stm, plans, stats_k, flagged = policy.multi_epoch(
            state0, params, counts, k=k, max_tenants=4, plan_size=16, exact_sampling=True
        )
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(stm)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for i in range(k):
            for a, b in zip(jax.tree.leaves(seq_stats[i]), jax.tree.leaves(stats_k)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[i])

    def test_stacked_outputs_shapes(self):
        state0, params, _ = self._state()
        _, plans, stats, flagged = policy.multi_epoch(
            state0, params, None, k=5, max_tenants=4, plan_size=16, exact_sampling=True
        )
        assert plans.promote.shape == (5, 16)
        assert stats.fmmr_ewma.shape == (5, 4)
        assert flagged.shape == (5, 4)

    def test_collect_plans_false_keeps_stats_exact(self):
        state0, params, rng = self._state(seed=5)
        counts = jnp.asarray(rng.integers(0, 20, 256), jnp.uint32)
        _, plans_a, stats_a, _ = policy.multi_epoch(
            jax.tree.map(jnp.copy, state0), params, counts, k=4, max_tenants=4, plan_size=16,
            exact_sampling=True, collect_plans=True,
        )
        _, plans_b, stats_b, _ = policy.multi_epoch(
            state0, params, counts, k=4, max_tenants=4, plan_size=16,
            exact_sampling=True, collect_plans=False,
        )
        assert plans_b is None
        for a, b in zip(jax.tree.leaves(stats_a), jax.tree.leaves(stats_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # plan lists agree with the promoted/demoted telemetry
        assert int((np.asarray(plans_a.promote) >= 0).sum(axis=1).sum()) == int(
            np.asarray(stats_a.promoted).sum()
        )


class TestManagerEngine:
    def _mgr(self, **kw):
        defaults = dict(
            num_pages=256, fast_capacity=64, migration_budget=32,
            max_tenants=8, sample_period=1, exact_sampling=True,
        )
        defaults.update(kw)
        return CentralManager(**defaults)

    def test_run_epochs_matches_single_stepping(self):
        counts = np.zeros(256, np.int64)
        counts[:128] = np.arange(128) % 11

        m1 = self._mgr()
        h1 = m1.register(0.2)
        m1.allocate(h1, 128)
        for _ in range(8):
            m1.record_access(counts)
            m1.run_epoch()

        m2 = self._mgr()
        h2 = m2.register(0.2)
        m2.allocate(h2, 128)
        res = m2.run_epochs(8, counts=counts)
        assert len(res) == 8
        np.testing.assert_array_equal(
            np.asarray(m1.pages.tier), np.asarray(m2.pages.tier)
        )
        np.testing.assert_array_equal(
            np.asarray(m1.pages.count), np.asarray(m2.pages.count)
        )
        assert m1.fmmr_of(h1) == pytest.approx(m2.fmmr_of(h2))
        assert m1.epoch_index == m2.epoch_index == 8

    def test_free_resets_cooling_stamp(self):
        """A reallocated page must not inherit the previous owner's cooling
        stamp (stale-metadata leak)."""
        m = self._mgr()
        h = m.register(1.0)
        pages = m.allocate(h, 32)
        # drive counts over the cooling threshold a few times
        counts = np.zeros(256, np.int64)
        counts[pages] = 100
        for _ in range(4):
            m.record_access(counts)
            m.run_epoch()
        assert int(m.tenants.cool_epoch[int(h)]) > 0
        m.free(h, pages)
        assert (np.asarray(m.pages.last_cool)[pages] == 0).all()
        assert (np.asarray(m.pages.count)[pages] == 0).all()
        m.unregister(h)
        # a new tenant reusing the slot (cool_epoch restarts at 0) sees
        # counts at face value, not spuriously halved or inflated
        h2 = m.register(1.0)
        assert int(h2) == int(h)
        p2 = m.allocate(h2, 32)
        m.record_access(counts)
        m.run_epoch()
        from repro.core import bins

        eff = np.asarray(bins.effective_count(m.pages, m.tenants))
        assert eff[p2].max() > 0

    def test_record_access_folds_on_device(self):
        m = self._mgr()
        h = m.register(0.5)
        m.allocate(h, 64)
        counts = np.zeros(256, np.int64)
        counts[:64] = 3
        m.record_access(counts)
        m.record_access(counts)
        assert int(np.asarray(m._state.pending)[:64].sum()) == 2 * 3 * 64

    def test_telemetry_snapshot_caching(self):
        m = self._mgr()
        h = m.register(0.5)
        pages = m.allocate(h, 100)
        snap1 = m.tiers()
        snap2 = m.tiers()
        assert snap1 is snap2  # cached between state changes
        m.record_access(np.ones(256, np.int64))
        m.run_epoch()
        assert m.tiers() is not snap1
        assert m.fast_pages_of(h) == 64
