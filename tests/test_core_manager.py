"""CentralManager end-to-end: allocation semantics, dynamic QoS, invariants."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # clean checkout: deterministic fallback sweep
    from _hypothesis_fallback import given, settings, st

from repro.core import CentralManager, TIER_FAST, TIER_NONE, TIER_SLOW


def _mgr(**kw):
    defaults = dict(
        num_pages=256,
        fast_capacity=64,
        migration_budget=32,
        max_tenants=8,
        sample_period=1,
        exact_sampling=True,
    )
    defaults.update(kw)
    return CentralManager(**defaults)


class TestAllocation:
    def test_fast_first_then_slow(self):
        m = _mgr()
        h = m.register(t_miss=0.5)
        pages = m.allocate(h, 100)
        tiers = m.tier_of(pages)
        assert (tiers == TIER_FAST).sum() == 64
        assert (tiers == TIER_SLOW).sum() == 36

    def test_oom_raises(self):
        m = _mgr()
        h = m.register(t_miss=1.0)
        with pytest.raises(MemoryError):
            m.allocate(h, 1000)

    def test_free_returns_pages(self):
        m = _mgr()
        h = m.register(t_miss=1.0)
        pages = m.allocate(h, 50)
        m.free(h, pages)
        assert (m.tier_of(pages) == TIER_NONE).all()
        h2 = m.register(t_miss=1.0)
        assert len(m.allocate(h2, 256)) == 256

    def test_cannot_free_other_tenants_pages(self):
        m = _mgr()
        h1, h2 = m.register(0.5), m.register(0.5)
        p1 = m.allocate(h1, 10)
        with pytest.raises(PermissionError):
            m.free(h2, p1)

    def test_t_miss_validation(self):
        m = _mgr()
        with pytest.raises(AssertionError):
            m.register(t_miss=0.0)  # FMMR 0 => disable tiering, not a target


class TestDynamicQoS:
    def _drive(self, m, tenants_pages, probs, epochs=20):
        """tenants_pages: {handle: page_ids}; probs: {handle: per-page probs}"""
        res = None
        for _ in range(epochs):
            counts = np.zeros(m.num_pages, np.int64)
            for h, ids in tenants_pages.items():
                counts[ids] += (probs[h] * 10_000).astype(np.int64)
            m.record_access(counts)
            res = m.run_epoch()
        return res

    def test_single_tenant_hot_set_lands_in_fast(self):
        m = _mgr(num_pages=128, fast_capacity=32, migration_budget=16)
        h = m.register(t_miss=0.1)
        pages = m.allocate(h, 128)
        probs = np.full(128, 0.1 / 96)
        probs[:32] = 0.9 / 32  # hot set = exactly fast capacity
        self._drive(m, {h: pages}, {h: probs}, epochs=30)
        hot_tiers = m.tier_of(pages[:32])
        assert (hot_tiers == TIER_FAST).mean() > 0.9
        assert m.fmmr_of(h) <= 0.15

    def test_qos_reallocation_between_tenants(self):
        """LS tenant (t=0.1) takes fast memory from BE tenant (t=1.0)."""
        m = _mgr(num_pages=256, fast_capacity=64, migration_budget=32)
        be = m.register(t_miss=1.0)
        be_pages = m.allocate(be, 128)  # grabs all fast first
        ls = m.register(t_miss=0.1)
        ls_pages = m.allocate(ls, 128)  # all slow now
        probs = np.full(128, 1 / 128)
        ls_probs = np.full(128, 0.05 / 80)
        ls_probs[:48] = 0.95 / 48  # LS hot set of 48 pages
        self._drive(m, {be: be_pages, ls: ls_pages}, {be: probs, ls: ls_probs}, 40)
        assert m.fmmr_of(ls) <= 0.12, f"LS tenant FMMR {m.fmmr_of(ls)} > target"
        assert m.fast_pages_of(ls) >= 40

    def test_exit_releases_memory_to_needers(self):
        m = _mgr(num_pages=256, fast_capacity=64, migration_budget=32)
        a = m.register(t_miss=0.5)
        pa = m.allocate(a, 64)
        b = m.register(t_miss=0.1)
        pb = m.allocate(b, 64)
        probs = np.full(64, 1 / 64)
        self._drive(m, {a: pa, b: pb}, {a: probs, b: probs}, 10)
        m.unregister(a)
        self._drive(m, {b: pb}, {b: probs}, 20)
        assert m.fast_pages_of(b) >= 56  # reclaimed the freed fast tier

    def test_dynamic_target_change(self):
        m = _mgr(num_pages=128, fast_capacity=32, migration_budget=16)
        h = m.register(t_miss=1.0)
        pages = m.allocate(h, 128)
        probs = np.full(128, 1 / 128)
        self._drive(m, {h: pages}, {h: probs}, 10)
        m.set_target(h, 0.1)
        # single tenant: fast capacity 32/128 pages uniform -> best FMMR .75;
        # the policy should still pull everything it can into fast
        self._drive(m, {h: pages}, {h: probs}, 30)
        assert m.fast_pages_of(h) == 32


class TestInvariants:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), n_tenants=st.integers(1, 4))
    def test_property_capacity_and_budget(self, seed, n_tenants):
        rng = np.random.default_rng(seed)
        m = _mgr(num_pages=128, fast_capacity=32, migration_budget=16)
        handles, pages = [], {}
        for i in range(n_tenants):
            h = m.register(t_miss=float(rng.uniform(0.05, 1.0)))
            handles.append(h)
            pages[h] = m.allocate(h, int(rng.integers(8, 32)))
        for _ in range(8):
            counts = np.zeros(m.num_pages, np.int64)
            for h in handles:
                counts[pages[h]] += rng.integers(0, 50, len(pages[h]))
            m.record_access(counts)
            res = m.run_epoch()
            tier = np.asarray(m.pages.tier)
            assert (tier == TIER_FAST).sum() <= 32
            moved = int(res.plan.num_promote) + int(res.plan.num_demote)
            assert moved <= 16
            # owners never change due to migration
            for h in handles:
                assert (np.asarray(m.pages.owner)[pages[h]] == int(h)).all()


class TestDataPlaneDispatch:
    def test_page_pool_reaches_kernels_through_ops(self, monkeypatch):
        """The pool's writes and migrations go through ``kernels.ops``, the
        one place that picks compiled (TPU) or interpreted (CPU) kernels."""
        from repro.kernels import ops

        calls = []
        for name in ("page_copy", "page_move"):
            real = getattr(ops, name)
            monkeypatch.setattr(
                ops, name,
                lambda *a, _real=real, _name=name: calls.append(_name) or _real(*a),
            )
        m = _mgr(queue_size=32, data_plane_elems=16)
        fast_h = m.register(t_miss=1.0)
        slow_h = m.register(t_miss=0.1)
        m.allocate(fast_h, 64)
        hot = m.allocate(slow_h, 64)
        rows = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
        m.pool.write_pages(hot, rows)
        counts = np.zeros(256, np.int64)
        counts[hot] = 50
        m.run_epochs(4, counts=counts)
        assert "page_copy" in calls and "page_move" in calls
        assert m.pool.moved_pages > 0
        m.pool.check(m.tiers())
        got = np.stack([m.pool.read_page(p) for p in hot])
        assert (got == rows).all()
