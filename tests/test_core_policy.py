"""Tests for the FMMR reallocation math and the full policy epoch (§3.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # clean checkout: deterministic fallback sweep
    from _hypothesis_fallback import given, settings, st

from repro.core import fmmr, policy
from repro.core.types import (
    TIER_FAST,
    TIER_SLOW,
    PageState,
    PolicyParams,
    TenantState,
)


def _tenants(t_miss, a_miss, fast=None):
    T = len(t_miss)
    ten = TenantState.create(T)
    return ten._replace(
        active=jnp.ones((T,), bool),
        t_miss=jnp.array(t_miss, jnp.float32),
        a_miss=jnp.array(a_miss, jnp.float32),
        arrival=jnp.arange(T, dtype=jnp.int32),
    )


class TestFMMR:
    def test_fmmr_now_zero_when_idle(self):
        out = fmmr.fmmr_now(jnp.array([0.0]), jnp.array([0.0]))
        assert float(out[0]) == 0.0  # idle tenants decay to zero (§3.1)

    def test_fmmr_now_ratio(self):
        out = fmmr.fmmr_now(jnp.array([90.0]), jnp.array([10.0]))
        assert np.isclose(float(out[0]), 0.1)

    def test_ewma_lambda_half(self):
        out = fmmr.update_ewma(jnp.array([0.4]), jnp.array([0.2]), 0.5)
        assert np.isclose(float(out[0]), 0.3)


class TestRealloc:
    def test_needer_receives_donor_gives(self):
        ten = _tenants([0.1, 1.0], [0.5, 0.2])  # t0 needs, t1 below target
        ra = fmmr.reallocate(
            ten, jnp.array([10, 100]), jnp.int32(0), jnp.int32(50)
        )
        assert int(ra.give[0]) > 0
        assert int(ra.take[1]) > 0
        assert int(ra.give[1]) == 0 and int(ra.take[0]) == 0

    def test_take_capped_at_fast_holdings(self):
        ten = _tenants([0.1, 1.0], [0.5, 0.2])
        ra = fmmr.reallocate(ten, jnp.array([10, 3]), jnp.int32(0), jnp.int32(50))
        assert int(ra.take[1]) <= 3

    def test_zero_amiss_single_donor_per_epoch(self):
        # two idle donors (a_miss=0): only the earliest-arrival one donates
        ten = _tenants([0.1, 1.0, 1.0], [0.9, 0.0, 0.0])
        ra = fmmr.reallocate(
            ten, jnp.array([5, 40, 40]), jnp.int32(0), jnp.int32(20)
        )
        donors = [i for i in range(3) if int(ra.take[i]) > 0]
        assert donors == [1]

    def test_gives_bounded_by_available(self):
        ten = _tenants([0.1], [1.0])
        ra = fmmr.reallocate(ten, jnp.array([0]), jnp.int32(7), jnp.int32(100))
        assert int(ra.give[0]) <= 7

    def test_fcfs_serves_earliest_first(self):
        ten = _tenants([0.1, 0.1], [1.0, 1.0])
        ra = fmmr.reallocate(ten, jnp.array([0, 0]), jnp.int32(10), jnp.int32(100))
        # both want 50; only 10 available; FCFS gives all to tenant 0
        assert int(ra.give[0]) == 10 and int(ra.give[1]) == 0
        assert bool(ra.flagged[1])

    def test_fair_mode_splits_proportionally(self):
        ten = _tenants([0.1, 0.1], [1.0, 1.0])
        ra = fmmr.reallocate(
            ten, jnp.array([0, 0]), jnp.int32(10), jnp.int32(100), fair_mode=True
        )
        assert int(ra.give[0]) == 5 and int(ra.give[1]) == 5

    def test_proportionality_to_distance(self):
        """Farther-from-target needers get more bandwidth (§3.4)."""
        ten = _tenants([0.1, 0.1, 1.0], [1.0, 0.2, 0.1])
        ra = fmmr.reallocate(
            ten, jnp.array([0, 0, 200]), jnp.int32(200), jnp.int32(100)
        )
        assert int(ra.give[0]) > int(ra.give[1]) > 0

    @settings(max_examples=60, deadline=None)
    @given(
        t=st.lists(st.floats(0.05, 1.0), min_size=2, max_size=8),
        a=st.lists(st.floats(0.0, 1.0), min_size=2, max_size=8),
        fast=st.lists(st.integers(0, 100), min_size=2, max_size=8),
        free=st.integers(0, 50),
        budget=st.integers(1, 64),
    )
    def test_property_invariants(self, t, a, fast, free, budget):
        n = min(len(t), len(a), len(fast))
        t, a, fast = t[:n], a[:n], fast[:n]
        ten = _tenants(t, a)
        ra = fmmr.reallocate(
            ten, jnp.array(fast, jnp.int32), jnp.int32(free), jnp.int32(budget)
        )
        give, take = np.asarray(ra.give), np.asarray(ra.take)
        assert np.all(give >= 0) and np.all(take >= 0)
        # takes never exceed holdings
        assert np.all(take <= np.array(fast))
        # gives never exceed what exists (free + takes)
        assert give.sum() <= free + take.sum()
        # nobody both gives and takes
        assert not np.any((give > 0) & (take > 0))
        # total gives bounded by the migration budget
        assert give.sum() <= budget


class TestPolicyEpoch:
    def _setup(self, P=64, T=4, F=16, R=16):
        pages = PageState.create(P)
        tenants = TenantState.create(T)
        params = PolicyParams(
            fast_capacity=jnp.int32(F),
            migration_budget=jnp.int32(R),
            sample_period=jnp.int32(1),
        )
        return pages, tenants, params

    def test_rebalance_promotes_hottest_demotes_coldest(self):
        P, T, F, R = 16, 1, 4, 8
        pages, tenants, params = self._setup(P, T, F, R)
        tenants = tenants._replace(
            active=tenants.active.at[0].set(True),
            t_miss=tenants.t_miss.at[0].set(1.0),
            arrival=tenants.arrival.at[0].set(0),
        )
        # tenant 0 owns all 16 pages; pages 0-3 fast (cold), 4-15 slow
        owner = jnp.zeros((P,), jnp.int32)
        tier = jnp.array([TIER_FAST] * 4 + [TIER_SLOW] * 12, jnp.int8)
        pages = pages._replace(owner=owner, tier=tier)
        # heat: slow pages 4,5 are hottest; fast pages are cold
        sampled = np.zeros(P, np.int64)
        sampled[4] = 20
        sampled[5] = 18
        sampled[0] = 1  # fast, slightly warm
        pages2, tenants2, plan, stats = policy.policy_epoch(
            pages,
            tenants,
            jnp.asarray(sampled, jnp.uint32),
            params,
            max_tenants=T,
            plan_size=R,
        )
        pages3 = policy.apply_plan(pages2, plan)
        tier3 = np.asarray(pages3.tier)
        assert tier3[4] == TIER_FAST and tier3[5] == TIER_FAST
        # cold fast pages displaced
        assert (tier3[:4] == TIER_SLOW).sum() >= 2

    def test_fast_capacity_never_exceeded(self):
        P, T, F, R = 64, 3, 16, 32
        pages, tenants, params = self._setup(P, T, F, R)
        rng = np.random.default_rng(0)
        owner = jnp.asarray(rng.integers(0, T, P), jnp.int32)
        tier = jnp.asarray(
            np.where(np.arange(P) < F, TIER_FAST, TIER_SLOW), jnp.int8
        )
        pages = pages._replace(owner=owner, tier=tier)
        tenants = tenants._replace(
            active=jnp.ones((T,), bool),
            t_miss=jnp.array([0.1, 0.5, 1.0], jnp.float32),
            arrival=jnp.arange(T, dtype=jnp.int32),
        )
        for step in range(10):
            sampled = jnp.asarray(rng.integers(0, 10, P), jnp.uint32)
            pages, tenants, plan, stats = policy.policy_epoch(
                pages, tenants, sampled, params, max_tenants=T, plan_size=R
            )
            pages = policy.apply_plan(pages, plan)
            n_fast = int((np.asarray(pages.tier) == TIER_FAST).sum())
            assert n_fast <= F, f"step {step}: fast tier over capacity {n_fast} > {F}"
            moved = int(plan.num_promote) + int(plan.num_demote)
            assert moved <= R, f"migration rate cap violated: {moved} > {R}"

    def test_idle_tenant_decays_and_donates(self):
        """Memory-inactive tenants converge a_miss -> 0 and give up fast mem."""
        P, T, F, R = 32, 2, 8, 8
        pages, tenants, params = self._setup(P, T, F, R)
        owner = jnp.asarray([0] * 16 + [1] * 16, jnp.int32)
        tier = jnp.asarray([TIER_FAST] * 8 + [TIER_SLOW] * 24, jnp.int8)
        pages = pages._replace(owner=owner, tier=tier)
        tenants = tenants._replace(
            active=jnp.ones((T,), bool),
            t_miss=jnp.array([1.0, 0.1], jnp.float32),
            a_miss=jnp.array([0.5, 0.0], jnp.float32),
            arrival=jnp.arange(T, dtype=jnp.int32),
        )
        rng = np.random.default_rng(1)
        for _ in range(12):
            sampled = np.zeros(P, np.int64)
            sampled[16:] = rng.integers(1, 10, 16)  # only tenant 1 active
            pages, tenants, plan, _ = policy.policy_epoch(
                pages, tenants, jnp.asarray(sampled, jnp.uint32), params,
                max_tenants=T, plan_size=int(params.migration_budget),
            )
            pages = policy.apply_plan(pages, plan)
        t0_fast = int(
            ((np.asarray(pages.owner) == 0) & (np.asarray(pages.tier) == TIER_FAST)).sum()
        )
        t1_fast = int(
            ((np.asarray(pages.owner) == 1) & (np.asarray(pages.tier) == TIER_FAST)).sum()
        )
        assert float(tenants.a_miss[0]) < 1e-3
        assert t1_fast > t0_fast  # active tenant captured the fast tier


@pytest.mark.parametrize("entry", ["epoch_step", "multi_epoch"])
def test_tick_stages_are_named_scopes(entry):
    """Each stage of the fused tick is traced under its ``tick.<stage>``
    named scope, on the single-step and the scanned path alike: metadata the
    compiled ops carry into the device trace, read by the benchmark's
    per-stage metrics."""
    import re

    from repro.core.manager import CentralManager

    mgr = CentralManager(num_pages=256, fast_capacity=32, migration_budget=16, max_tenants=4,
                         queue_size=32, migration_bandwidth=16, sample_period=4, sentinel=True)
    mgr.allocate(mgr.register(0.3), 100)
    mgr._ensure_segs()
    kw = dict(max_tenants=4, plan_size=16, exact_sampling=False, count_clamp=policy.COUNT_CLAMP)
    if entry == "epoch_step":
        lowered = policy._jitted_epoch_step().lower(mgr._state, mgr.params, **kw)
    else:
        lowered = policy._jitted_multi_epoch().lower(mgr._state, mgr.params, None, k=2,
                                                     collect_plans=False, **kw)
    scopes = set(re.findall(r"tick\.[a-z]+", lowered.as_text(debug_info=True)))
    assert scopes == {"tick.sample", "tick.bins", "tick.fmmr", "tick.select", "tick.queue",
                      "tick.sentinel"}
