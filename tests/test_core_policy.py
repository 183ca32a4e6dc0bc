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

    @pytest.mark.parametrize("kind", ["bits", "integers", "ratios"])
    def test_div_rn_is_the_ieee_quotient(self, kind):
        """``div_rn`` returns numpy's correctly rounded float32 quotient bit
        for bit wherever operands and result are normal numbers: random bit
        patterns, integer page counts, and ratios of FMMRs."""
        rng = np.random.default_rng(len(kind))
        n = 200_000
        if kind == "bits":
            x, y = (rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32).view(np.float32)
                    for _ in range(2))
        elif kind == "integers":
            x = rng.integers(0, 2**24, n).astype(np.float32)
            y = rng.integers(1, 2**24, n).astype(np.float32)
        else:
            x = rng.random(n).astype(np.float32)
            y = (rng.random(n) + 1e-9).astype(np.float32)
        with np.errstate(all="ignore"):
            want = np.divide(x, y)
        normal = lambda v: np.isfinite(v) & (np.abs(v) >= np.finfo(np.float32).tiny)  # noqa: E731
        keep = normal(x) & normal(y) & normal(want)
        got = np.asarray(jax.jit(fmmr.div_rn)(x, y))
        np.testing.assert_array_equal(got[keep].view(np.uint32), want[keep].view(np.uint32))
        assert keep.sum() > n // 3

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


# ------------------------------------------- gather-free ranks and holdings
# P above the tiled-cumsum threshold and not a multiple of the 1024-page block
GF_P, GF_T = 131072 + 333, 16


def _fresh_epoch_step():
    """A jit of the tick that traces anew, so a ``_select_victims`` patched
    in by the test is the one it runs."""
    def impl(state, params, *, max_tenants, plan_size, exact_sampling, count_clamp,
             compile_sentinel=True):
        return policy._epoch_step_impl(
            state, params, max_tenants=max_tenants, plan_size=plan_size,
            exact_sampling=exact_sampling, count_clamp=count_clamp,
            compile_sentinel=compile_sentinel,
        )

    return jax.jit(impl, static_argnames=("max_tenants", "plan_size", "exact_sampling",
                                          "count_clamp", "compile_sentinel"),
                   donate_argnums=(0,))


def _gf_run(case, path, monkeypatch, seen=None):
    """Six queue-mode epochs of ``case`` on one path (``"segments"``: the
    manager's state with owner segments; ``"onehot"``: without); tenant B departs
    after epoch 2 and a new tenant arrives after epoch 3. Returns every
    epoch's (state less segments, plan, stats) leaves as numpy arrays, and
    appends each epoch's selection inputs and masks, and the queue's depth
    going into it (its pages are excluded from selection), to ``seen``."""
    from repro.core.manager import CentralManager

    step = _fresh_epoch_step()
    with monkeypatch.context() as mp:
        mp.setattr(policy, "_jitted_epoch_step", lambda: step)
        if seen is not None:
            select = policy._select_victims

            def recorded(key, owner, slow_cand, fast_cand, cum_slow, cum_fast, pq, dq):
                pm, dm = select(key, owner, slow_cand, fast_cand, cum_slow, cum_fast, pq, dq)
                jax.debug.callback(lambda *a: seen.append([np.asarray(v) for v in a]),
                                   key, owner, slow_cand, fast_cand, pq, dq, pm, dm)
                return pm, dm

            mp.setattr(policy, "_select_victims", recorded)
        m = CentralManager(num_pages=GF_P, fast_capacity=16384, max_tenants=GF_T,
                           migration_budget=case["budget"], queue_size=512,
                           migration_bandwidth=96, migration_latency=1, sample_period=1,
                           exact_sampling=True, seed=7)
        hs = [m.register(t) for t in (0.1, 0.5, 1.0)]
        for _ in range(3):  # tenants interleaved in runs of 7,000 pages
            for h in hs:
                m.allocate(h, 7000)
        if case["idle_slot"]:
            m.register(0.3)  # slot 3: active, and never holds a page

        def onehot():
            if path == "onehot":
                m._segs_owner = None
                m._state = m._state._replace(segs=None)

        onehot()
        rng = np.random.default_rng(11)
        out = []
        for e in range(6):
            owner = np.asarray(m.owners())
            counts = np.where(owner >= 0, case["base"], 0)
            # a strided hot set: buckets whose members spread over blocks
            hot = (owner >= 0) & (np.arange(GF_P) % case["hot_every"] == 0)
            counts[hot] += rng.integers(case["hot_lo"], case["hot_hi"] + 1, int(hot.sum()))
            m.record_access(counts)
            depth = m.queue_depth()
            r = m.run_epoch()
            if seen is not None:
                seen[-1].append(depth)
            st = m._state._replace(segs=None)
            out.append([np.asarray(v) for v in jax.tree.leaves((st, r.plan, r.stats))])
            if e == 2:
                m.unregister(hs[1])
                onehot()
            if e == 3:
                m.allocate(m.register(0.2), 9000)
                onehot()
        return out


def _gf_ranked(key, owner, slow_cand, fast_cand, t):
    """Tenant t's candidates in selection order: slow hottest first, fast
    coldest first, ties by page id (a lexsort reference)."""
    s = np.flatnonzero(slow_cand & (owner == t))
    f = np.flatnonzero(fast_cand & (owner == t))
    return s[np.lexsort((s, -key[s]))], f[np.lexsort((f, key[f]))]


@pytest.mark.parametrize("case", [
    dict(id="straddle_blocks", budget=2048, base=1, hot_every=3, hot_lo=4, hot_hi=4,
         idle_slot=False),
    # a hot set smaller than the rebalance share: promoted whole
    dict(id="residual_fills_bucket", budget=2048, base=0, hot_every=300, hot_lo=9, hot_hi=9,
         idle_slot=False),
    dict(id="zero_quotas", budget=0, base=1, hot_every=3, hot_lo=1, hot_hi=9, idle_slot=False),
    dict(id="empty_slot", budget=1024, base=0, hot_every=3, hot_lo=1, hot_hi=30,
         idle_slot=True),
], ids=lambda c: c["id"])
def test_gather_free_matches_segments_and_onehot(case, monkeypatch):
    """At the benchmark's branch (P > 65,536, queue mode with in-flight
    exclusions, a departure and an arrival mid-run), the gather-free ranks
    and holdings give the same state, plan and stats, leaf for leaf, every
    epoch, whether the state carries owner segments (scatter-add bins) or
    not (one-hot bins); the selection equals a lexsort reference, and the
    case's situation occurs in it."""
    seen = []
    got = _gf_run(case, "segments", monkeypatch, seen)
    want = _gf_run(case, "onehot", monkeypatch)
    for e, (g, w) in enumerate(zip(got, want)):
        assert len(g) == len(w)
        for u, v in zip(g, w):
            np.testing.assert_array_equal(u, v, err_msg=f"epoch {e}")
    assert len(seen) == 6
    happened = False
    assert case["budget"] == 0 or max(depth for *_, depth in seen) > 0  # in-flight exclusions
    for key, owner, slow_cand, fast_cand, pq, dq, pm, dm, _ in seen:
        for t in range(GF_T):
            for ranked, q, mask in zip(_gf_ranked(key, owner, slow_cand, fast_cand, t),
                                       (pq[t], dq[t]), (pm, dm)):
                q = min(max(int(q), 0), len(ranked))
                np.testing.assert_array_equal(np.flatnonzero(mask & (owner == t)),
                                              np.sort(ranked[:q]))
                if not 0 < q < len(ranked):
                    continue
                bucket = ranked[key[ranked] == key[ranked[q - 1]]]
                taken = np.isin(bucket, ranked[:q])
                if case["id"] == "straddle_blocks":
                    # the quota lands inside a bucket whose members span blocks
                    happened |= (not taken.all()) and len(np.unique(bucket // 1024)) > 1
                elif case["id"] == "residual_fills_bucket":
                    # the quota takes its last bucket to the bucket's end
                    happened |= bool(taken.all()) and (taken.size > 0)
        if case["id"] == "zero_quotas":
            happened = True
            assert (pq == 0).all() and (dq == 0).all()
        elif case["id"] == "empty_slot":
            # an active slot that holds no page, beside tenants that do
            happened |= not (owner == 3).any() and bool((pq + dq).sum() > 0)
    assert happened, case["id"]


def _p_by_p_gathers(hlo: str, P: int):
    """Gathers in compiled HLO text whose operand and output both hold P
    elements (a P-long source read through a P-long index)."""
    import re

    def elems(shape):
        return int(np.prod([int(d) for d in shape.split(",") if d])) if shape else 1

    shape_of = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo))
    found = []
    for name, out, operand in re.findall(
        r"%([\w.-]+) = \w+\[([\d,]*)\]\S* gather\(%([\w.-]+)", hlo
    ):
        if elems(out) == P and elems(shape_of.get(operand, "")) == P:
            found.append(name)
    return found


@pytest.mark.parametrize("T", [GF_T, 256])
def test_tick_reads_no_page_by_page_gather(T):
    """The compiled tick (queue mode, owner segments on, P > 65,536) reads
    no P-long source through a P-long index, at the benchmark's 16 tenant
    slots and at the 256 of the largest scale geometry: in-bucket ranks and
    holdings take the gather-free path at every T."""
    from repro.core.manager import CentralManager

    m = CentralManager(num_pages=GF_P, fast_capacity=16384, migration_budget=64,
                       max_tenants=T, queue_size=64, migration_bandwidth=32, sample_period=1)
    m.allocate(m.register(0.5), 1000)
    m._ensure_segs()
    hlo = policy._jitted_epoch_step().lower(
        m._state, m.params, max_tenants=T, plan_size=m.plan_size,
        exact_sampling=m.exact_sampling, count_clamp=policy.COUNT_CLAMP,
    ).compile().as_text()
    assert _p_by_p_gathers(hlo, GF_P) == []
