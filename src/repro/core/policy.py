"""The MaxMem per-epoch policy step (paper §3.1 + §3.2), fully jittable.

Pipeline per epoch (cf. Figure 1 of the paper):
  1. fold sampled accesses into per-page counters (+ lazy cooling)   [bins]
  2. compute instantaneous FMMR per tenant, update EWMA (lambda=.5)  [fmmr]
  3. reallocate fast memory proportionally to distance from target   [fmmr]
     using half the migration budget
  4. intra-tenant rebalance with the other half: promote hottest-slow
     / demote coldest-fast pairs where it strictly improves FMMR
  5. emit a bounded MigrationPlan (page id lists) + telemetry

Victim selection is O(P) and *exact*: instead of sorting, each (tenant, tier)
candidate group is histogrammed by clamped effective count, and prefix sums
over the count axis yield a per-tenant cutoff count plus a residual for the
bucket the quota lands in — the paper's per-bin lists restated as cumulative
offsets at count granularity (DESIGN.md §2). Ties within a count bucket break
by lowest page id, matching the stable lexsort the seed used, and there is no
candidate window: selection is exact for any number of candidates per tenant.

Entry points:
  * ``policy_epoch``  — one epoch on explicit (pages, tenants, sampled).
  * ``epoch_step``    — fused sample -> policy -> apply on a ``PolicyState``
                        (single dispatch; buffers donated).
  * ``multi_epoch``   — ``lax.scan`` of the epoch across k epochs in one
                        dispatch, with stacked per-epoch telemetry.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bins, fmmr
from repro.core.faults import (
    SENTINEL_NAN,
    SENTINEL_OCCUPANCY,
    SENTINEL_ORPHAN,
    SENTINEL_OWNERSHIP,
    SENTINEL_QUEUE,
)
from repro.core.sampler import sample_accesses
from repro.core.tiling import CUMSUM_BLOCK, tiled_cumsum
from repro.core.types import (
    DIR_DEMOTE,
    DIR_NONE,
    DIR_PROMOTE,
    TIER_FAST,
    TIER_NONE,
    TIER_SLOW,
    EpochStats,
    MigrationPlan,
    MigrationQueue,
    OwnerSegments,
    PageState,
    PolicyParams,
    PolicyState,
    QueueStats,
    TenantState,
)

# Effective counts at or above this value share one histogram bucket (their
# relative order becomes a tie). Cooling fires at most once per epoch
# (paper §3.2), so steady-state effective counts approach 2x the per-epoch
# sampled adds — ~64 at paper-scale sampling, but THOUSANDS under
# simulator-scale access streams, where a tighter clamp would saturate hot
# and cold candidates into one bucket and strictly-improving rebalance
# pairs would vanish. 4096 keeps count-granular ranks through that regime;
# the [T, C] tables it sizes are consulted by per-tenant binary searches
# (not full-width reductions), so the width costs two cumsums, not a
# dozen O(T*C) passes.
COUNT_CLAMP = 4096


def _per_tenant_pages(
    pages: PageState,
    max_tenants: int,
    owner_onehot: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(fast_pages[T], slow_pages[T]) holdings: a [T, P] one-hot reduction,
    fused into its [T] outputs when no ``owner_onehot`` is given (no
    P-element scatter-add, which XLA:CPU executes element-serially, and no
    gather through the owner-sorted permutation, which costs about 7 ns an
    element on the chip)."""
    if owner_onehot is None:
        T = max_tenants
        owner_onehot = pages.owner[None, :] == jnp.arange(T, dtype=jnp.int32)[:, None]
    fast = (owner_onehot & (pages.tier == TIER_FAST)[None, :]).sum(axis=1)
    slow = (owner_onehot & (pages.tier == TIER_SLOW)[None, :]).sum(axis=1)
    return fast.astype(jnp.int32), slow.astype(jnp.int32)


def _select_victims(
    key,  # i32[P] clamped effective counts
    owner,  # i32[P] owner clamped to >= 0
    slow_cand,  # bool[P] promotion candidates
    fast_cand,  # bool[P] demotion candidates
    cum_slow,  # i32[T,C] inclusive prefix sums of the candidate histograms
    cum_fast,
    pq,  # i32[T] promote quota
    dq,  # i32[T] demote quota
):
    """(promote_mask, demote_mask) bool[P]: per tenant, exactly the ``pq[t]``
    HOTTEST slow candidates and ``dq[t]`` COLDEST fast candidates.

    Counting-rank selection from the [T, C] candidate histograms: buckets
    strictly beyond a per-tenant cutoff count are taken whole; the single
    bucket each quota lands in is filled in page-id order (stable, matching
    the seed's lexsort tie-break): up to the page id of its residual-th
    member (``_bucket_cutoffs``).
    """
    T, C = cum_slow.shape
    P = key.shape[0]
    srch = jax.vmap(partial(jnp.searchsorted, side="left"))
    srch_r = jax.vmap(partial(jnp.searchsorted, side="right"))
    idx_t = jnp.arange(T)

    # hot side: smallest count whose whole bucket fits under the quota.
    # #candidates with count >= c is total - cum[c-1] (non-increasing), so
    # the cutoff is a per-tenant binary search on the cumulative table —
    # [T] log C work instead of materializing the [T, C] suffix-count
    # table and reducing over it (bit-identical: same integer predicate).
    total_slow = cum_slow[:, -1]
    v = total_slow - pq
    c_full = jnp.where(v <= 0, 0, 1 + srch(cum_slow, v))  # [T]; C when none fit
    cum_at = cum_slow[idx_t, jnp.maximum(c_full - 1, 0)]
    above = total_slow - jnp.where(c_full > 0, cum_at, 0)
    above = jnp.where(c_full < C, above, 0)  # candidates already taken whole
    r_p = pq - above  # residual from the straddling bucket c_full - 1

    # cold side: largest count whose whole bucket fits (cum_fast increasing)
    n_full = srch_r(cum_fast, dq)  # buckets taken whole: c < n_full
    below = cum_fast[idx_t, jnp.clip(n_full - 1, 0, C - 1)]
    below = jnp.where(n_full > 0, below, 0)
    r_d = dq - below  # residual from the straddling bucket n_full

    # The per-page tests below consume four per-tenant scalars (c_full,
    # n_full, r_p, r_d) — naively eight [T] -> [P] gathers through `owner`,
    # which dominate the whole selection pass on XLA:CPU. Pack each side's
    # (cutoff, residual) into ONE u32 table entry so each side costs a
    # single gather: cutoff in the high bits, residual (clamped at 0 —
    # the tests only consult positive residuals) in the low `rbits`.
    # r <= P < 2^rbits and cutoff <= C, so the pack is exact whenever
    # cbits + rbits <= 32; the unpacked comparands are bit-identical to
    # the unpacked path, which remains for (huge-P, huge-C) configurations.
    rbits = int(P).bit_length()
    cbits = int(C).bit_length()
    if cbits + rbits <= 32:
        def _pack(cut, res):
            return (cut.astype(jnp.uint32) << rbits) | jnp.maximum(res, 0).astype(jnp.uint32)

        sp = _pack(c_full, r_p)[owner]  # one gather for the slow side
        fp = _pack(n_full, r_d)[owner]  # one gather for the fast side
        cf_pg = (sp >> rbits).astype(jnp.int32)
        rp_pg = (sp & ((1 << rbits) - 1)).astype(jnp.int32)
        nf_pg = (fp >> rbits).astype(jnp.int32)
        rd_pg = (fp & ((1 << rbits) - 1)).astype(jnp.int32)
    else:
        cf_pg, rp_pg = c_full[owner], r_p[owner]
        nf_pg, rd_pg = n_full[owner], r_d[owner]
    member_p = slow_cand & (key == cf_pg - 1) & (rp_pg > 0)
    member_d = fast_cand & (key == nf_pg) & (rd_pg > 0)

    x_p, x_d = _bucket_cutoffs(member_p, member_d, owner, r_p, r_d, T)
    pid = jnp.arange(P, dtype=jnp.int32)
    in_p = member_p & (pid <= x_p[owner])
    in_d = member_d & (pid <= x_d[owner])

    promote = (slow_cand & (key >= cf_pg)) | in_p
    demote = (fast_cand & (key < nf_pg)) | in_d
    return promote, demote


def _bucket_cutoffs(member_p, member_d, owner, r_p, r_d, T: int):
    """(x_p, x_d) i32[T]: per tenant, the page id of its ``r``-th member in
    page-id order (P when it has fewer), so ``member & (page id <=
    x[owner])`` is exactly ``member & (in-bucket position <= r)``.

    No gather reads a P-long source through a P-long index (on the chip
    each such gather costs about 7 ns an element). Member counts per
    (tenant, block of ``CUMSUM_BLOCK`` pages) come from ONE fused
    compare-and-reduce with a [T, nb] output; their running sum over blocks
    locates the block holding each tenant's r-th member, and a [T, block]
    slice of that block finds the page inside it. Both member sets ride one
    u32 per page (promote low 16 bits, demote high 16: a block holds at
    most 1024 of either). Exact integer counts at any P; the cost grows
    with T * P.
    """
    P = owner.shape[0]
    B = CUMSUM_BLOCK
    nb = -(-P // B)
    packed = member_p.astype(jnp.uint32) | (member_d.astype(jnp.uint32) << 16)
    packed = jnp.pad(packed, (0, nb * B - P)).reshape(nb, B)
    own = jnp.pad(owner, (0, nb * B - P)).reshape(nb, B)
    t = jnp.arange(T, dtype=own.dtype)
    cnt = jnp.where(own[None] == t[:, None, None], packed[None], 0).sum(
        axis=2, dtype=jnp.uint32
    )  # [T, nb]

    def cutoff(r, shift):
        ccum = jnp.cumsum(((cnt >> shift) & 0xFFFF).astype(jnp.int32), axis=1)
        # the running sums are non-decreasing: counting the entries below r
        # is the left-side search for the block of the r-th member
        b = (ccum < r[:, None]).sum(axis=1)  # nb when the tenant has fewer
        bi = jnp.minimum(b, nb - 1)
        before = jnp.take_along_axis(ccum, jnp.maximum(b - 1, 0)[:, None], axis=1)[:, 0]
        need = r - jnp.where(b > 0, before, 0)
        row = (own[bi] == t[:, None]) & ((packed[bi] >> shift) & 1 == 1)  # [T, B]
        j = (jnp.cumsum(row, axis=1, dtype=jnp.int32) < need[:, None]).sum(axis=1)
        return jnp.where(b < nb, bi * B + j, P).astype(jnp.int32)

    return cutoff(r_p, 0), cutoff(r_d, 16)


def _pair_count(cum_slow, cum_fast, give, take, cap):
    """i32[T]: number of strictly-improving (hottest-slow, coldest-fast)
    rebalance pairs after skipping the reallocation victims.

    With hot counts descending and cold counts ascending the improving pairs
    form a prefix, and its length has a closed form over the count domain:
    pair m-1 improves iff some count c separates it, i.e.

        max_c min(#slow_hotter_than(c) - give, #fast_at_most(c) - take)

    f(c) = #slow_hotter_than(c) - give is non-increasing and g(c) =
    #fast_at_most(c) - take non-decreasing, so min(f, g) is unimodal with
    its maximum at the crossing: max = max(g(c*-1), f(c*)) where c* is the
    first c with g >= f. The crossing is a per-tenant binary search on the
    (non-decreasing) sum cum_fast + cum_slow — [T] log C work instead of
    building and max-reducing the [T, C] pairwise-minimum table, with the
    identical integer result.
    """
    T, C = cum_slow.shape
    idx_t = jnp.arange(T)
    total_slow = cum_slow[:, -1]
    # g(c) - f(c) = cum_fast[c] + cum_slow[c] - (total_slow + take - give)
    # (hotter(c) = #slow with count > c = total - cum_slow[c])
    h = cum_fast + cum_slow  # non-decreasing
    thr = total_slow + take - give
    c_star = jax.vmap(partial(jnp.searchsorted, side="left"))(h, thr)  # [T]
    # g(c*-1) (valid when c* > 0) and f(c*) (valid when c* < C)
    g_lo = cum_fast[idx_t, jnp.maximum(c_star - 1, 0)] - take
    f_hi = total_slow - cum_slow[idx_t, jnp.minimum(c_star, C - 1)] - give
    m = jnp.maximum(
        jnp.where(c_star > 0, g_lo, jnp.iinfo(jnp.int32).min),
        jnp.where(c_star < C, f_hi, jnp.iinfo(jnp.int32).min),
    )
    return jnp.clip(m, 0, cap).astype(jnp.int32)


def _epoch_core(
    pages: PageState,
    tenants: TenantState,
    sampled: jax.Array,  # u32[P] sampled accesses this epoch (PEBS analogue)
    params: PolicyParams,
    max_tenants: int,
    plan_size: int,
    count_clamp: int,
    collect_plan: bool,
    exclude: Optional[jax.Array] = None,  # bool[P] pages barred from selection
    segs: Optional[OwnerSegments] = None,  # owner-sorted permutation (§5)
):
    """One policy epoch; trace-time body shared by all jitted entry points.

    Returns (pages, tenants, promote_mask, demote_mask, plan | None, stats).
    ``pages`` still carries pre-migration tiers; callers apply the masks (or
    the plan) themselves so data movement can be scheduled separately.

    ``exclude`` (queue mode) removes in-flight pages from the candidate
    sets so a queued migration is never re-selected; holdings telemetry and
    the free-fast computation still count them — an in-flight page keeps
    serving from (and occupying) its source tier until the drain commits.
    With ``exclude=None`` the trace is the original instant-apply program.
    """
    P = pages.owner.shape[0]
    T = max_tenants
    C = count_clamp
    with jax.named_scope("tick.bins"):
        # Per-tenant reductions: owner-segment cumsums when the state carries
        # the sorted permutation (manager-built states), else a [T, P] one-hot.
        oh = None
        if segs is None:
            oh = pages.owner[None, :] == jnp.arange(T, dtype=jnp.int32)[:, None]  # [T,P]

        # ---- 1. per-tenant fast/slow sample counts (tier *before* migration) ----
        is_fast = pages.tier == TIER_FAST
        is_slow = pages.tier == TIER_SLOW
        # owner is stored i16 (packed layouts, types.py); every slot-arithmetic
        # consumer below (flat histogram keys, T + owner offsets) needs i32
        # range, so upcast ONCE here — one fused elementwise pass
        owner32 = pages.owner.astype(jnp.int32)
        if segs is not None:
            # one [2T+1] scatter-add replaces the two global segment cumsums
            # plus their sorted-order gathers (measurably faster under both
            # XLA:CPU runtimes); u32 adds are associative mod 2^32, so the
            # per-tenant totals are bit-identical to the cumsum path whatever
            # the accumulation order (owned pages are always fast or slow:
            # allocate/free set owner and tier together, so fast|slow covers
            # every owned page exactly once)
            T2 = max_tenants
            own_ok = pages.owner >= 0
            idx = jnp.where(
                own_ok & is_fast, owner32,
                jnp.where(own_ok, T2 + owner32, 2 * T2),
            )
            tbl = jnp.zeros((2 * T2 + 1,), jnp.uint32).at[idx].add(
                sampled.astype(jnp.uint32), mode="drop"
            )
            s_fast = tbl[:T2]
            s_slow = tbl[T2 : 2 * T2]
        else:
            s_fast = jnp.where(oh & is_fast[None, :], sampled[None, :], 0).sum(axis=1)
            s_slow = jnp.where(oh & is_slow[None, :], sampled[None, :], 0).sum(axis=1)
        pages, tenants, cooled, eff = bins.accumulate_and_count(
            pages, tenants, sampled, params.num_bins, owner_onehot=oh, segs=segs
        )

    with jax.named_scope("tick.fmmr"):
        # ---- 2. FMMR update ------------------------------------------------------
        now = fmmr.fmmr_now(s_fast.astype(jnp.float32), s_slow.astype(jnp.float32))
        ewma = fmmr.update_ewma(tenants.a_miss, now, params.ewma_lambda)
        ewma = jnp.where(tenants.active, ewma, 0.0)
        tenants = tenants._replace(a_miss=ewma)

    with jax.named_scope("tick.select"):
        # ---- per-(tenant, tier, clamped count) candidate histograms --------------
        # ONE P-element scatter; everything below — holdings, candidate totals,
        # rebalance pair counts, victim cutoffs — reads off these two tables and
        # their prefix sums.
        is_owned = pages.owner >= 0
        owner = jnp.maximum(owner32, 0)
        slow_cand = is_owned & is_slow
        fast_cand = is_owned & is_fast
        if exclude is not None:
            slow_cand = slow_cand & ~exclude
            fast_cand = fast_cand & ~exclude
        key = jnp.minimum(eff.astype(jnp.int32), C - 1)
        flat = jnp.where(
            slow_cand,
            owner * C + key,
            jnp.where(fast_cand, T * C + owner * C + key, 2 * T * C),
        )
        hist2 = jnp.zeros((2 * T * C + 1,), jnp.int32).at[flat].add(1, mode="drop")
        hist_slow = hist2[: T * C].reshape(T, C)
        hist_fast = hist2[T * C : 2 * T * C].reshape(T, C)
        # tiled past 64k-element rows — at [256, 4096] the row scans alone cost
        # ~20 ms untiled (core/tiling.py; bit-identical integer addition)
        cum_slow = tiled_cumsum(hist_slow, axis=1)  # [T,C] candidates with count <= c
        cum_fast = tiled_cumsum(hist_fast, axis=1)
        n_slow_cand = cum_slow[:, -1]  # == per-tenant slow-page holdings
        n_fast_cand = cum_fast[:, -1]  # == per-tenant fast-page holdings
        if exclude is None:
            fast_hold, slow_hold = n_fast_cand, n_slow_cand
        else:
            # in-flight pages are excluded from the candidate histograms but
            # still occupy their source tier: holdings must count them
            fast_hold, slow_hold = _per_tenant_pages(pages, max_tenants, owner_onehot=oh)

    with jax.named_scope("tick.fmmr"):
        # ---- 3. proportional reallocation (budget R/2) ---------------------------
        # alloc_headroom fast pages are reserved for first-touch allocation
        # (DESIGN.md §8): the policy never promotes into them, so a new page's
        # allocation can land fast instead of waiting an epoch for promotion.
        # Allocations may transiently consume the reserve (holdings then exceed
        # the promotion ceiling) — clamp at zero rather than forcing net
        # demotions; request churn regenerates the headroom on free.
        free_fast = jnp.maximum(
            params.fast_capacity - params.alloc_headroom - fast_hold.sum(), 0
        )
        realloc_budget = params.migration_budget // 2
        # asymmetric hysteresis guards: negative band = inherit the symmetric
        # ``hysteresis`` value, which keeps the default program bit-identical
        band_need = jnp.where(
            params.promote_band >= 0, params.promote_band, params.hysteresis
        )
        band_donor = jnp.where(
            params.demote_band >= 0, params.demote_band, params.hysteresis
        )
        ra = fmmr.reallocate(
            tenants, fast_hold, free_fast, realloc_budget,
            fair_mode=params.fair_mode, hysteresis=params.hysteresis,
            need_band=band_need, donor_band=band_donor,
        )
        tenants = tenants._replace(flagged=ra.flagged)
        # the R/2 reallocation budget counts BOTH promotions and the demotions
        # that make room for them: rescale if gives+takes overshoot.
        ra_moves = ra.give.sum() + ra.take.sum()
        ra_scale = jnp.where(
            ra_moves > realloc_budget,
            fmmr.div_rn(realloc_budget, jnp.maximum(ra_moves, 1)),
            1.0,
        )
        take2 = jnp.floor(ra.take * ra_scale).astype(jnp.int32)
        give2 = jnp.floor(ra.give * ra_scale).astype(jnp.int32)
        # integer flooring can break gives <= free + takes: FCFS re-clamp
        give2 = fmmr.clamp_gives(give2, tenants.arrival, free_fast + take2.sum())
        ra = ra._replace(give=give2, take=take2)

    with jax.named_scope("tick.select"):
        # ---- 4. intra-tenant rebalance (budget R/2; each pair = 2 moves) ---------
        n_active = jnp.maximum(tenants.active.sum(), 1)
        rebal_share = (params.migration_budget - realloc_budget) // (2 * n_active)

        # Reallocation consumes the first `give` hottest-slow / `take` coldest-fast
        # victims; the i-th REBALANCE pair is (hot[give+i], cold[take+i]). Pairs
        # must fit the remaining candidates on BOTH sides so promote/demote stay
        # 1:1 per tenant (capacity invariant) — _pair_count enforces this.
        give_eff = jnp.minimum(ra.give, n_slow_cand)
        take_eff = jnp.minimum(ra.take, n_fast_cand)
        n_rebal = _pair_count(cum_slow, cum_fast, give_eff, take_eff, rebal_share)
        n_rebal = jnp.where(tenants.active, n_rebal, 0)

        # ---- 5. quotas -> victim masks -> plan -----------------------------------
        promote_quota = give_eff + n_rebal  # <= n_slow_cand by construction
        demote_quota = take_eff + n_rebal  # <= n_fast_cand by construction

        promote_mask, demote_mask = _select_victims(
            key, owner, slow_cand, fast_cand, cum_slow, cum_fast,
            promote_quota, demote_quota,
        )

        plan = None
        if collect_plan:
            # id lists by rank lookup: the j-th selected page is the first index
            # whose running selection count reaches j+1 — cumsum + searchsorted
            # + masked identity, no P-element scatter (XLA:CPU scatters are
            # element-serial; binary-searching plan_size ranks is ~20x cheaper)
            j = jnp.arange(plan_size, dtype=jnp.int32)
            cum_p = tiled_cumsum(promote_mask.astype(jnp.int32))
            cum_d = tiled_cumsum(demote_mask.astype(jnp.int32))
            idx_p = jnp.searchsorted(cum_p, j + 1, side="left").astype(jnp.int32)
            idx_d = jnp.searchsorted(cum_d, j + 1, side="left").astype(jnp.int32)
            plan = MigrationPlan(
                promote=jnp.where(j < cum_p[-1], idx_p, -1),
                demote=jnp.where(j < cum_d[-1], idx_d, -1),
            )

        # ---- stats ---------------------------------------------------------------
        # selection takes exactly min(quota, candidates) pages per tenant, so the
        # per-tenant promoted/demoted telemetry needs no extra reduction.
        promoted = jnp.minimum(promote_quota, n_slow_cand)
        demoted = jnp.minimum(demote_quota, n_fast_cand)
        stats = EpochStats(
            fmmr_now=now,
            fmmr_ewma=ewma,
            fast_pages=fast_hold,
            slow_pages=slow_hold,
            promoted=promoted,
            demoted=demoted,
            cooled=cooled,
        )
    return pages, tenants, promote_mask, demote_mask, plan, stats


def _apply_masks(pages: PageState, promote_mask, demote_mask) -> PageState:
    """Metadata migration via the victim masks — one fused elementwise pass."""
    tier = jnp.where(
        promote_mask,
        jnp.int8(TIER_FAST),
        jnp.where(demote_mask, jnp.int8(TIER_SLOW), pages.tier),
    )
    return pages._replace(tier=tier)


@partial(jax.jit, static_argnames=("max_tenants", "plan_size", "count_clamp"))
def policy_epoch(
    pages: PageState,
    tenants: TenantState,
    sampled: jax.Array,  # u32[P] sampled accesses this epoch (PEBS analogue)
    params: PolicyParams,
    *,
    max_tenants: int,
    plan_size: int,
    count_clamp: int = COUNT_CLAMP,
):
    """Returns (pages', tenants', MigrationPlan, EpochStats). Tiers in
    ``pages'`` are pre-migration; use :func:`apply_plan` to commit the plan."""
    pages, tenants, _pm, _dm, plan, stats = _epoch_core(
        pages, tenants, sampled, params, max_tenants, plan_size, count_clamp,
        collect_plan=True,
    )
    return pages, tenants, plan, stats


def _apply_plan_core(pages: PageState, plan: MigrationPlan) -> PageState:
    P = pages.tier.shape[0]
    # -1 padding would wrap to P-1: remap to P so mode="drop" discards it
    promote = jnp.where(plan.promote >= 0, plan.promote, P)
    demote = jnp.where(plan.demote >= 0, plan.demote, P)
    tier = pages.tier
    tier = tier.at[promote].set(jnp.int8(TIER_FAST), mode="drop")
    tier = tier.at[demote].set(jnp.int8(TIER_SLOW), mode="drop")
    return pages._replace(tier=tier)


@jax.jit
def apply_plan(pages: PageState, plan: MigrationPlan) -> PageState:
    """Execute a migration plan on the metadata (data movement is the
    caller's job — pools + Pallas page_copy kernel, or DMA on real HW)."""
    return _apply_plan_core(pages, plan)


# --------------------------------------------------------------------------
# Bounded-bandwidth asynchronous migration data plane (DESIGN.md §4).
# --------------------------------------------------------------------------

def _compact(mask, out_len: int, arrays, pads):
    """Stable-compact entries where ``mask`` holds to the front of fresh
    arrays of length ``out_len`` (entries beyond it are dropped — callers
    count them as overflow). Rank lookup instead of scatter: ONE cumsum
    shared by every array, then the j-th kept entry is found by binary
    search and gathered — searchsorted + gathers are orders of magnitude
    cheaper than element-serial scatters on XLA:CPU."""
    cum = tiled_cumsum(mask.astype(jnp.int32))
    j = jnp.arange(out_len, dtype=jnp.int32)
    idx = jnp.searchsorted(cum, j + 1, side="left").astype(jnp.int32)
    idx = jnp.minimum(idx, mask.shape[0] - 1)
    keep = j < cum[-1]
    return [jnp.where(keep, a[idx], pad) for a, pad in zip(arrays, pads)]


def _real_depth(queue: MigrationQueue) -> jax.Array:
    """i32[] count of REAL in-flight migrations: occupied slots whose
    direction is +-1. Cooldown tombstones (direction DIR_NONE) hold their
    page in the exclusion mask but carry no pending migration, so every
    depth consumer of the conservation identity must skip them.
    ``MigrationQueue.depth`` remains the physical slot-occupancy count."""
    return ((queue.page >= 0) & (queue.direction != DIR_NONE)).sum()


def _inflight_mask(state: PolicyState) -> Optional[jax.Array]:
    """bool[P] pages with a queued migration (None when the queue is off)."""
    queue = state.queue
    if queue is None or queue.size == 0:
        return None
    P = state.pending.shape[0]
    idx = jnp.where(queue.page >= 0, queue.page, P)
    return jnp.zeros((P,), bool).at[idx].set(True, mode="drop")


def _queue_tick(
    queue: MigrationQueue,
    plan: MigrationPlan,
    pages: PageState,
    tenants: TenantState,
    params: PolicyParams,
    epoch: jax.Array,  # i32[] current epoch (the queue clock)
):
    """Enqueue this epoch's selections, then drain the FIFO under the
    bandwidth/latency budget and commit the drained tier flips.

    Semantics (all inside the fused tick, fixed shapes throughout):
      * commit-on-completion — tier metadata changes only when an entry
        drains, so in-flight pages keep serving from their source tier;
      * thrashing guard — queued demotions whose page re-heated (hotness
        bin rose above its enqueue-time bin) are cancelled, as are entries
        whose page was freed;
      * drain order — demotions first (they free the fast slots promotions
        need: fast occupancy can never exceed capacity mid-flight), FIFO
        within each direction, promotions additionally capped by free fast
        room; at most ``migration_bandwidth`` total commits per epoch;
      * overflow — entries that neither drain nor fit the fixed queue are
        dropped newest-first (the policy re-selects them next epoch since
        the tiers did not change);
      * storm guards (DESIGN.md §11, all default-off) —
        ``params.promote_admission`` caps new enqueues per direction per
        tick and tightens under cancel pressure; ``params.demote_cooldown``
        turns reheat-cancelled demotions into exclusion tombstones so
        their pages cannot ping-pong straight back into the queue.

    With ``bandwidth=BANDWIDTH_UNLIMITED`` and ``latency=0`` every entry
    drains in its enqueue epoch: placements are identical to instant apply
    and the queue is empty at every epoch boundary.
    """
    Q = queue.size
    S = plan.promote.shape[0]
    W = Q + 2 * S  # workspace: worst-case live entries this epoch
    P = pages.tier.shape[0]

    heat_bin = bins.bin_of(bins.effective_count(pages, tenants), params.num_bins)

    # ---- thrashing / ownership guard on the in-flight entries --------------
    # Slots split into REAL migrations (direction +-1) and TOMBSTONES
    # (direction DIR_NONE): under ``demote_cooldown`` a reheat-cancelled
    # demotion parks its page in the queue instead of vacating, so the
    # in-flight exclusion keeps barring it from re-selection for
    # ``cooldown`` epochs — the select -> cancel -> re-select ping-pong the
    # thrash guard otherwise burns enqueue bandwidth on. Tombstones never
    # drain, never count toward depth/conservation, and expire when the
    # epoch reaches the expiry stored in ``complete_epoch``. With
    # cooldown == 0 no tombstone is ever created and the tick is
    # bit-identical to the pre-guard engine.
    occupied = queue.page >= 0
    real = occupied & (queue.direction != DIR_NONE)
    tomb = occupied & (queue.direction == DIR_NONE)
    qp = jnp.maximum(queue.page, 0)
    owned = pages.owner[qp] >= 0
    reheat = real & (queue.direction == DIR_DEMOTE) & (heat_bin[qp] > queue.heat)
    cancel = real & (~owned | reheat)
    cooldown = jnp.maximum(params.demote_cooldown, 0)
    entomb = cancel & reheat & owned & (cooldown > 0)
    tomb_live = tomb & owned & (epoch < queue.complete_epoch)
    keep = (real & ~cancel) | entomb | tomb_live
    n_cancel = cancel.sum()

    # ---- enqueue: kept entries first (FIFO), then new demotes, promotes ----
    lat = jnp.maximum(params.migration_latency, 0)

    # Same-tick dedupe (queue-conservation fix): a page already carried by
    # a kept entry — live or tombstone — must never gain a second entry in
    # the same tick. Manager paths pre-exclude in-flight pages from
    # selection, but a free -> allocate -> re-select sequence inside one
    # epoch (or a direct policy caller without the exclusion mask) could
    # otherwise enqueue the page twice, double-counting it in
    # ``enqueued == drained + cancelled + dropped + depth``.
    in_q = (
        jnp.zeros((P,), bool)
        .at[jnp.where(keep, queue.page, P)]
        .set(True, mode="drop")
    )

    def _dedupe(ids):
        return jnp.where(in_q[jnp.maximum(ids, 0)], -1, ids)

    d_ids = _dedupe(plan.demote)
    p_ids = _dedupe(plan.promote)

    # ---- queue admission control (params.promote_admission) ----------------
    # Cap NEW enqueues per direction at ``clamp`` per tick, tightening to
    # clamp/2 (clamp/4) when this tick's cancels reach half (all) of the
    # pre-tick depth — a storm that cancels faster than it drains gets its
    # inflow throttled instead of livelocking the queue. The cap is
    # per-direction because a drop-requeue cycle feeds on either side: an
    # oversubscribed selector floods the queue with promotions after a
    # phase flip and with rebalance demotions under steady contention; both
    # overflow the same FIFO and burn the same enqueue work. A rejected
    # selection never enqueues and is NOT counted: the tiers did not
    # change, so the policy simply re-selects it next epoch.
    clamp = params.promote_admission
    depth_pre = real.sum()
    sev = jnp.clip((2 * n_cancel) // jnp.maximum(depth_pre, 1), 0, 2)
    eff = jnp.where(
        clamp < 0,
        jnp.int32(jnp.iinfo(jnp.int32).max),
        jnp.maximum(jnp.maximum(clamp, 0) >> sev, 1),
    )
    pv = p_ids >= 0
    p_ids = jnp.where(pv & (tiled_cumsum(pv.astype(jnp.int32)) <= eff), p_ids, -1)
    dv = d_ids >= 0
    d_ids = jnp.where(dv & (tiled_cumsum(dv.astype(jnp.int32)) <= eff), d_ids, -1)

    def _new(ids, direction):
        v = ids >= 0
        pid = jnp.maximum(ids, 0)
        return (
            ids,
            jnp.where(v, jnp.int8(direction), jnp.int8(0)),
            jnp.full((S,), epoch, jnp.int32),
            jnp.full((S,), epoch + lat, jnp.int32),
            # bins are < 2^7 by construction (types.py): store i8 to match
            # the packed queue leaf
            jnp.where(v, heat_bin[pid], 0).astype(jnp.int8),
        )

    nd, npr = _new(d_ids, DIR_DEMOTE), _new(p_ids, DIR_PROMOTE)
    # entombed slots flip to DIR_NONE and carry their expiry epoch in
    # ``complete_epoch``; ordinary kept entries pass through unchanged
    k_dir = jnp.where(entomb, jnp.int8(DIR_NONE), queue.direction)
    k_cmp = jnp.where(entomb, epoch + cooldown, queue.complete_epoch)
    w_page = jnp.concatenate([jnp.where(keep, queue.page, -1), nd[0], npr[0]])
    w_dir = jnp.concatenate([k_dir, nd[1], npr[1]])
    w_enq = jnp.concatenate([queue.enqueue_epoch, nd[2], npr[2]])
    w_cmp = jnp.concatenate([k_cmp, nd[3], npr[3]])
    w_heat = jnp.concatenate([queue.heat, nd[4], npr[4]])
    n_new = (p_ids >= 0).sum() + (d_ids >= 0).sum()

    # The workspace is already in FIFO order: the surviving queue prefix is
    # front-compacted from the previous tick and new entries append after
    # it. Cancellation holes and plan padding carry page == -1 and drop out
    # of every mask below, so the drain can run DIRECTLY on the workspace —
    # the old front-compaction pass (one cumsum + five scatters) was pure
    # overhead and is gone; only the survivors are re-compacted at the end.
    c_page, c_dir, c_enq, c_cmp, c_heat = w_page, w_dir, w_enq, w_cmp, w_heat

    # ---- bounded drain: demotes first, FIFO within each direction ----------
    cv = c_page >= 0
    elig = cv & (epoch >= c_cmp)
    bw = jnp.where(
        params.migration_bandwidth < 0,
        jnp.int32(jnp.iinfo(jnp.int32).max),
        params.migration_bandwidth,
    ).astype(jnp.int32)
    is_d = elig & (c_dir == DIR_DEMOTE)
    is_p = elig & (c_dir == DIR_PROMOTE)
    drain_d = is_d & (tiled_cumsum(is_d.astype(jnp.int32)) <= bw)
    n_d = drain_d.sum()
    fast_occ = (pages.tier == TIER_FAST).sum()
    # drained promotions respect the allocation reserve too: a promotion
    # selected before an allocation burst must not retake the headroom the
    # burst just consumed (it stays queued until room reappears)
    room = params.fast_capacity - params.alloc_headroom - (fast_occ - n_d)
    drain_p = is_p & (tiled_cumsum(is_p.astype(jnp.int32)) <= jnp.minimum(bw - n_d, room))
    n_p = drain_p.sum()

    # commit-on-completion: tier flips only for the drained entries
    tier = pages.tier
    tier = tier.at[jnp.where(drain_d, c_page, P)].set(jnp.int8(TIER_SLOW), mode="drop")
    tier = tier.at[jnp.where(drain_p, c_page, P)].set(jnp.int8(TIER_FAST), mode="drop")
    pages = pages._replace(tier=tier)

    (drained_d_ids,) = _compact(drain_d, W, (c_page,), (-1,))
    (drained_p_ids,) = _compact(drain_p, W, (c_page,), (-1,))

    # ---- survivors back into the fixed queue; overflow drops the newest ----
    left = cv & ~drain_d & ~drain_p
    n_drop = jnp.maximum(left.sum() - Q, 0)
    q_page, q_dir, q_enq, q_cmp, q_heat = _compact(
        left, Q, (c_page, c_dir, c_enq, c_cmp, c_heat), (-1, 0, 0, 0, 0)
    )
    new_queue = MigrationQueue(
        page=q_page, direction=q_dir, enqueue_epoch=q_enq,
        complete_epoch=q_cmp, heat=q_heat,
    )
    # depth counts REAL migrations only: tombstones occupy slots but carry
    # no pending work, so the conservation identity stays exact under
    # cooldown (the cancel was already counted when the tombstone formed).
    # Overflow drops can only hit new entries — the kept prefix fits the
    # fixed queue by construction — so ``dropped`` is real-only too.
    qstats = QueueStats(
        depth=((q_page >= 0) & (q_dir != DIR_NONE)).sum(),
        enqueued=n_new,
        drained_promote=n_p,
        drained_demote=n_d,
        cancelled=n_cancel,
        dropped=n_drop,
        drained_promote_ids=drained_p_ids,
        drained_demote_ids=drained_d_ids,
    )
    return pages, new_queue, qstats


def _commit(state, pages, tenants, pm, dm, plan, stats, params):
    """Apply this epoch's migrations: instantly (zero-capacity queue — the
    original engine, bit-identical) or through the bounded queue tick.
    Returns (pages', queue', epoch', stats'). The branch is on a static
    array shape, so each mode traces to its own program."""
    queue = state.queue
    if queue is None or queue.size == 0:
        pages = _apply_masks(pages, pm, dm)
        epoch = None if state.epoch is None else state.epoch + 1
        return pages, queue, epoch, stats
    pages, queue, qstats = _queue_tick(queue, plan, pages, tenants, params, state.epoch)
    return pages, queue, state.epoch + 1, stats._replace(queue=qstats)


def _sentinel_bits(
    pages: PageState,
    tenants: TenantState,
    params: PolicyParams,
    max_tenants: int,
    qstats: Optional[QueueStats],
    depth_before: Optional[jax.Array],
) -> jax.Array:
    """Invariant-sentinel bitmask (core/faults.py SENTINEL_*), computed on the
    POST-commit state inside the fused tick. A handful of O(P) reductions —
    cheap next to the tick itself — gated by the traced ``params.sentinel``
    flag so flipping the sentinel never retraces. The host-side
    :func:`repro.core.faults.deep_validate` is the exhaustive counterpart.

    The reductions sit under ``lax.cond`` so a flag-OFF program SKIPS them
    at runtime, not just masks their result — that is what keeps the
    perf-gate's sentinel-off overhead band tight. (Inside the vmapped
    fleet tick the cond lowers to a select and both branches execute; the
    gated band is the single-machine tick, and the fleet's per-machine
    epoch cost dwarfs the reductions.)"""
    i32 = jnp.int32

    def compute(_):
        fast_occ = (pages.tier == TIER_FAST).sum()
        bits = jnp.where(
            fast_occ > params.fast_capacity, i32(SENTINEL_OCCUPANCY), i32(0)
        )
        owned = pages.owner >= 0
        placed = pages.tier != TIER_NONE
        bits = bits | jnp.where(
            jnp.any(owned != placed), i32(SENTINEL_OWNERSHIP), i32(0)
        )
        own = jnp.clip(pages.owner, 0, max_tenants - 1)
        orphan = owned & ~tenants.active[own]
        bits = bits | jnp.where(jnp.any(orphan), i32(SENTINEL_ORPHAN), i32(0))
        bad = jnp.any(~jnp.isfinite(tenants.a_miss))
        bits = bits | jnp.where(bad, i32(SENTINEL_NAN), i32(0))
        if qstats is not None and depth_before is not None:
            flow = (
                qstats.enqueued
                - qstats.drained_promote
                - qstats.drained_demote
                - qstats.cancelled
                - qstats.dropped
            )
            bits = bits | jnp.where(
                qstats.depth != depth_before + flow, i32(SENTINEL_QUEUE), i32(0)
            )
        return bits

    return jax.lax.cond(params.sentinel > 0, compute, lambda _: i32(0), None)


def _tick(
    st: PolicyState,
    pending: jax.Array,  # u32[P] the access backlog this epoch consumes
    params: PolicyParams,
    *,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool,
    count_clamp: int,
    collect_plan: bool,
    compile_sentinel: bool,
    z: Optional[jax.Array] = None,  # pre-drawn sampling deviates (scan path)
):
    """One fused epoch on a ``PolicyState`` (sample -> policy -> commit ->
    sentinel), the body of ``epoch_step`` and of each ``multi_epoch`` step.
    Each stage sits in a ``tick.<stage>`` named scope (sample, bins, fmmr,
    select, queue, sentinel): trace-time metadata that the compiled ops
    carry, so a device trace can split the tick's time by stage."""
    with jax.named_scope("tick.sample"):
        rng, sub = jax.random.split(st.rng)
        sampled = sample_accesses(
            sub, pending, params.sample_period, exact=exact_sampling, z=z
        )
    with jax.named_scope("tick.queue"):
        depth_before = None
        if st.queue is not None and st.queue.size > 0:
            depth_before = _real_depth(st.queue)
        exclude = _inflight_mask(st)
    pages, tenants, pm, dm, plan, stats = _epoch_core(
        st.pages, st.tenants, sampled, params, max_tenants, plan_size,
        count_clamp, collect_plan=collect_plan, exclude=exclude, segs=st.segs,
    )
    with jax.named_scope("tick.queue"):
        pages, queue, epoch, stats = _commit(st, pages, tenants, pm, dm, plan, stats, params)
    if compile_sentinel:
        with jax.named_scope("tick.sentinel"):
            stats = stats._replace(sentinel=_sentinel_bits(
                pages, tenants, params, max_tenants, stats.queue, depth_before
            ))
    new_state = st._replace(
        pages=pages, tenants=tenants,
        pending=jnp.zeros_like(pending), rng=rng,
        queue=queue, epoch=epoch,
    )
    return new_state, plan, stats


def _epoch_step_impl(
    state: PolicyState,
    params: PolicyParams,
    *,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool,
    count_clamp: int,
    compile_sentinel: bool = True,
):
    return _tick(
        state, state.pending, params, max_tenants=max_tenants, plan_size=plan_size,
        exact_sampling=exact_sampling, count_clamp=count_clamp, collect_plan=True,
        compile_sentinel=compile_sentinel,
    )


@lru_cache(maxsize=None)
def _jitted_epoch_step():
    return jax.jit(
        _epoch_step_impl,
        static_argnames=(
            "max_tenants", "plan_size", "exact_sampling", "count_clamp",
            "compile_sentinel",
        ),
        donate_argnums=(0,),
    )


def epoch_step(
    state: PolicyState,
    params: PolicyParams,
    *,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool = False,
    count_clamp: int = COUNT_CLAMP,
    compile_sentinel: bool = True,
):
    """Fused policy tick: sample -> policy -> migrate, one dispatch.

    Consumes ``state.pending`` (the access backlog) and the PRNG key carried
    in the state; returns (state', plan, stats) with ``pending`` zeroed and
    the migration already applied to the metadata. The state buffers are
    donated — do not reuse the argument.
    ``compile_sentinel=False`` omits the invariant-sentinel reductions from
    the program entirely (the reference point for the perf-gate overhead
    band); the default compiles them in, gated by the traced
    ``params.sentinel`` flag.
    """
    return _jitted_epoch_step()(
        state, params, max_tenants=max_tenants, plan_size=plan_size,
        exact_sampling=exact_sampling, count_clamp=count_clamp,
        compile_sentinel=compile_sentinel,
    )


def _trim_stats(stats: EpochStats) -> EpochStats:
    """Drop the telemetry leaves the sweep record path never reads
    (DESIGN.md §6): ``cooled``/``slow_pages``, and — the big one in queue
    mode — the fixed-size drained id lists, whose [W]-wide rows dominate
    the stacked snapshot transfer. ``None`` leaves are empty pytree
    subtrees, so stacking, slicing and host copies all skip them. Safe
    because trimming only runs on paths without a pool-backed data plane
    (the only consumer of the drained id lists)."""
    if stats.queue is not None:
        stats = stats._replace(
            queue=stats.queue._replace(
                drained_promote_ids=None, drained_demote_ids=None
            )
        )
    return stats._replace(cooled=None, slow_pages=None)


def _multi_epoch_impl(
    state: PolicyState,
    params: PolicyParams,
    counts: Optional[jax.Array],
    *,
    k: int,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool,
    count_clamp: int,
    collect_plans: bool,
    trim_stats: bool = False,
    compile_sentinel: bool = True,
):
    P = state.pending.shape[0]
    per_epoch = None
    xs_counts = None
    if counts is not None:
        counts = jnp.asarray(counts, jnp.uint32)
        if counts.ndim == 1:
            per_epoch = counts
        else:
            xs_counts = counts  # [k, P]

    # Pre-draw all sampling noise in one batched call (the per-epoch PRNG
    # split chain still advances identically to k epoch_step calls, so the
    # exact-sampling path is bit-identical to single-stepping). The scan's
    # noise stream was never bit-compatible with single-stepped sampling,
    # so it uses exactly-standardized CLT deviates instead of true
    # normals: popcount of 16 random bits is Binomial(16, 1/2), giving
    # (pc - 8)/2 mean 0 and variance 1 EXACTLY. FMMR consumes per-tenant
    # aggregates of thousands of pages where the CLT washes out the
    # half-sigma granularity — and this costs half the threefry bits and
    # none of the erfinv of a normal draw, which together were the single
    # largest line in the fleet-scan profile (DESIGN.md §5).
    xs_z = None
    if not exact_sampling:
        with jax.named_scope("tick.sample"):
            half = (P + 1) // 2
            bits = jax.random.bits(
                jax.random.fold_in(state.rng, 0x5A), (k, half), jnp.uint32
            )
            pc = jax.lax.population_count
            z2 = jnp.stack([pc(bits & 0xFFFF), pc(bits >> 16)], axis=-1)
            xs_z = (z2.reshape(k, 2 * half)[:, :P].astype(jnp.float32) - 8.0) * 0.5

    # the queue tick consumes the plan id lists, so queue mode always
    # collects them internally even when the caller does not want them out
    queue_mode = state.queue is not None and state.queue.size > 0

    def step(st: PolicyState, x):
        x_counts, z = x
        pending = st.pending
        if per_epoch is not None:
            pending = pending + per_epoch
        if x_counts is not None:
            pending = pending + x_counts
        st2, plan, stats = _tick(
            st, pending, params, max_tenants=max_tenants, plan_size=plan_size,
            exact_sampling=exact_sampling, count_clamp=count_clamp,
            collect_plan=collect_plans or queue_mode,
            compile_sentinel=compile_sentinel, z=z,
        )
        if trim_stats:
            stats = _trim_stats(stats)
        return st2, (plan if collect_plans else None, stats, st2.tenants.flagged)

    state, (plans, stats, flagged) = jax.lax.scan(step, state, (xs_counts, xs_z), length=k)
    return state, plans, stats, flagged


@lru_cache(maxsize=None)
def _jitted_multi_epoch():
    return jax.jit(
        _multi_epoch_impl,
        static_argnames=(
            "k", "max_tenants", "plan_size", "exact_sampling", "count_clamp",
            "collect_plans", "trim_stats", "compile_sentinel",
        ),
        donate_argnums=(0,),
    )


def multi_epoch(
    state: PolicyState,
    params: PolicyParams,
    counts: Optional[jax.Array] = None,
    *,
    k: int,
    max_tenants: int,
    plan_size: int,
    exact_sampling: bool = False,
    count_clamp: int = COUNT_CLAMP,
    collect_plans: bool = True,
    trim_stats: bool = False,
    compile_sentinel: bool = True,
):
    """Scan the fused epoch across ``k`` epochs in ONE dispatch.

    ``counts`` feeds the access stream: ``None`` consumes the backlog already
    in ``state.pending`` (epoch 1) and runs the rest idle; ``[P]`` replays the
    same exact counts every epoch (steady-state workload); ``[k, P]`` gives
    each epoch its own counts. Returns (state', plans, stats, flagged) with
    every per-epoch output stacked on a leading k axis; ``plans`` is None
    when ``collect_plans=False`` (metadata-only simulation — the per-tenant
    promoted/demoted telemetry in ``stats`` is still exact). The state
    buffers are donated — do not reuse the argument. ``trim_stats=True``
    drops the telemetry leaves the sweep record path never reads (see
    :func:`_trim_stats`).
    """
    return _jitted_multi_epoch()(
        state, params, counts, k=k, max_tenants=max_tenants, plan_size=plan_size,
        exact_sampling=exact_sampling, count_clamp=count_clamp,
        collect_plans=collect_plans, trim_stats=trim_stats,
        compile_sentinel=compile_sentinel,
    )
