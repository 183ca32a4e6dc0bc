"""FMMR measurement + proportional fast-memory reallocation (paper §3.1).

All functions are pure/jittable and operate on [T]-shaped tenant arrays.

Reallocation semantics implemented exactly as §3.1:
  * needers (a_miss > t_miss) receive migration bandwidth
        M_p = (a_miss/t_miss) / F_need * R
  * donors (a_miss < t_miss, holding fast memory) give up
        M_p = (t_miss/a_miss) / F_surplus * R
  * a_miss == 0 denominators substitute infinity, inf/inf = 1; with multiple
    a_miss == 0 donors only ONE (earliest arrival) donates per epoch.
  * takes are capped at the donor's current fast pages.
  * gives are additionally capped by what is actually available (free fast
    pages + takes); when infeasible, needers are served FCFS by arrival
    (paper default) or equal-fraction (fair_mode).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import TenantState

_EPS = 1e-9


def div_rn(x, y) -> jax.Array:
    """float32 ``x / y`` correctly rounded, in int32 ops.

    A TPU's float32 divide is not correctly rounded: of 1,000,000 quotients
    the v5e returns about a third one ulp off (PERF.md §4), and a quota
    floored from such a quotient can land one page off the IEEE result the
    CPU gives. Here the 24-bit mantissas are divided by shift-and-subtract
    to 25 quotient bits, and the guard bit rounds (a quotient of two
    float32 never falls exactly halfway between two of them), so every
    backend gives the IEEE quotient bit for bit. Zero, non-finite and
    subnormal operands and results outside the normal range take the plain
    divide.
    """
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    bx = jax.lax.bitcast_convert_type(x, jnp.int32)
    by = jax.lax.bitcast_convert_type(y, jnp.int32)
    ex = (bx >> 23) & 0xFF
    ey = (by >> 23) & 0xFF
    mx = (bx & 0x7FFFFF) | 0x800000
    my = (by & 0x7FFFFF) | 0x800000
    lo = (mx < my).astype(jnp.int32)  # quotient of mantissas below 1
    r = mx << lo
    q = jnp.zeros_like(r)
    for _ in range(25):  # 24 mantissa bits and a guard bit
        bit = (r >= my).astype(jnp.int32)
        q = (q << 1) | bit
        r = (r - bit * my) << 1
    mant = (q >> 1) + (q & 1)
    carry = mant >> 24
    e = ex - ey - lo + 127 + carry
    bits = ((bx ^ by) & jnp.int32(-(1 << 31))) | (e << 23) | ((mant >> carry) & 0x7FFFFF)
    exact = jax.lax.bitcast_convert_type(bits, jnp.float32)
    ok = (ex > 0) & (ex < 255) & (ey > 0) & (ey < 255) & (e > 0) & (e < 255)
    return jnp.where(ok, exact, x / y)


def fmmr_now(a_fast: jax.Array, a_slow: jax.Array) -> jax.Array:
    """Instantaneous FMMR; 0 when no samples (idle tenants decay, §3.1)."""
    tot = a_fast + a_slow
    return jnp.where(tot > 0, div_rn(a_slow, jnp.maximum(tot, 1)), 0.0).astype(jnp.float32)


def update_ewma(prev: jax.Array, now: jax.Array, lam) -> jax.Array:
    return (lam * now + (1.0 - lam) * prev).astype(jnp.float32)


class Realloc(NamedTuple):
    give: jax.Array  # i32[T] fast pages granted this epoch
    take: jax.Array  # i32[T] fast pages reclaimed this epoch
    flagged: jax.Array  # bool[T] needers that could not be served


def reallocate(
    tenants: TenantState,
    fast_pages: jax.Array,  # i32[T] current fast-page holdings
    free_fast: jax.Array,  # i32[] unallocated fast slots
    budget: jax.Array,  # i32[] R: pages of reallocation bandwidth this epoch
    fair_mode: bool = False,
    hysteresis=0.0,
    need_band=None,
    donor_band=None,
) -> Realloc:
    act = tenants.active
    a, t = tenants.a_miss, tenants.t_miss
    R = budget.astype(jnp.float32)
    band = jnp.asarray(hysteresis, jnp.float32)
    # Asymmetric trigger bands (PolicyParams.promote_band/demote_band): the
    # needer and donor thresholds may carry their own hysteresis. ``None``
    # falls back to the symmetric ``hysteresis`` band (the original engine).
    nb = band if need_band is None else jnp.asarray(need_band, jnp.float32)
    db = band if donor_band is None else jnp.asarray(donor_band, jnp.float32)

    need_mask = act & (a > t * (1.0 + nb))
    # donors: below target AND holding fast memory. a==0 handled separately.
    donor_mask = act & (a < t * (1.0 - db)) & (fast_pages > 0)
    zero_donor = donor_mask & (a <= _EPS)

    # --- takes ---------------------------------------------------------------
    # finite-ratio donors
    ratio_d = jnp.where(donor_mask & ~zero_donor, div_rn(t, jnp.maximum(a, _EPS)), 0.0)
    # a_miss == 0 donors: ratio would be inf; only the earliest-arrival one
    # donates, and (inf / inf == 1) it absorbs the full take bandwidth.
    any_zero = zero_donor.any()
    arrival_key = jnp.where(zero_donor, tenants.arrival, jnp.iinfo(jnp.int32).max)
    first_zero = jnp.argmin(arrival_key)
    F_surplus = ratio_d.sum()
    take_frac = jnp.where(
        any_zero,
        jnp.zeros_like(ratio_d).at[first_zero].set(1.0) * zero_donor.any(),
        jnp.where(F_surplus > 0, div_rn(ratio_d, jnp.maximum(F_surplus, _EPS)), 0.0),
    )
    take = jnp.minimum(jnp.floor(take_frac * R).astype(jnp.int32), fast_pages)
    take = jnp.where(act, take, 0)

    # --- gives ---------------------------------------------------------------
    ratio_n = jnp.where(need_mask, div_rn(a, jnp.maximum(t, _EPS)), 0.0)
    F_need = ratio_n.sum()
    give_want = jnp.where(
        F_need > 0, jnp.floor(div_rn(ratio_n, jnp.maximum(F_need, _EPS)) * R), 0.0
    ).astype(jnp.int32)

    available = free_fast.astype(jnp.int32) + take.sum()
    total_want = give_want.sum()

    def _fcfs(give_want):
        # serve earliest arrivals fully first (paper default)
        order = jnp.argsort(jnp.where(need_mask, tenants.arrival, jnp.iinfo(jnp.int32).max))
        want_sorted = give_want[order]
        cum = jnp.cumsum(want_sorted)
        grant_sorted = jnp.clip(available - (cum - want_sorted), 0, want_sorted)
        return jnp.zeros_like(give_want).at[order].set(grant_sorted)

    def _fair(give_want):
        scale = jnp.where(
            total_want > 0,
            jnp.minimum(1.0, div_rn(available, jnp.maximum(total_want, 1))),
            0.0,
        )
        return jnp.floor(give_want.astype(jnp.float32) * scale).astype(jnp.int32)

    # fair_mode may be a traced bool (it lives in PolicyParams): evaluate both
    # allocations (cheap, [T]-sized) and select.
    give = jnp.where(jnp.asarray(fair_mode), _fair(give_want), _fcfs(give_want))
    give = jnp.where(act, give, 0)

    # avoid useless churn: don't take more than what gets redistributed
    # (paper: "stopping once it has met all the target FMMRs it can")
    excess_take = jnp.maximum(take.sum() - jnp.maximum(give.sum() - free_fast, 0), 0)
    # release excess from donors proportionally (largest takes first)
    def _trim(take, excess):
        order = jnp.argsort(-take)
        t_sorted = take[order]
        cum = jnp.cumsum(t_sorted)
        # keep = take - portion of excess assigned greedily
        reduce_sorted = jnp.clip(excess - (cum - t_sorted), 0, t_sorted)
        return jnp.zeros_like(take).at[order].set(t_sorted - reduce_sorted)

    take = _trim(take, excess_take)

    # --- §3.4 fair sharing: with no needers, equalize the surplus -----------
    # "If more fast memory is still available at this point, then MaxMem
    # allocates the remaining equally to all processes." Tenants strictly
    # below target shed fast pages beyond their equal share; under-share
    # tenants receive them (bounded by the same migration budget).
    no_needers = ~need_mask.any()
    n_act = jnp.maximum(act.sum(), 1)
    share = (fast_pages.sum() + free_fast) // n_act
    # a TRICKLE (budget/8) so equalization can never fight target convergence:
    # tenants drift toward equal share; the moment one crosses its target the
    # needer path (full budget) dominates again.
    trickle = jnp.maximum(budget.astype(jnp.int32) // 8, 1)
    # only tenants COMFORTABLY below target donate surplus (hysteresis margin
    # keeps tenants hovering at their target from oscillating)
    want_take_eq = jnp.where(
        act & (a < 0.7 * t), jnp.maximum(fast_pages - share, 0), 0
    )
    want_give_eq = jnp.where(act, jnp.maximum(share - fast_pages, 0), 0)

    def _scale(want, cap):
        tot = jnp.maximum(want.sum(), 1.0)
        return jnp.floor(want * div_rn(jnp.minimum(cap, tot), tot)).astype(jnp.int32)

    matched = jnp.minimum(
        jnp.minimum(want_take_eq.sum(), want_give_eq.sum() + free_fast), trickle
    ).astype(jnp.float32)
    take_eq = _scale(want_take_eq.astype(jnp.float32), matched)
    give_eq = _scale(
        want_give_eq.astype(jnp.float32),
        jnp.minimum((take_eq.sum() + free_fast).astype(jnp.float32),
                    trickle.astype(jnp.float32)),
    )
    give = jnp.where(no_needers, give_eq, give)
    take = jnp.where(no_needers, take_eq, take)

    flagged = need_mask & (give == 0) & (give_want > 0)
    return Realloc(give=give, take=take, flagged=flagged)


def clamp_gives(give: jax.Array, arrival: jax.Array, available: jax.Array) -> jax.Array:
    """Greedy FCFS clamp so that sum(give) <= available (invariant repair
    after integer rescaling)."""
    order = jnp.argsort(jnp.where(give > 0, arrival, jnp.iinfo(jnp.int32).max))
    g_sorted = give[order]
    cum = jnp.cumsum(g_sorted)
    grant = jnp.clip(available - (cum - g_sorted), 0, g_sorted)
    return jnp.zeros_like(give).at[order].set(grant)
