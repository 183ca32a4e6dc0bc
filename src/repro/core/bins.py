"""Hotness bins with lazy cooling (paper §3.2), dense-array TPU adaptation.

The paper keeps per-bin linked lists; pointer chasing is hostile to TPU, so
bins are *derived* from a dense per-page counter array:

    bin(count) = 0                 if count == 0
               = min(floor(log2(count)) + 1, num_bins - 1)

i.e. bin k>=1 holds counts in [2^(k-1), 2^k) — exponential heat classes, one
bin ~2x hotter than its colder neighbor, exactly the paper's semantics.

Cooling: when any page of a tenant would exceed the hottest bin's threshold
(2^(num_bins-1) with 6 bins), all of that tenant's pages halve — implemented
*lazily* via a per-tenant ``cool_epoch`` counter and per-page ``last_cool``
stamp; a page's effective count is ``count >> (cool_epoch - last_cool)``,
applied on its next touch. Cooling fires at most once per epoch (paper).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import OwnerSegments, PageState, TenantState


def bin_of(count: jax.Array, num_bins) -> jax.Array:
    """Vectorized heat-bin id for (effective) counts."""
    c = count.astype(jnp.uint32)
    # floor(log2(c)) via bit width; c==0 -> bin 0
    fl = jnp.where(c > 0, 31 - jax.lax.clz(jnp.maximum(c, 1).astype(jnp.int32)), -1)
    return jnp.clip(fl + 1, 0, num_bins - 1).astype(jnp.int32)


def cool_threshold(num_bins) -> jax.Array:
    """Counts >= 2^(num_bins-1) trigger a tenant-wide cooling event."""
    return (jnp.uint32(1) << jnp.uint32(num_bins - 1)).astype(jnp.uint32)


def effective_count(pages: PageState, tenants: TenantState) -> jax.Array:
    """Apply pending (lazy) cooling: count >> cooling events since last touch."""
    owner = jnp.maximum(pages.owner, 0)
    pending = jnp.maximum(tenants.cool_epoch[owner] - pages.last_cool, 0)
    pending = jnp.minimum(pending, 31).astype(jnp.uint32)
    eff = pages.count >> pending
    return jnp.where(pages.owner >= 0, eff, jnp.uint32(0))


def accumulate_and_count(
    pages: PageState,
    tenants: TenantState,
    sampled: jax.Array,  # u32[P] sampled accesses this epoch
    num_bins,
    owner_onehot: jax.Array = None,  # bool[T, P] (owner == t), built if None
    segs: OwnerSegments = None,  # owner segments: cooled via a [T+1] scatter-add instead
) -> Tuple[PageState, TenantState, jax.Array, jax.Array]:
    """Fold one epoch of samples into the counters; fire cooling if needed.

    Returns (pages, tenants, cooled[T] bool, eff u32[P]) where ``eff`` is the
    post-accumulation effective count (what ``effective_count`` would return
    on the new state) — computed here for free so the policy hot path does not
    need a second cooling-materialization pass. Lazy-cooling bookkeeping:
    pages touched this epoch materialize their pending shifts; untouched
    pages keep their stale counts + stamps (materialized on their next touch
    or read via ``effective_count``).
    """
    T = tenants.cool_epoch.shape[0]
    eff = effective_count(pages, tenants)
    new_count = eff + sampled.astype(jnp.uint32)
    touched = sampled > 0
    owner = jnp.maximum(pages.owner, 0)

    count1 = jnp.where(touched, new_count, pages.count)
    last1 = jnp.where(touched, tenants.cool_epoch[owner], pages.last_cool)

    # cooling: any page of tenant t reaching the top-bin threshold halves all.
    thresh = cool_threshold(num_bins)
    over = touched & (new_count >= thresh) & (pages.owner >= 0)
    if segs is not None:
        # one [T+1] scatter-add of the over flags (cheaper than the global
        # cumsum + sorted gather under both XLA:CPU runtimes; exact integer
        # counts, so the any-reduction is bit-identical)
        idx = jnp.where(over, owner, T)
        cooled = jnp.zeros((T + 1,), jnp.int32).at[idx].add(1, mode="drop")[:T] > 0
    else:
        if owner_onehot is None:
            owner_onehot = pages.owner[None, :] == jnp.arange(T, dtype=jnp.int32)[:, None]
        cooled = (owner_onehot & over[None, :]).any(axis=1)
    cool_epoch2 = tenants.cool_epoch + cooled.astype(jnp.int32)

    # materialize the new cooling event for touched pages immediately
    do_halve = cooled[owner] & touched
    count2 = jnp.where(do_halve, count1 >> 1, count1)
    last2 = jnp.where(touched, cool_epoch2[owner], last1)

    pages2 = pages._replace(count=count2, last_cool=last2)
    tenants2 = tenants._replace(cool_epoch=cool_epoch2)
    # effective count on the NEW state: touched pages are fully materialized;
    # untouched pages halve once more if their tenant cooled this epoch.
    eff_new = jnp.where(do_halve, count1 >> 1, jnp.where(touched, count1, eff))
    eff_new = jnp.where(~touched & cooled[owner], eff_new >> 1, eff_new)
    eff_new = jnp.where(pages.owner >= 0, eff_new, jnp.uint32(0))
    return pages2, tenants2, cooled, eff_new


def accumulate_samples(
    pages: PageState,
    tenants: TenantState,
    sampled: jax.Array,  # u32[P] sampled accesses this epoch
    num_bins,
) -> Tuple[PageState, TenantState, jax.Array]:
    """Compatibility wrapper around :func:`accumulate_and_count`; returns
    (pages, tenants, cooled[T] bool)."""
    pages2, tenants2, cooled, _ = accumulate_and_count(pages, tenants, sampled, num_bins)
    return pages2, tenants2, cooled


def count_histogram(
    values: jax.Array,  # i32/u32[P] per-page bucket keys (clamped to num_buckets-1)
    owner: jax.Array,  # i32[P] tenant slot; entries with mask=False ignored
    mask: jax.Array,  # bool[P] which pages participate
    num_buckets: int,
    max_tenants: int,
) -> jax.Array:
    """[T, num_buckets] page counts per (tenant, bucket).

    The generic form of the paper's per-bin lists: one scatter-add builds the
    whole (tenant, bucket) occupancy table in O(P); cumulative sums over the
    bucket axis then give exact victim *ranks* without any sort (DESIGN.md §2).
    """
    key = jnp.minimum(values.astype(jnp.int32), num_buckets - 1)
    # owner may be the packed i16 leaf: the flat key needs i32 range
    flat = jnp.where(
        mask, owner.astype(jnp.int32) * num_buckets + key,
        max_tenants * num_buckets,
    )
    hist = jnp.zeros((max_tenants * num_buckets + 1,), jnp.int32).at[flat].add(
        1, mode="drop"
    )
    return hist[:-1].reshape(max_tenants, num_buckets)


def heat_histogram(
    pages: PageState, tenants: TenantState, num_bins: int, max_tenants: int
) -> jax.Array:
    """[T, num_bins] page counts per (tenant, bin) — the heat gradient."""
    eff = effective_count(pages, tenants)
    b = bin_of(eff, num_bins)
    return count_histogram(b, pages.owner, pages.owner >= 0, num_bins, max_tenants)
