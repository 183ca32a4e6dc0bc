"""MaxMem core state pytrees.

All policy state lives in fixed-size jnp arrays so the per-epoch policy step
is one jittable pure function (`repro.core.policy.policy_epoch`). Tenants are
slots in [0, max_tenants); pages are slots in a global pool [0, num_pages).

Tier encoding per page: -1 unallocated, 0 slow, 1 fast.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

TIER_NONE = -1
TIER_SLOW = 0
TIER_FAST = 1

# Migration-queue entry directions (core/policy.py data plane).
DIR_NONE = 0
DIR_PROMOTE = 1
DIR_DEMOTE = -1

# PolicyParams.migration_bandwidth sentinel: drain the whole queue per epoch.
BANDWIDTH_UNLIMITED = -1

# Widest tenant slot index an int16 ``PageState.owner`` can carry (packed
# state layouts, DESIGN.md §10). Enforced at state-construction time; every
# compute site that does slot *arithmetic* (e.g. ``owner * C + key`` flat
# histogram keys) upcasts to int32 first, so the narrow width is purely a
# storage/bandwidth contract.
MAX_TENANT_SLOTS = 32767


class PolicyParams(NamedTuple):
    """Knobs of the paper's policy (§3.1/§3.2) in page units."""

    fast_capacity: jnp.int32  # F: fast-tier page slots
    migration_budget: jnp.int32  # R: total pages migrated per epoch (paper: 4 GB)
    num_bins: jnp.int32 = 6  # hotness bins (paper: 6)
    ewma_lambda: jnp.float32 = 0.5  # FMMR EWMA (paper: 0.5)
    sample_period: jnp.int32 = 100  # PEBS-analogue: 1-in-100 accesses
    fair_mode: bool = False  # False = paper FCFS; True = equal-distance fairness
    # Stability addition (beyond paper; see EXPERIMENTS §Perf notes): tenants
    # within +-hysteresis of target are neither needers nor donors. Without
    # it, near-saturated mixes oscillate: serving one needer flips marginal
    # donors over target and starvation rotates tenant-to-tenant.
    hysteresis: jnp.float32 = 0.08
    # Migration data plane (DESIGN.md §4). Only consulted when the state
    # carries a non-empty MigrationQueue; with queue_size=0 the policy
    # applies migrations instantly (the pre-data-plane behavior).
    # bandwidth: pages the DMA engine can commit per epoch
    # (BANDWIDTH_UNLIMITED = drain everything — degenerates to instant).
    migration_bandwidth: jnp.int32 = BANDWIDTH_UNLIMITED
    # latency: epochs an entry waits in the queue before it may commit.
    migration_latency: jnp.int32 = 0
    # Invariant sentinel (DESIGN.md §7): when > 0 the fused tick emits a
    # violation bitmask (core/faults.py SENTINEL_*) in EpochStats.sentinel.
    # Traced, so flipping it never retraces; compiling the checks out
    # entirely is the static ``compile_sentinel`` knob on the entry points.
    sentinel: jnp.int32 = 0
    # Allocation headroom (DESIGN.md §8, TPP-style): fast pages the policy
    # leaves unfilled so first-touch allocations of NEW pages can land fast
    # instead of waiting an epoch for promotion. The policy treats
    # ``fast_capacity - alloc_headroom`` as its promotion ceiling; the
    # allocator still fills to ``fast_capacity``, and request churn
    # (free -> allocate) keeps regenerating the reserve. Traced: the
    # serving benchmark legs flip it without retracing.
    alloc_headroom: jnp.int32 = 0
    # Adversarial-dynamics guards (DESIGN.md §11) — every knob defaults OFF
    # and is traced, so guarded and unguarded runs share one compiled
    # program and the default program is bit-identical to the pre-guard
    # engine.
    # Asymmetric FMMR hysteresis: separate trigger bands for needers
    # (promotion pressure) and donors (demotion pressure). A tenant only
    # becomes a needer above ``t * (1 + promote_band)`` and a donor below
    # ``t * (1 - demote_band)``. Negative = inherit the symmetric
    # ``hysteresis`` band.
    promote_band: jnp.float32 = -1.0
    demote_band: jnp.float32 = -1.0
    # Promotion admission control: cap on NEW promotion enqueues per queue
    # tick. The effective cap tightens (halves, then quarters) as the
    # tick's cancel count rises against the pre-tick queue depth — graceful
    # degradation under promotion/demotion storms instead of queue
    # livelock. Negative = unlimited (bit-identical to no admission).
    promote_admission: jnp.int32 = -1
    # Queue-aware victim cooldown: epochs a reheat-cancelled demotion's
    # page stays barred from re-selection. The cancelled entry leaves a
    # tombstone (direction DIR_NONE) in the queue, which keeps the page in
    # the in-flight exclusion mask until the tombstone expires — breaking
    # the select -> cancel -> re-select ping-pong that burns enqueue
    # bandwidth. 0 = off (cancelled entries vacate immediately).
    demote_cooldown: jnp.int32 = 0

    @classmethod
    def from_profile(cls, name: str, **overrides) -> "PolicyParams":
        """Load a committed tuned profile from ``repro.configs.tuned``.

        Profiles are the autotuner's committed winners (one JSON per
        scenario family × geometry, e.g. ``"thrash_4k"``; see DESIGN.md §9
        and docs/PARAMS.md). Returns a fully-populated ``PolicyParams``
        with every leaf cast to its traced dtype; keyword ``overrides``
        replace individual fields (e.g. a different ``fast_capacity`` when
        replaying a profile on a machine with another tier geometry).
        """
        # lazy import: configs.tuned needs PolicyParams itself
        from repro.configs.tuned import params_from_profile

        return params_from_profile(name, **overrides)


class TenantState(NamedTuple):
    """Per-tenant QoS state. Arrays of length max_tenants."""

    active: jax.Array  # bool[T]
    t_miss: jax.Array  # f32[T] target FMMR in (0, 1]
    a_miss: jax.Array  # f32[T] EWMA of achieved FMMR
    arrival: jax.Array  # i32[T] arrival order (FCFS tie-break); lower = earlier
    cool_epoch: jax.Array  # i32[T] per-tenant cooling counter (lazy cooling)
    flagged: jax.Array  # bool[T] cannot meet target (admin signal, §3.1)

    @classmethod
    def create(cls, max_tenants: int) -> "TenantState":
        T = max_tenants
        return cls(
            active=jnp.zeros((T,), bool),
            t_miss=jnp.ones((T,), jnp.float32),
            a_miss=jnp.zeros((T,), jnp.float32),
            arrival=jnp.full((T,), jnp.iinfo(jnp.int32).max, jnp.int32),
            cool_epoch=jnp.zeros((T,), jnp.int32),
            flagged=jnp.zeros((T,), bool),
        )

    def clear_slot(self, slot: int) -> "TenantState":
        """Reset one slot to its creation defaults. Departure must scrub the
        whole slot: a merely-deactivated slot leaks its EWMA/target through
        ``fmmr_of`` until the next epoch zeroes it, and scenario-driven churn
        reuses slots within the same epoch."""
        return self._replace(
            active=self.active.at[slot].set(False),
            t_miss=self.t_miss.at[slot].set(1.0),
            a_miss=self.a_miss.at[slot].set(0.0),
            arrival=self.arrival.at[slot].set(jnp.iinfo(jnp.int32).max),
            cool_epoch=self.cool_epoch.at[slot].set(0),
            flagged=self.flagged.at[slot].set(False),
        )


class PageState(NamedTuple):
    """Per-page metadata. Arrays of length num_pages.

    Dtype-width audit (packed state layouts, DESIGN.md §10) — the [P]
    leaves dominate state bytes, upload cost, and the memory-bound passes
    of the fused tick, so each field carries the narrowest width its value
    range admits:

    * ``owner`` i16: tenant slots are bounded by :data:`MAX_TENANT_SLOTS`
      (asserted at construction). Index gathers take any int width; the
      flat-key arithmetic sites upcast to i32 locally.
    * ``tier`` i8: three-valued.
    * ``count`` u32 — NOT narrowable: counts accumulate raw sampled
      accesses between cooling events, and cooling only halves a tenant's
      pages when one of them crosses ``2^(num_bins-1)`` *via a touch* —
      exact-sampling replays fold entire backlogs in at once, so a single
      epoch can legitimately add far more than 2^16 to one page.
    * ``last_cool`` i32 — pairs with ``TenantState.cool_epoch`` (i32,
      monotone over the run); a narrower stamp would wrap on long sweeps
      and silently un-cool a stale page.
    """

    owner: jax.Array  # i16[P] tenant slot, -1 if unallocated
    tier: jax.Array  # i8[P]
    count: jax.Array  # u32[P] accumulated (lazily cooled) sample count
    last_cool: jax.Array  # i32[P] owner cool_epoch at last count update

    @classmethod
    def create(cls, num_pages: int) -> "PageState":
        P = num_pages
        return cls(
            owner=jnp.full((P,), -1, jnp.int16),
            tier=jnp.full((P,), TIER_NONE, jnp.int8),
            count=jnp.zeros((P,), jnp.uint32),
            last_cool=jnp.zeros((P,), jnp.int32),
        )


class OwnerSegments(NamedTuple):
    """Host-maintained owner-sorted page permutation (DESIGN.md §5).

    Page ownership only changes on control-plane operations (allocate /
    free), so the manager keeps a permutation of page ids sorted by
    (owner, page id) — stable, unowned pages last — and rebuilds it there.
    Its presence selects the tick's scatter-add per-tenant sums and
    cooling flags (``core/bins.py``) over [T, P] one-hot passes; the tick
    reads none of its arrays. In-bucket victim ranks and in-flight holdings
    once went through the permutation (a gather into owner-sorted order,
    a global cumsum, a gather back); on a TPU v5e each of those P-long
    gathers costs about 7 ns an element, and eight of them were most of
    the tick, so they are fused [T, P] compare-and-reduce passes at every
    T now (``policy._bucket_cutoffs``). Results are bit-identical on both
    paths.
    """

    order: jax.Array  # i32[P] page ids sorted by (owner, id); unowned last
    inv: jax.Array  # i32[P] inverse permutation: inv[order[i]] = i
    start: jax.Array  # i32[T+1] first sorted index per tenant; start[T] = #owned

    @classmethod
    def build(cls, owner, max_tenants: int) -> "OwnerSegments":
        """Host-side rebuild from an owner array (numpy or device)."""
        import numpy as np

        order, inv, start = segments_build_host(np.asarray(owner), max_tenants)
        return cls(
            order=jnp.asarray(order), inv=jnp.asarray(inv), start=jnp.asarray(start)
        )


def segments_build_host(owner, max_tenants: int):
    """From-scratch ``(order, inv, start)`` host arrays for an owner array
    — ONE stable argsort; the reference the incremental patcher must match
    bit-for-bit."""
    import numpy as np

    own = np.asarray(owner)
    key = np.where(own >= 0, own, max_tenants)
    order = np.argsort(key, kind="stable").astype(np.int32)
    inv = np.empty_like(order)
    inv[order] = np.arange(order.shape[0], dtype=np.int32)
    counts = np.bincount(key, minlength=max_tenants + 1)
    start = np.zeros((max_tenants + 1,), np.int32)
    np.cumsum(counts[:max_tenants], out=start[1:])
    return order, inv, start


def segments_update_host(order, inv, start, prev_owner, new_owner, changed, max_tenants):
    """Patch ``(order, inv, start)`` for the pages in ``changed`` whose
    owner moved from ``prev_owner`` to ``new_owner`` — the incremental
    alternative to :func:`segments_build_host` the manager uses on
    register/allocate/free/unregister churn (DESIGN.md §10).

    The permutation is uniquely determined by the stable (key, id) sort
    order and ids are unique, so delete-then-merge reproduces the full
    rebuild BIT-IDENTICALLY: changed entries are deleted from their old
    sorted positions (known in O(1) each via ``inv``), re-keyed, sorted
    among themselves (d log d for d changes), and merged back at positions
    found by binary search on the composite (key, id) rank. Sequential
    O(P) memmoves + O(d log P) searches replace the full O(P log P)
    random-access argsort.

    ``changed`` must contain each mutated page id exactly once with
    ``prev_owner[p] != new_owner[p]``; ``inv``/``order``/``start`` must
    describe ``prev_owner``.
    """
    import numpy as np

    P = order.shape[0]
    T = max_tenants
    changed = np.asarray(changed, np.int64)
    old_k = np.where(prev_owner[changed] >= 0, prev_owner[changed], T).astype(np.int64)
    new_k = np.where(new_owner[changed] >= 0, new_owner[changed], T).astype(np.int64)

    # Every changed page is removed once and inserted once, and both its
    # segments lie inside [first affected segment, last affected segment] —
    # so sorted positions OUTSIDE that segment-aligned window carry zero net
    # shift and the splice (delete + merge + inverse-permutation scatter)
    # only has to touch the window. bounds[t] is the first sorted index of
    # segment t (t == T is the unowned tail), bounds[T+1] == P.
    bounds = np.concatenate([start.astype(np.int64), [np.int64(P)]])
    k_lo = int(min(old_k.min(), new_k.min()))
    k_hi = int(max(old_k.max(), new_k.max()))
    lo = int(bounds[k_lo])
    hi = int(bounds[k_hi + 1])

    win = order[lo:hi]
    rm_local = np.sort(inv[changed]) - lo
    kept_win = np.delete(win, rm_local)
    # kept segment starts, window-relative: old starts shifted left by the
    # removals in earlier window segments
    rem_counts = np.bincount(old_k - k_lo, minlength=k_hi - k_lo + 1)
    wb = bounds[k_lo : k_hi + 2] - lo
    kept_wb = wb - np.concatenate([[0], np.cumsum(rem_counts)])

    # Merge positions WITHOUT materializing an O(P) composite key: within a
    # segment `kept_win` is id-ascending, so group the (re-keyed, id-sorted)
    # changed entries by destination segment — at most min(d, T+1) groups —
    # and binary-search each group's ids inside that one segment slice.
    ins_sort = np.argsort(new_k * np.int64(P) + changed, kind="stable")
    changed_sorted = changed[ins_sort].astype(np.int32)
    keys_sorted = new_k[ins_sort]
    pos = np.empty(changed_sorted.shape[0], np.int64)
    seg_ids, run_starts = np.unique(keys_sorted, return_index=True)
    run_ends = np.append(run_starts[1:], keys_sorted.shape[0])
    for k, rlo, rhi in zip(seg_ids, run_starts, run_ends):
        kw = int(k) - k_lo
        seg = kept_win[kept_wb[kw] : kept_wb[kw + 1]]
        pos[rlo:rhi] = kept_wb[kw] + np.searchsorted(seg, changed_sorted[rlo:rhi])
    new_win = np.insert(kept_win, pos, changed_sorted)

    new_order = order.copy()
    new_order[lo:hi] = new_win
    new_inv = inv.copy()
    new_inv[new_win] = np.arange(lo, hi, dtype=np.int32)

    counts = np.concatenate([np.diff(start), [np.int32(P) - start[T]]]).astype(np.int64)
    np.add.at(counts, new_k, 1)
    np.add.at(counts, old_k, -1)
    new_start = np.zeros((T + 1,), np.int32)
    new_start[1:] = np.cumsum(counts[:T]).astype(np.int32)
    return new_order, new_inv, new_start


class MigrationQueue(NamedTuple):
    """Fixed-shape in-flight migration queue (DESIGN.md §4).

    Array order IS FIFO order (the per-epoch tick compacts valid entries to
    the front). ``page == -1`` marks an empty slot. Tier metadata does not
    change at enqueue: a queued page keeps serving from its source tier
    until the bounded-bandwidth drain commits the entry
    (commit-on-completion, like the paper's asynchronous DMA migrations).
    """

    page: jax.Array  # i32[Q] page id, -1 = empty slot (pools exceed 2^15 pages)
    direction: jax.Array  # i8[Q] DIR_PROMOTE / DIR_DEMOTE / DIR_NONE
    enqueue_epoch: jax.Array  # i32[Q] epoch the entry was admitted
    complete_epoch: jax.Array  # i32[Q] first epoch the entry may commit
    # Heat bins are ``bin_of`` values, bounded by num_bins - 1 <= 31 (bins
    # derive from u32 counts), so one byte holds the thrashing-guard
    # snapshot; epochs stay i32 (monotone queue clock, wraps on long runs
    # otherwise).
    heat: jax.Array  # i8[Q] hotness bin at enqueue (thrashing guard)

    @classmethod
    def create(cls, size: int) -> "MigrationQueue":
        return cls(
            page=jnp.full((size,), -1, jnp.int32),
            direction=jnp.zeros((size,), jnp.int8),
            enqueue_epoch=jnp.zeros((size,), jnp.int32),
            complete_epoch=jnp.zeros((size,), jnp.int32),
            heat=jnp.zeros((size,), jnp.int8),
        )

    @property
    def size(self) -> int:
        return self.page.shape[0]

    @property
    def depth(self) -> jax.Array:
        return (self.page >= 0).sum()


class QueueStats(NamedTuple):
    """Per-epoch migration-queue telemetry (scalars + fixed-size id lists).

    Conservation contract (tested after every event and epoch):
    cumulative enqueued == drained + cancelled + dropped + current depth.
    The drained id lists are sized [W] (W = queue capacity + both plan
    sides), padded with -1 — fixed-size plans the pool-backed data plane
    feeds straight to the Pallas page-move kernel.
    """

    depth: jax.Array  # i32[] in-flight entries after the tick
    enqueued: jax.Array  # i32[] new entries admitted this epoch
    drained_promote: jax.Array  # i32[] promotions committed this epoch
    drained_demote: jax.Array  # i32[] demotions committed this epoch
    cancelled: jax.Array  # i32[] thrash/ownership cancellations this epoch
    dropped: jax.Array  # i32[] overflow drops (queue full) this epoch
    drained_promote_ids: jax.Array  # i32[W] committed promote ids, -1 pad
    drained_demote_ids: jax.Array  # i32[W] committed demote ids, -1 pad


class PolicyState(NamedTuple):
    """The complete on-device policy-engine state threaded through epochs.

    Bundling pages + tenants + the un-sampled access backlog + the PRNG key
    into one pytree lets ``policy.epoch_step`` / ``policy.multi_epoch`` run
    the whole tick (sample -> bin -> FMMR -> realloc -> rebalance -> apply)
    as a single dispatch with donated buffers — no host round-trips.

    ``queue``/``epoch`` carry the asynchronous migration data plane: with a
    zero-capacity queue (the default) the tick applies migrations instantly
    and is bit-identical to the pre-data-plane engine; with ``queue_size >
    0`` selections are enqueued and committed by the bounded-bandwidth
    drain (DESIGN.md §4).
    """

    pages: "PageState"
    tenants: "TenantState"
    pending: jax.Array  # u32[P] accesses reported since the last epoch
    rng: jax.Array  # PRNG key for the PEBS-analogue subsampling
    queue: Optional["MigrationQueue"] = None  # None == zero-capacity queue
    epoch: Optional[jax.Array] = None  # i32[] epoch counter (queue clock)
    # Owner-sorted page permutation (None = derive reductions from a [T, P]
    # one-hot instead — the legacy path; states built by the manager carry
    # segments and take the cheaper gather/cumsum path, DESIGN.md §5).
    segs: Optional["OwnerSegments"] = None

    @classmethod
    def create(
        cls, num_pages: int, max_tenants: int, seed: int = 0, queue_size: int = 0
    ) -> "PolicyState":
        # pending stays u32: it accumulates UNSAMPLED access reports across
        # arbitrarily many control-plane calls between epochs — no policy
        # invariant bounds it below 2^16.
        assert max_tenants <= MAX_TENANT_SLOTS, (
            f"max_tenants {max_tenants} exceeds the int16 owner width "
            f"({MAX_TENANT_SLOTS}); widen PageState.owner to grow further"
        )
        return cls(
            pages=PageState.create(num_pages),
            tenants=TenantState.create(max_tenants),
            pending=jnp.zeros((num_pages,), jnp.uint32),
            rng=jax.random.PRNGKey(seed),
            queue=MigrationQueue.create(queue_size),
            epoch=jnp.int32(0),
        )


class MigrationPlan(NamedTuple):
    """Output of the policy step: bounded page-move lists.

    promote/demote: i32[R] page ids (padded with -1). Promotions move
    slow->fast, demotions fast->slow. len <= migration_budget by construction.
    """

    promote: jax.Array
    demote: jax.Array

    @property
    def num_promote(self) -> jax.Array:
        return (self.promote >= 0).sum()

    @property
    def num_demote(self) -> jax.Array:
        return (self.demote >= 0).sum()


class EpochStats(NamedTuple):
    """Telemetry emitted each epoch (per tenant unless noted).

    ``promoted``/``demoted`` count policy *selections*; with a migration
    queue the committed moves are in ``queue`` (``None`` in instant mode).
    """

    fmmr_now: jax.Array  # f32[T] instantaneous FMMR this epoch
    fmmr_ewma: jax.Array  # f32[T]
    fast_pages: jax.Array  # i32[T]
    slow_pages: jax.Array  # i32[T]
    promoted: jax.Array  # i32[T]
    demoted: jax.Array  # i32[T]
    cooled: jax.Array  # bool[T] cooling event fired
    queue: Optional["QueueStats"] = None  # data-plane telemetry (queue mode)
    # Invariant-sentinel bitmask (i32[], core/faults.py SENTINEL_*); zero
    # when green, and identically zero when params.sentinel == 0. None when
    # the checks were compiled out (compile_sentinel=False).
    sentinel: Optional[jax.Array] = None


def state_nbytes(tree) -> int:
    """Total array bytes of a pytree of device (or host) arrays.

    The packed-layout audit observable: ``PageState.owner`` at i16 and
    ``MigrationQueue.heat`` at i8 shrink this directly, and a stacked
    fleet state multiplies every per-page leaf by the machine axis — so
    the scale bench records it per (pages, tenants, machines) geometry.
    Python scalars in the tree count as zero (they occupy no array
    storage).
    """
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        total += int(jnp.size(leaf)) * jnp.dtype(dtype).itemsize
    return total
