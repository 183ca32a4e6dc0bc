"""MaxMem central manager + tenant handles (paper §3.3 user-space design).

The manager owns all policy state (trust model: tenants cannot touch it) and
exposes the libMaxMem-analogue surface:

    mgr = CentralManager(num_pages=..., fast_capacity=..., ...)
    h = mgr.register(t_miss=0.1)          # process connects over the socket
    pages = mgr.allocate(h, n_pages)      # mmap/page-fault analogue
    mgr.record_access(counts)             # engine reports page accesses
    stats = mgr.run_epoch()               # policy thread tick
    res = mgr.run_epochs(k, counts)       # k ticks in ONE device dispatch
    mgr.set_target(h, 0.5)                # dynamic QoS update
    mgr.free(h, pages); mgr.unregister(h) # process exit

Allocation follows §3.1: fast first, slow if fast exhausted, error if both
exhausted. On tenant exit, memory returns to the free pool and is granted to
needers on the next epoch.

All hot-path state (pages, tenants, the un-sampled access backlog, the PRNG
key) lives on device in one ``PolicyState`` pytree: ``record_access`` folds
reports with a jitted add, ``run_epoch`` is one fused dispatch
(``policy.epoch_step``), and ``run_epochs`` scans k epochs in one dispatch
(``policy.multi_epoch``). Telemetry reads go through a cached host snapshot
so a burst of ``fast_pages_of``/``tier_of`` calls costs one transfer.
Control-plane operations (register/allocate/free) stay host-side — they are
rare and inherently serial.

Each host-side step sits in a ``jax.profiler.TraceAnnotation`` span named
``maxmem.<step>``, one per call, on the profiler's clock beside the device
ops: ``run_epoch`` (carrying ``epoch``, the manager's ``epoch_index``) and,
nested in it, ``segs`` (only when the owner segments need a rebuild),
``dispatch`` (the fused tick's call), ``fetch`` (each blocking device->host
read) and the pool's ``pool.execute``; ``record_access``; ``register``,
``unregister``, ``allocate`` and ``free``; ``snapshot`` (only on a miss of
the cached host snapshot); and the pool's ``pool.on_allocate``,
``pool.on_free`` and ``pool.write_pages`` (core/dataplane.py). A span costs
about a microsecond when no profiler runs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy
from repro.core.dataplane import PagePool
from repro.core.types import (
    BANDWIDTH_UNLIMITED,
    TIER_FAST,
    TIER_NONE,
    TIER_SLOW,
    EpochStats,
    MigrationPlan,
    OwnerSegments,
    PageState,
    PolicyParams,
    PolicyState,
    TenantState,
    segments_build_host,
    segments_update_host,
)


def _fetch(x) -> np.ndarray:
    """A blocking device->host read, in its own ``maxmem.fetch`` span."""
    with jax.profiler.TraceAnnotation("maxmem.fetch"):
        return np.asarray(x)


class TenantHandle(int):
    """Opaque tenant slot id (the libMaxMem connection analogue)."""


@jax.jit
def _fold_counts(pending: jax.Array, counts: jax.Array) -> jax.Array:
    return pending + counts


@dataclasses.dataclass
class EpochResult:
    stats: EpochStats
    plan: Optional[MigrationPlan]
    flags: np.ndarray  # bool[T] tenants that could not be served

    def fmmr(self, h: int) -> float:
        return float(self.stats.fmmr_ewma[h])

    @property
    def migrated_pages(self) -> int:
        """Pages actually MOVED this epoch: queue drains in data-plane mode
        (selections may still be in flight), plan selections otherwise."""
        q = self.stats.queue
        if q is not None:
            return int(q.drained_promote) + int(q.drained_demote)
        return int(self.plan.num_promote) + int(self.plan.num_demote)

    @property
    def queue_depth(self) -> int:
        q = self.stats.queue
        return 0 if q is None else int(q.depth)

    @property
    def queue_flow(self) -> Tuple[int, int, int]:
        """(enqueued, drained, cancelled) this epoch — the storm-health
        observables (scenario ``ResponsivenessStats``); zeros without a
        queue."""
        q = self.stats.queue
        if q is None:
            return (0, 0, 0)
        return (
            int(q.enqueued),
            int(q.drained_promote) + int(q.drained_demote),
            int(q.cancelled),
        )


@dataclasses.dataclass
class MultiEpochResult:
    """Stacked output of ``run_epochs``: every array has a leading k axis."""

    stats: EpochStats  # [k, T] leaves
    plans: Optional[MigrationPlan]  # [k, R] leaves, None if not collected
    flags: np.ndarray  # bool[k, T]

    def __len__(self) -> int:
        return self.flags.shape[0]

    def unstack(self) -> List[EpochResult]:
        k = len(self)
        return [
            EpochResult(
                stats=jax.tree.map(lambda a: a[i], self.stats),
                plan=None if self.plans is None else jax.tree.map(lambda a: a[i], self.plans),
                flags=self.flags[i],
            )
            for i in range(k)
        ]

    @property
    def migrated_per_epoch(self) -> np.ndarray:
        """i64[k] pages MOVED each epoch: drained commits in data-plane
        mode, otherwise the selections from the exact stats telemetry."""
        q = self.stats.queue
        if q is not None:
            return np.asarray(q.drained_promote, np.int64) + np.asarray(
                q.drained_demote, np.int64
            )
        moved = np.asarray(self.stats.promoted) + np.asarray(self.stats.demoted)
        return moved.sum(axis=1)

    @property
    def queue_depth_per_epoch(self) -> np.ndarray:
        q = self.stats.queue
        if q is None:
            return np.zeros(len(self), np.int64)
        return np.asarray(q.depth, np.int64)

    @property
    def queue_flow_per_epoch(self) -> np.ndarray:
        """i64[k, 3] (enqueued, drained, cancelled) per epoch; zeros
        without a queue (storm-health telemetry, scenario
        ``ResponsivenessStats``)."""
        q = self.stats.queue
        if q is None:
            return np.zeros((len(self), 3), np.int64)
        return np.stack(
            [
                np.asarray(q.enqueued, np.int64),
                np.asarray(q.drained_promote, np.int64)
                + np.asarray(q.drained_demote, np.int64),
                np.asarray(q.cancelled, np.int64),
            ],
            axis=1,
        )


class CentralManager:
    def __init__(
        self,
        num_pages: int,
        fast_capacity: int,
        migration_budget: int,
        max_tenants: int = 16,
        num_bins: int = 6,
        sample_period: int = 100,
        ewma_lambda: float = 0.5,
        fair_mode: bool = False,
        hysteresis: float = 0.08,
        seed: int = 0,
        exact_sampling: bool = False,
        queue_size: int = 0,
        migration_bandwidth: Optional[int] = None,
        migration_latency: int = 0,
        data_plane_elems: Optional[int] = None,
        sentinel: bool = False,
        alloc_headroom: int = 0,
        promote_band: float = -1.0,
        demote_band: float = -1.0,
        promote_admission: Optional[int] = None,
        demote_cooldown: int = 0,
    ):
        """``queue_size > 0`` enables the asynchronous migration data plane
        (DESIGN.md §4): selections are queued and committed by a bounded
        per-epoch drain of ``migration_bandwidth`` pages (None = unlimited)
        after ``migration_latency`` epochs in flight. The default
        ``queue_size=0`` is the instant-apply engine, bit-identical to the
        pre-data-plane behavior. ``data_plane_elems`` additionally backs
        every page with ``data_plane_elems`` elements of real content in a
        :class:`~repro.core.dataplane.PagePool`; drained migrations then
        move actual bytes through the Pallas page-move kernel.
        ``sentinel=True`` turns on the in-trace invariant sentinel
        (DESIGN.md §7): each epoch's stats carry a violation bitmask
        (``EpochStats.sentinel``, core/faults.py SENTINEL_*). The flag is a
        traced parameter — toggling it via :meth:`set_sentinel` never
        retraces. ``alloc_headroom`` reserves that many fast pages the
        policy never promotes into, so first-touch allocations of new pages
        can land fast (TPP-style allocation reserve, DESIGN.md §8); also
        traced.

        Storm guards (DESIGN.md §11, all default-off and traced):
        ``promote_band``/``demote_band`` give the FMMR needer/donor
        triggers separate hysteresis (negative = inherit the symmetric
        ``hysteresis``); ``promote_admission`` caps new enqueues per
        direction per epoch, tightening under cancel pressure
        (None = unlimited);
        ``demote_cooldown`` bars a reheat-cancelled demotion's page from
        re-selection for that many epochs."""
        assert fast_capacity <= num_pages
        if migration_bandwidth is not None and queue_size == 0:
            raise ValueError(
                "finite migration_bandwidth requires the queue data plane: "
                "pass queue_size > 0"
            )
        if (promote_admission is not None or demote_cooldown) and queue_size == 0:
            raise ValueError(
                "promote_admission / demote_cooldown act on the migration "
                "queue: pass queue_size > 0"
            )
        self.num_pages = num_pages
        self.max_tenants = max_tenants
        # fleet dirty-tracking (core/fleet.py): the policy state lives behind
        # a property; any setter marks the machine mutated, and a fleet
        # dispatch parks the advanced slice as a lazy thunk so clean
        # machines never materialize (or re-upload) per-machine arrays
        self._state_val = None
        self._state_thunk = None
        self._mutated = True
        self.params = PolicyParams(
            fast_capacity=jnp.int32(fast_capacity),
            migration_budget=jnp.int32(migration_budget),
            num_bins=jnp.int32(num_bins),
            ewma_lambda=jnp.float32(ewma_lambda),
            sample_period=jnp.int32(sample_period),
            fair_mode=fair_mode,
            hysteresis=jnp.float32(hysteresis),
            migration_bandwidth=jnp.int32(
                BANDWIDTH_UNLIMITED if migration_bandwidth is None
                else migration_bandwidth
            ),
            migration_latency=jnp.int32(migration_latency),
            sentinel=jnp.int32(1 if sentinel else 0),
            alloc_headroom=jnp.int32(alloc_headroom),
            promote_band=jnp.float32(promote_band),
            demote_band=jnp.float32(demote_band),
            promote_admission=jnp.int32(
                -1 if promote_admission is None else promote_admission
            ),
            demote_cooldown=jnp.int32(demote_cooldown),
        )
        self.plan_size = int(migration_budget)
        self.queue_size = int(queue_size)
        self._state = PolicyState.create(
            num_pages, max_tenants, seed=seed, queue_size=queue_size
        )
        # owner-sorted permutation for the tick's segment reductions
        # (DESIGN.md §5); ownership only changes here in the control plane,
        # so allocate/free mark it stale and the next tick rebuilds it.
        # The rebuild is incremental when the churn since the last build is
        # known (DESIGN.md §10): host numpy mirrors of the current segs
        # (`_segs_host`), the owner array they were built from
        # (`_segs_built_owner`), and the changed page ids since
        # (`_segs_delta`; None = unknown -> full rebuild). `_segs_ref`
        # guards staleness by identity: checkpoint restores and fleet
        # parking swap `_state.segs` wholesale, which invalidates the
        # mirrors without going through these helpers.
        self._segs_owner: Optional[np.ndarray] = None
        self._segs_host = None
        self._segs_built_owner: Optional[np.ndarray] = None
        self._segs_delta: Optional[list] = None
        self._segs_ref = None
        self._refresh_segs(np.full((num_pages,), -1, np.int32))
        self._arrival_seq = 0
        self.exact_sampling = exact_sampling
        self.epoch_index = 0
        self._snap: Optional[Dict[str, np.ndarray]] = None
        # cumulative queue counters (conservation invariant, tests):
        # enqueued == drained + cancelled + dropped + queue_depth()
        self.queue_enqueued = 0
        self.queue_drained = 0
        self.queue_cancelled = 0
        self.queue_dropped = 0
        # pages whose DMA move was abandoned by the fault injector and whose
        # tier flip was reverted (commit-on-completion fallback)
        self.migration_failures = 0
        self.pool: Optional[PagePool] = None
        if data_plane_elems is not None:
            self.pool = PagePool(
                num_pages, fast_capacity, row_elems=data_plane_elems,
                plan_slots=max(2 * self.plan_size, 8),
            )

    # --------------------------------------------------------- state views
    @property
    def _state(self) -> PolicyState:
        if self._state_thunk is not None:
            self._state_val = self._state_thunk()
            self._state_thunk = None
        return self._state_val

    @_state.setter
    def _state(self, value: PolicyState) -> None:
        self._state_val = value
        self._state_thunk = None
        self._mutated = True

    def _set_fleet_state(self, thunk) -> None:
        """Park the machine's advanced state as a lazy slice of the fleet's
        stacked pytree (core/fleet.py). The slice only materializes if a
        control-plane or telemetry path actually reads it; until a setter
        fires, the fleet knows this machine's row in its cached stack is
        current and skips the restack entirely."""
        self._state_val = None
        self._state_thunk = thunk
        self._mutated = False

    @property
    def pages(self) -> PageState:
        return self._state.pages

    @pages.setter
    def pages(self, value: PageState) -> None:
        self._state = self._state._replace(pages=value)
        self._snap = None
        # state.segs must mirror pages.owner (DESIGN.md §5): any path that
        # can change ownership — allocate/free or a client assigning the
        # documented state view directly — marks the permutation stale here
        self._refresh_segs(np.asarray(value.owner))

    def _set_pages_churn(self, value: PageState, changed_ids) -> None:
        """Pages setter for allocate/free, which KNOW which page ids they
        mutated: the recorded delta lets ``_ensure_segs`` patch the
        owner-sorted permutation instead of re-sorting the pool."""
        self._state = self._state._replace(pages=value)
        self._snap = None
        self._refresh_segs(np.asarray(value.owner), changed=changed_ids)

    @property
    def tenants(self) -> TenantState:
        return self._state.tenants

    @tenants.setter
    def tenants(self, value: TenantState) -> None:
        self._state = self._state._replace(tenants=value)

    def _refresh_segs(self, owner: np.ndarray, changed=None) -> None:
        """Note an ownership change; the owner-sorted permutation is
        rebuilt lazily before the next policy tick (``_ensure_segs``), so a
        burst of control-plane operations (scenario arrivals allocating a
        dozen tenants) pays ONE host rebuild instead of one per call.

        ``changed`` names the page ids the caller mutated; the lazy rebuild
        can then PATCH the previous permutation (types.segments_update_host
        — a windowed splice, ~20x cheaper than the argsort for localized
        churn) instead of re-sorting from scratch. ``changed=None`` (a
        wholesale state assignment) invalidates the delta and forces the
        full rebuild."""
        self._segs_owner = np.asarray(owner)
        if changed is None:
            self._segs_delta = None
        elif self._segs_delta is not None:
            self._segs_delta.append(np.asarray(changed, np.int64))

    def _ensure_segs(self) -> None:
        if self._segs_owner is None:
            return
        with jax.profiler.TraceAnnotation("maxmem.segs"):
            self._rebuild_segs()

    def _rebuild_segs(self) -> None:
        cur = self._segs_owner
        T = self.max_tenants
        host = None
        segs = self._state.segs
        # the incremental path needs mirrors that describe the CURRENT segs:
        # `_segs_ref` identity breaks when a checkpoint restore or fleet
        # park replaced _state.segs behind our back
        if (
            self._segs_delta is not None
            and self._segs_host is not None
            and self._segs_built_owner is not None
            and segs is not None
            and segs.order is self._segs_ref
        ):
            if self._segs_delta:
                ids = np.unique(np.concatenate(self._segs_delta))
            else:
                ids = np.empty((0,), np.int64)
            ids = ids[self._segs_built_owner[ids] != cur[ids]]
            if ids.size == 0:
                host = self._segs_host
            else:
                host = segments_update_host(
                    *self._segs_host, self._segs_built_owner, cur, ids, T
                )
        if host is None:
            host = segments_build_host(cur, T)
        if host is not self._segs_host:
            order, inv, start = host
            self._state = self._state._replace(
                segs=OwnerSegments(
                    order=jnp.asarray(order),
                    inv=jnp.asarray(inv),
                    start=jnp.asarray(start),
                )
            )
        self._segs_host = host
        self._segs_built_owner = cur
        self._segs_ref = self._state.segs.order
        self._segs_delta = []
        self._segs_owner = None

    def _snapshot(self) -> Dict[str, np.ndarray]:
        """Host copy of the page metadata; ONE batched transfer per epoch no
        matter how many telemetry reads follow."""
        if self._snap is None:
            with jax.profiler.TraceAnnotation("maxmem.snapshot"):
                tier, owner = jax.device_get((self._state.pages.tier, self._state.pages.owner))
            self._snap = {"tier": tier, "owner": owner}
        return self._snap

    # ------------------------------------------------------------- tenants
    @partial(jax.profiler.annotate_function, name="maxmem.register")
    def register(self, t_miss: float) -> TenantHandle:
        assert 0.0 < t_miss <= 1.0, "t_miss must be in (0, 1] (§3.1)"
        active = np.asarray(self.tenants.active)
        free = np.flatnonzero(~active)
        if len(free) == 0:
            raise RuntimeError("tenant table full")
        slot = int(free[0])
        t = self.tenants
        self.tenants = t._replace(
            active=t.active.at[slot].set(True),
            t_miss=t.t_miss.at[slot].set(t_miss),
            a_miss=t.a_miss.at[slot].set(0.0),
            arrival=t.arrival.at[slot].set(self._arrival_seq),
            cool_epoch=t.cool_epoch.at[slot].set(0),
            flagged=t.flagged.at[slot].set(False),
        )
        self._arrival_seq += 1
        return TenantHandle(slot)

    def set_target(self, h: TenantHandle, t_miss: float) -> None:
        assert 0.0 < t_miss <= 1.0
        self.tenants = self.tenants._replace(
            t_miss=self.tenants.t_miss.at[int(h)].set(t_miss)
        )

    @partial(jax.profiler.annotate_function, name="maxmem.unregister")
    def unregister(self, h: TenantHandle) -> None:
        owned = np.flatnonzero(self._snapshot()["owner"] == int(h))
        if len(owned):
            self.free(h, owned)
        # scrub the whole slot (not just active=False): stale a_miss/t_miss
        # was observable via fmmr_of until the next epoch, and a reused
        # handle inherited the departed tenant's cool_epoch pairing
        self.tenants = self.tenants.clear_slot(int(h))

    # ------------------------------------------------------------- memory
    @partial(jax.profiler.annotate_function, name="maxmem.allocate")
    def allocate(self, h: TenantHandle, n_pages: int) -> np.ndarray:
        """First-touch allocation: fast while available, then slow (§3.1)."""
        snap = self._snapshot()
        tier = snap["tier"]
        owner = snap["owner"]
        unalloc = np.flatnonzero(tier == TIER_NONE)
        if len(unalloc) < n_pages:
            raise MemoryError(
                f"tenant {int(h)}: out of tiered memory "
                f"({n_pages} requested, {len(unalloc)} free)"
            )
        fast_used = int((tier == TIER_FAST).sum())
        fast_room = max(int(self.params.fast_capacity) - fast_used, 0)
        take = unalloc[:n_pages]
        n_fast = min(fast_room, n_pages)
        new_tier = tier.copy()
        new_owner = owner.copy()
        new_tier[take[:n_fast]] = TIER_FAST
        new_tier[take[n_fast:]] = TIER_SLOW
        new_owner[take] = int(h)
        self._set_pages_churn(
            self.pages._replace(tier=jnp.asarray(new_tier), owner=jnp.asarray(new_owner)),
            take,
        )
        if self.pool is not None:
            self.pool.on_allocate(take, new_tier[take])
        return take

    @partial(jax.profiler.annotate_function, name="maxmem.free")
    def free(self, h: TenantHandle, page_ids: Sequence[int]) -> None:
        ids = np.asarray(page_ids, np.int32)
        snap = self._snapshot()
        owner = snap["owner"]
        if not np.all(owner[ids] == int(h)):
            raise PermissionError("tenant freeing pages it does not own")
        tier = snap["tier"].copy()
        owner = owner.copy()
        tier[ids] = TIER_NONE
        owner[ids] = -1
        count = np.asarray(self.pages.count).copy()
        count[ids] = 0
        # reset the cooling stamp too: a freed slot must not leak the previous
        # owner's cool_epoch, or a tenant that reuses it would see its counts
        # spuriously halved (stale last_cool > 0 vs a fresh tenant's epoch 0
        # is no halving, but a RE-registered slot restarts cool_epoch at 0
        # while a stale stamp could be arbitrarily high — keep them paired).
        last_cool = np.asarray(self.pages.last_cool).copy()
        last_cool[ids] = 0
        self._set_pages_churn(
            self.pages._replace(
                tier=jnp.asarray(tier),
                owner=jnp.asarray(owner),
                count=jnp.asarray(count),
                last_cool=jnp.asarray(last_cool),
            ),
            ids,
        )
        pending = np.asarray(self._state.pending).copy()
        pending[ids] = 0
        self._state = self._state._replace(pending=jnp.asarray(pending))
        # scrub queued migrations of the freed pages NOW (not at the next
        # epoch's ownership guard): the slots may be re-allocated before the
        # next tick and a stale entry would then migrate the new owner's page
        queue = self._state.queue
        if queue is not None and queue.size:
            qp = np.asarray(queue.page)
            qd = np.asarray(queue.direction)
            stale = (qp >= 0) & np.isin(qp, ids)
            if stale.any():
                # only REAL migrations count as cancelled here: a stale
                # cooldown tombstone (direction 0) was already counted when
                # its demotion was cancelled, and is simply scrubbed
                self.queue_cancelled += int((stale & (qd != 0)).sum())
                qp = qp.copy()
                qp[stale] = -1
                qd = qd.copy()
                qd[stale] = 0
                self._state = self._state._replace(
                    queue=queue._replace(
                        page=jnp.asarray(qp), direction=jnp.asarray(qd)
                    )
                )
        if self.pool is not None:
            self.pool.on_free(ids)

    # ------------------------------------------------------------- accesses
    @partial(jax.profiler.annotate_function, name="maxmem.record_access")
    def record_access(self, counts: np.ndarray) -> None:
        """Engine-side access report: exact per-page access counts since the
        last call (the instrumented attention/GUPS stream). Folded into the
        on-device backlog with a jitted add — no host-side accumulator."""
        c = jnp.asarray(np.asarray(counts).astype(np.uint32, copy=False))
        self._state = self._state._replace(
            pending=_fold_counts(self._state.pending, c)
        )

    # ------------------------------------------------------------- epoch
    def _fold_queue_stats(self, q) -> None:
        self.queue_enqueued += int(_fetch(q.enqueued).sum())
        self.queue_drained += int(
            _fetch(q.drained_promote).sum() + _fetch(q.drained_demote).sum()
        )
        self.queue_cancelled += int(_fetch(q.cancelled).sum())
        self.queue_dropped += int(_fetch(q.dropped).sum())

    def _pool_execute(self, dem_ids, pro_ids, failed_dem: set, failed_pro: set) -> None:
        """Run one drained batch through the pool, folding fault outcomes.

        Pages moved successfully drop out of the accumulated failed sets (a
        later retry superseded the earlier failure); freshly failed ids are
        added. With no injector attached this is exactly ``pool.execute``.
        """
        self.pool.execute(dem_ids, pro_ids)
        if self.pool.fault_injector is None:
            return
        fd, fp = self.pool.last_failed
        dem = np.asarray(dem_ids).ravel()
        pro = np.asarray(pro_ids).ravel()
        ok = set(dem[dem >= 0].tolist()) | set(pro[pro >= 0].tolist())
        ok -= set(fd.tolist()) | set(fp.tolist())
        failed_dem -= ok
        failed_pro -= ok
        failed_dem.update(fd.tolist())
        failed_pro.update(fp.tolist())

    def _revert_failed_moves(self, failed_dem: set, failed_pro: set) -> None:
        """Commit-on-completion fallback: a page whose DMA move was
        abandoned stays in its SOURCE tier — roll the policy's optimistic
        tier flip back so placements and frames never diverge. Degraded
        (the policy will re-select the page next epoch), never corrupt."""
        if not failed_dem and not failed_pro:
            return
        tier = np.asarray(self.pages.tier).copy()
        if failed_dem:
            tier[list(failed_dem)] = TIER_FAST
        if failed_pro:
            tier[list(failed_pro)] = TIER_SLOW
        # ownership is untouched, so the owner-sorted segments stay valid
        self._state = self._state._replace(
            pages=self.pages._replace(tier=jnp.asarray(tier))
        )
        self._snap = None
        self.migration_failures += len(failed_dem) + len(failed_pro)

    def run_epoch(self) -> EpochResult:
        """Policy-thread tick: sample -> policy -> migrate, one dispatch."""
        with jax.profiler.TraceAnnotation("maxmem.run_epoch", epoch=self.epoch_index):
            self._ensure_segs()
            with jax.profiler.TraceAnnotation("maxmem.dispatch"):
                self._state, plan, stats = policy.epoch_step(
                    self._state,
                    self.params,
                    max_tenants=self.max_tenants,
                    plan_size=self.plan_size,
                    exact_sampling=self.exact_sampling,
                )
            self.epoch_index += 1
            self._snap = None
            fd, fp = set(), set()
            if stats.queue is not None:
                self._fold_queue_stats(stats.queue)
                if self.pool is not None:
                    self._pool_execute(
                        _fetch(stats.queue.drained_demote_ids),
                        _fetch(stats.queue.drained_promote_ids),
                        fd, fp,
                    )
            elif self.pool is not None:
                self._pool_execute(_fetch(plan.demote), _fetch(plan.promote), fd, fp)
            self._revert_failed_moves(fd, fp)
            flags = _fetch(self._state.tenants.flagged)
        return EpochResult(stats=stats, plan=plan, flags=flags)

    def run_epochs(
        self,
        k: int,
        counts: Optional[np.ndarray] = None,
        collect_plans: bool = False,
    ) -> MultiEpochResult:
        """Run ``k`` policy epochs in ONE device dispatch (``lax.scan``).

        ``counts``: None (consume the recorded backlog, then idle), [P]
        (replayed every epoch — steady-state workload), or [k, P]. With the
        default ``collect_plans=False`` the per-epoch page-id lists are not
        materialized (the per-tenant promoted/demoted telemetry in ``stats``
        is still exact); pass True when a DMA driver needs the ids.
        """
        self._ensure_segs()
        c = None
        if counts is not None:
            c = jnp.asarray(np.asarray(counts).astype(np.uint32, copy=False))
        self._state, plans, stats, flagged = policy.multi_epoch(
            self._state,
            self.params,
            c,
            k=k,
            max_tenants=self.max_tenants,
            plan_size=self.plan_size,
            exact_sampling=self.exact_sampling,
            collect_plans=collect_plans or (self.pool is not None and not self.queue_size),
        )
        self.epoch_index += k
        self._snap = None
        # With faults injected, failed moves accumulate over the k-epoch host
        # loop and the tier flips are reverted ONCE at chunk end: the in-scan
        # trajectory is internally consistent (it committed optimistically),
        # and the chunk boundary is where placements and frames reconverge.
        fd, fp = set(), set()
        if stats.queue is not None:
            self._fold_queue_stats(stats.queue)
            if self.pool is not None:
                dem = np.asarray(stats.queue.drained_demote_ids)
                pro = np.asarray(stats.queue.drained_promote_ids)
                for i in range(k):
                    self._pool_execute(dem[i], pro[i], fd, fp)
        elif self.pool is not None:
            dem = np.asarray(plans.demote)
            pro = np.asarray(plans.promote)
            for i in range(k):
                self._pool_execute(dem[i], pro[i], fd, fp)
        self._revert_failed_moves(fd, fp)
        return MultiEpochResult(stats=stats, plans=plans, flags=np.asarray(flagged))

    # ------------------------------------------------------- data plane
    @property
    def migration_bounded(self) -> bool:
        """True when the data-plane queue actually paces migrations (a
        finite bandwidth is set). The simulator's DMA-stall model only
        applies to backends whose drain is NOT already paced."""
        return self.queue_size > 0 and int(self.params.migration_bandwidth) >= 0

    def set_migration_bandwidth(self, pages_per_epoch: Optional[int]) -> None:
        """Dynamically bound the migration drain (None = unlimited). The
        bandwidth is a traced policy parameter: no recompilation. An
        instant-apply manager (queue_size=0) has no drain to bound — a
        finite request there would be silently ignored while the same
        scenario event clamps the baselines, so it fails loudly instead."""
        if pages_per_epoch is not None and self.queue_size == 0:
            raise ValueError(
                "finite migration_bandwidth requires the queue data plane: "
                "construct CentralManager(queue_size > 0)"
            )
        self.params = self.params._replace(
            migration_bandwidth=jnp.int32(
                BANDWIDTH_UNLIMITED if pages_per_epoch is None else pages_per_epoch
            )
        )

    def set_migration_latency(self, epochs: int) -> None:
        self.params = self.params._replace(migration_latency=jnp.int32(epochs))

    # --------------------------------------------------- faults & sentinel
    def set_sentinel(self, on: bool) -> None:
        """Toggle the in-trace invariant sentinel (traced: no retrace)."""
        self.params = self.params._replace(sentinel=jnp.int32(1 if on else 0))

    def set_fault_injector(self, injector) -> None:
        """Attach a ``core.faults.FaultInjector`` to the page data plane
        (or detach with ``None``). Requires a pool — without real frames
        there is nothing whose move can fail."""
        if self.pool is None:
            raise ValueError(
                "data-plane fault injection requires a page pool: construct "
                "CentralManager(data_plane_elems=...)"
            )
        self.pool.set_fault_injector(injector)

    def poison_telemetry(self, kind: str = "tier") -> None:
        """Corrupt one cell of the policy state (the TelemetryCorrupt
        scenario event): ``"tier"`` unplaces the first owned page (its owner
        survives — an owned page with no tier), ``"nan"`` drops a NaN into
        an active tenant's FMMR EWMA. Both are exactly the corruptions the
        invariant sentinel exists to catch; tests assert it does."""
        snap = self._snapshot()
        if kind == "tier":
            owned = np.flatnonzero(snap["owner"] >= 0)
            if len(owned) == 0:
                raise RuntimeError("no owned pages to poison")
            tier = snap["tier"].copy()
            tier[owned[0]] = TIER_NONE
            self._state = self._state._replace(
                pages=self.pages._replace(tier=jnp.asarray(tier))
            )
            self._snap = None
        elif kind == "nan":
            act = np.flatnonzero(np.asarray(self.tenants.active))
            if len(act) == 0:
                raise RuntimeError("no active tenants to poison")
            self.tenants = self.tenants._replace(
                a_miss=self.tenants.a_miss.at[int(act[0])].set(jnp.nan)
            )
        else:
            raise ValueError(f"unknown poison kind: {kind!r}")

    def queue_depth(self) -> int:
        """In-flight migrations right now (0 when the queue is off).
        Counts REAL migrations only — cooldown tombstones (direction 0,
        ``demote_cooldown``) occupy slots without pending work and sit
        outside the conservation identity."""
        queue = self._state.queue
        if queue is None or not queue.size:
            return 0
        return int(
            ((np.asarray(queue.page) >= 0) & (np.asarray(queue.direction) != 0)).sum()
        )

    def queue_counters(self) -> Dict[str, int]:
        """Cumulative data-plane counters; conservation must always hold:
        enqueued == drained + cancelled + dropped + depth."""
        return {
            "enqueued": self.queue_enqueued,
            "drained": self.queue_drained,
            "cancelled": self.queue_cancelled,
            "dropped": self.queue_dropped,
            "depth": self.queue_depth(),
        }

    # ------------------------------------------------------------- telemetry
    def tiers(self) -> np.ndarray:
        """i8[P] tier of every page (cached host snapshot)."""
        return self._snapshot()["tier"]

    def owners(self) -> np.ndarray:
        """i32[P] owner of every page (cached host snapshot)."""
        return self._snapshot()["owner"]

    def fast_pages_of(self, h: TenantHandle) -> int:
        snap = self._snapshot()
        m = (snap["owner"] == int(h)) & (snap["tier"] == TIER_FAST)
        return int(m.sum())

    def tier_of(self, page_ids) -> np.ndarray:
        return self._snapshot()["tier"][np.asarray(page_ids)]

    def fmmr_of(self, h: TenantHandle) -> float:
        return float(self.tenants.a_miss[int(h)])
