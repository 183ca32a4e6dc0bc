"""Plain reference of the MaxMem manager, written from the paper's policy
(sections 3.1 and 3.2) and the manager's documented contract, in numpy on the
host. It imports nothing of the program under test.

Per epoch, on the access samples reported since the last one:

1. fold the samples into each page's count, after lazy cooling (a count
   halves once per cooling event of its tenant since it was last touched; a
   tenant cools when a touched page of it reaches ``2**(num_bins-1)``);
2. FMMR: the share of a tenant's samples that hit slow pages, smoothed by an
   EWMA of weight ``ewma_lambda``;
3. reallocation with half the budget: tenants above ``t_miss (1 + band)``
   get fast pages in proportion to ``a_miss / t_miss``, tenants below
   ``t_miss (1 - band)`` give them up in proportion to ``t_miss / a_miss``
   (with the paper's zero-miss, first-come and equal-share rules);
4. rebalance with the other half: per tenant, swap its hottest slow page for
   its coldest fast page while that strictly raises the hot page's count
   over the cold one's, at most ``budget / (4 * active tenants)`` pairs;
5. the chosen pages (hottest by clamped count, ties to the lowest page id;
   coldest likewise) enter a FIFO migration queue; demotions of pages that
   re-heated are cancelled; each epoch drains at most ``bandwidth`` entries,
   demotions first, promotions only into free fast room; tiers change when
   an entry drains.

The float arithmetic of step 2 and 3 runs in ``ftype``: float32 as the
deployment states, or a lower precision for the control.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

TIER_NONE, TIER_SLOW, TIER_FAST = -1, 0, 1
DEMOTE, PROMOTE = -1, 1
INT_MAX = np.iinfo(np.int32).max
NO_ULPS = 99  # no rounding in the bracket gives the size
EPS = 1e-9


def _stable_argsort(x):
    return np.argsort(x, kind="stable")


class Manager:
    """Takes the manager's own keyword arguments, as a configuration's
    ``manager`` entry gives them; a knob it does not model is an error."""

    def __init__(self, num_pages: int, fast_capacity: int, max_tenants: int, migration_budget: int,
                 migration_bandwidth: int, queue_size: int, sample_period: int = 1,
                 num_bins: int = 6, ewma_lambda: float = 0.5, hysteresis: float = 0.08,
                 count_clamp: int = 4096, ftype=np.float32):
        if sample_period != 1:
            raise ValueError("the reference takes the access report as the sample stream (sample_period 1)")
        P, T = num_pages, max_tenants
        self.P, self.F, self.T = P, fast_capacity, T
        self.budget, self.bandwidth, self.Q = migration_budget, migration_bandwidth, queue_size
        self.plan_size = migration_budget
        self.num_bins, self.C = num_bins, count_clamp
        self.f = ftype
        self.lam = ftype(ewma_lambda)
        self.band = ftype(hysteresis)
        self.tier = np.full(P, TIER_NONE, np.int8)
        self.owner = np.full(P, -1, np.int64)
        self.count = np.zeros(P, np.uint32)
        self.last_cool = np.zeros(P, np.int64)
        self.pending = np.zeros(P, np.uint32)
        self.active = np.zeros(T, bool)
        self.t_miss = np.ones(T, ftype)
        self.a_miss = np.zeros(T, ftype)
        self.arrival = np.full(T, INT_MAX, np.int64)
        self.cool_epoch = np.zeros(T, np.int64)
        self.arrivals = 0
        # the FIFO queue, oldest first
        self.q_page = np.empty(0, np.int64)
        self.q_dir = np.empty(0, np.int64)
        self.q_heat = np.empty(0, np.int64)
        self.counters = dict(enqueued=0, drained=0, cancelled=0, dropped=0)
        self._members = None

    # ------------------------------------------------------------ tenants
    def register(self, t_miss: float) -> int:
        slot = int(np.flatnonzero(~self.active)[0])
        self.active[slot] = True
        self.t_miss[slot] = self.f(t_miss)
        self.a_miss[slot] = 0
        self.arrival[slot] = self.arrivals
        self.cool_epoch[slot] = 0
        self.arrivals += 1
        return slot

    def unregister(self, slot: int) -> None:
        owned = np.flatnonzero(self.owner == slot)
        if len(owned):
            self.free(slot, owned)
        self.active[slot] = False
        self.t_miss[slot] = 1
        self.a_miss[slot] = 0
        self.arrival[slot] = INT_MAX
        self.cool_epoch[slot] = 0

    def allocate(self, slot: int, n: int) -> np.ndarray:
        """First touch: the lowest free pages, fast while the fast tier has room."""
        free = np.flatnonzero(self.tier == TIER_NONE)
        if len(free) < n:
            raise MemoryError(f"{n} pages requested, {len(free)} free")
        take = free[:n]
        n_fast = min(max(self.F - int((self.tier == TIER_FAST).sum()), 0), n)
        self.tier[take[:n_fast]] = TIER_FAST
        self.tier[take[n_fast:]] = TIER_SLOW
        self.owner[take] = slot
        self._members = None
        return take

    def free(self, slot: int, ids) -> None:
        ids = np.asarray(ids, np.int64)
        if not np.all(self.owner[ids] == slot):
            raise PermissionError("tenant freeing pages it does not own")
        self.tier[ids] = TIER_NONE
        self.owner[ids] = -1
        self.count[ids] = 0
        self.last_cool[ids] = 0
        self.pending[ids] = 0
        gone = np.isin(self.q_page, ids)
        self.counters["cancelled"] += int(gone.sum())
        self._keep_queue(~gone)
        self._members = None

    def record(self, counts) -> None:
        self.pending = self.pending + np.asarray(counts, np.uint32)

    # ------------------------------------------------------------ helpers
    def _keep_queue(self, keep) -> None:
        self.q_page, self.q_dir, self.q_heat = self.q_page[keep], self.q_dir[keep], self.q_heat[keep]

    def members(self):
        """Each tenant's pages, in ascending page id."""
        if self._members is None:
            order = _stable_argsort(self.owner)
            owners = self.owner[order]
            self._members = [order[owners == t] for t in range(self.T)]
        return self._members

    def effective_count(self) -> np.ndarray:
        own = self.owner >= 0
        o = np.maximum(self.owner, 0)
        shift = np.clip(self.cool_epoch[o] - self.last_cool, 0, 31).astype(np.uint32)
        return np.where(own, self.count >> shift, 0).astype(np.uint32)

    def heat_bin(self, eff) -> np.ndarray:
        """0 for no accesses, else 1 + floor(log2(count)), at most num_bins - 1."""
        _, exp = np.frexp(eff.astype(np.float64))  # count = m * 2**exp, m in [0.5, 1)
        return np.minimum(np.where(eff > 0, exp, 0), self.num_bins - 1).astype(np.int64)

    # ------------------------------------------------------------ FMMR
    def _div(self, x, y, ulp: int = 0):
        """``x / y`` in ``ftype``, moved ``ulp`` ulps down (< 0) or up (> 0),
        as a divider that is not correctly rounded may return it."""
        q = np.asarray(np.divide(x, y)).astype(self.f)
        for _ in range(abs(ulp)):
            q = np.nextafter(q, np.float32(np.inf * ulp)).astype(self.f)
        return q

    def reallocate(self, fast_hold, free_fast, budget, ulp: int = 0):
        f = self.f
        div = lambda x, y: self._div(x, y, ulp)  # noqa: E731
        act, a, t = self.active, self.a_miss, self.t_miss
        R = f(budget)
        eps = f(EPS)
        need = act & (a > t * (f(1) + self.band))
        donor = act & (a < t * (f(1) - self.band)) & (fast_hold > 0)
        zero = donor & (a <= eps)
        ratio_d = np.where(donor & ~zero, div(t, np.maximum(a, eps)), f(0)).astype(f)
        if zero.any():
            take_frac = np.zeros(self.T, f)
            take_frac[int(np.argmin(np.where(zero, self.arrival, INT_MAX)))] = 1
        else:
            surplus = ratio_d.sum(dtype=f)
            take_frac = div(ratio_d, np.maximum(surplus, eps)) if surplus > 0 else np.zeros(self.T, f)
        take = np.minimum(np.floor(take_frac * R).astype(np.int64), fast_hold)
        take = np.where(act, take, 0)

        ratio_n = np.where(need, div(a, np.maximum(t, eps)), f(0)).astype(f)
        f_need = ratio_n.sum(dtype=f)
        if f_need > 0:
            want = np.floor(div(ratio_n, np.maximum(f_need, eps)) * R).astype(np.int64)
        else:
            want = np.zeros(self.T, np.int64)
        available = int(free_fast) + int(take.sum())
        give = _first_come(want, np.where(need, self.arrival, INT_MAX), available)
        give = np.where(act, give, 0)

        # take no more than is handed on, shaving the largest takes first
        excess = max(int(take.sum()) - max(int(give.sum()) - int(free_fast), 0), 0)
        order = _stable_argsort(-take)
        ts = take[order]
        cut = np.clip(excess - (np.cumsum(ts) - ts), 0, ts)
        take = np.zeros_like(take)
        take[order] = ts - cut

        # no needer: drift toward equal shares, a trickle of budget/8 an epoch
        if not need.any():
            n_act = max(int(act.sum()), 1)
            share = (int(fast_hold.sum()) + int(free_fast)) // n_act
            trickle = max(int(budget) // 8, 1)
            want_take = np.where(act & (a < f(0.7) * t), np.maximum(fast_hold - share, 0), 0)
            want_give = np.where(act, np.maximum(share - fast_hold, 0), 0)
            matched = f(min(min(int(want_take.sum()), int(want_give.sum()) + int(free_fast)), trickle))
            take = _scaled(want_take.astype(f), matched, f, div)
            give = _scaled(want_give.astype(f),
                           min(f(int(take.sum()) + int(free_fast)), f(trickle)), f, div)
        return give.astype(np.int64), take.astype(np.int64)

    def selection_sizes(self, fast_hold, n_slow_c, n_fast_c, half: int, ulp: int = 0):
        """(give, take): pages each tenant gains and yields by reallocation,
        rescaled into the half budget and capped by its candidates."""
        f = self.f
        free_fast = max(self.F - int(fast_hold.sum()), 0)
        give, take = self.reallocate(fast_hold, free_fast, half, ulp)
        moves = int(give.sum() + take.sum())
        scale = self._div(f(half), f(max(moves, 1)), ulp) if moves > half else f(1)
        take = np.floor(take.astype(f) * scale).astype(np.int64)
        give = np.floor(give.astype(f) * scale).astype(np.int64)
        give = _first_come(give, np.where(give > 0, self.arrival, INT_MAX), free_fast + int(take.sum()))
        return np.minimum(give, n_slow_c), np.minimum(take, n_fast_c)

    # ------------------------------------------------------------ epoch
    def epoch(self, forced: Optional[Dict[str, np.ndarray]] = None, ulps=(0, -1, 1)) -> dict:
        """One policy epoch. ``forced`` (per-tenant ``promoted``/``demoted``)
        replaces the reference's own selection sizes, so that it follows a run
        whose sizes differ by rounding; ``promoted_ulps``/``demoted_ulps`` give,
        per tenant, the fewest ulps by which its divisions have to move (the
        roundings in ``ulps``: 0 nearest, -k k ulps down, +k up) for its own
        size to equal the forced one, or ``NO_ULPS`` where none does;
        ``own_promoted``/``own_demoted`` are its own sizes, rounded to nearest."""
        f, T, C = self.f, self.T, self.C
        own = self.owner >= 0
        fast = self.tier == TIER_FAST
        slow = self.tier == TIER_SLOW
        sampled = self.pending

        # per owned page: 4 * owner + 2 * fast + in flight (a migration queued)
        inflight = np.zeros(self.P, bool)
        inflight[self.q_page] = True
        cls = (4 * self.owner + 2 * fast + inflight)[own]
        n_cls = np.bincount(cls, minlength=4 * T)[: 4 * T].reshape(T, 4).astype(np.int64)
        s_cls = np.bincount(cls, weights=sampled[own].astype(np.float64), minlength=4 * T)[: 4 * T]
        s_cls = s_cls.reshape(T, 4)

        # 1. per-tenant samples, then fold with lazy cooling
        s_fast, s_slow = s_cls[:, 2] + s_cls[:, 3], s_cls[:, 0] + s_cls[:, 1]
        o = np.maximum(self.owner, 0)
        eff = self.effective_count()
        new = eff + sampled
        touched = sampled > 0
        count = np.where(touched, new, self.count)
        last = np.where(touched, self.cool_epoch[o], self.last_cool)
        over = touched & own & (new >= np.uint32(1 << (self.num_bins - 1)))
        cooled = np.zeros(T, bool)
        cooled[np.unique(o[over])] = True
        self.cool_epoch = self.cool_epoch + cooled
        halve = cooled[o] & touched
        self.count = np.where(halve, count >> np.uint32(1), count).astype(np.uint32)
        self.last_cool = np.where(touched, self.cool_epoch[o], last)
        eff = self.effective_count()

        # 2. FMMR
        a_fast, a_slow = s_fast.astype(f), s_slow.astype(f)
        tot = (a_fast + a_slow).astype(f)
        now = np.where(tot > 0, a_slow / np.maximum(tot, f(1)), f(0)).astype(f)
        ewma = (self.lam * now + (f(1) - self.lam) * self.a_miss).astype(f)
        self.a_miss = np.where(self.active, ewma, f(0)).astype(f)

        # candidates: owned pages with no migration in flight
        slow_c = own & slow & ~inflight
        fast_c = own & fast & ~inflight
        key = np.minimum(eff.astype(np.int64), C - 1)
        fast_hold = n_cls[:, 2] + n_cls[:, 3]
        n_slow_c, n_fast_c = n_cls[:, 0], n_cls[:, 2]

        # 3. reallocation with half the budget, 4. rebalance pairs: the
        # selection sizes, once per rounding of the divisions (nearest first)
        mem = self.members()
        hot_keys = [(key[i], i) for i in (ids[slow_c[ids]] for ids in mem)]
        cold_keys = [(key[i], i) for i in (ids[fast_c[ids]] for ids in mem)]
        half = self.budget // 2
        cap = (self.budget - half) // (2 * max(int(self.active.sum()), 1))
        pairs = {}

        def rebalance(t: int, give: int, take: int) -> int:
            if (t, give, take) not in pairs:
                hot = _top(hot_keys[t][0], give + cap, largest=True)[give:]
                cold = _top(cold_keys[t][0], take + cap, largest=False)[take:]
                m = min(len(hot), len(cold), cap)
                better = hot[:m] > cold[:m]
                pairs[t, give, take] = m if better.all() else int(np.argmin(better))
            return pairs[t, give, take]

        sizes = []
        for ulp in ((0,) if forced is None else tuple(ulps)):
            give, take = self.selection_sizes(fast_hold, n_slow_c, n_fast_c, half, ulp)
            n_rebal = np.array([rebalance(t, int(give[t]), int(take[t]))
                                if self.active[t] and cap > 0 else 0 for t in range(T)], np.int64)
            sizes.append((np.minimum(give + n_rebal, n_slow_c), np.minimum(take + n_rebal, n_fast_c)))
        own_pro, own_dem = sizes[0]
        pro_n = own_pro if forced is None else np.minimum(forced["promoted"], n_slow_c)
        dem_n = own_dem if forced is None else np.minimum(forced["demoted"], n_fast_c)
        far = np.array([abs(u) for u in (ulps if forced is not None else (0,))])[:, None]
        pro_ulps = np.min(np.where([pro_n == p for p, _ in sizes], far, NO_ULPS), axis=0)
        dem_ulps = np.min(np.where([dem_n == d for _, d in sizes], far, NO_ULPS), axis=0)
        pro_ids = [_pick(hk, ids, int(n), largest=True) for (hk, ids), n in zip(hot_keys, pro_n)]
        dem_ids = [_pick(ck, ids, int(n), largest=False) for (ck, ids), n in zip(cold_keys, dem_n)]
        plan_pro = np.sort(np.concatenate(pro_ids))[: self.plan_size]
        plan_dem = np.sort(np.concatenate(dem_ids))[: self.plan_size]

        # queue: cancel re-heated demotions, enqueue, drain, commit
        reheat = (self.q_dir == DEMOTE) & (self.heat_bin(eff[self.q_page]) > self.q_heat)
        cancel = reheat | (self.owner[self.q_page] < 0)
        self._keep_queue(~cancel)
        queued = set(self.q_page.tolist())
        new_dem = np.array([p for p in plan_dem if p not in queued], np.int64)
        new_pro = np.array([p for p in plan_pro if p not in queued], np.int64)
        w_page = np.concatenate([self.q_page, new_dem, new_pro])
        w_dir = np.concatenate([self.q_dir, np.full(len(new_dem), DEMOTE), np.full(len(new_pro), PROMOTE)])
        w_heat = np.concatenate([self.q_heat, self.heat_bin(eff[new_dem]), self.heat_bin(eff[new_pro])])
        is_d = w_dir == DEMOTE
        drain_d = is_d & (np.cumsum(is_d) <= self.bandwidth)
        n_d = int(drain_d.sum())
        room = self.F - (int((self.tier == TIER_FAST).sum()) - n_d)
        is_p = w_dir == PROMOTE
        drain_p = is_p & (np.cumsum(is_p) <= min(self.bandwidth - n_d, room))
        self.tier[w_page[drain_d]] = TIER_SLOW
        self.tier[w_page[drain_p]] = TIER_FAST
        left = ~(drain_d | drain_p)
        n_drop = max(int(left.sum()) - self.Q, 0)
        keep = np.flatnonzero(left)[: self.Q]
        self.q_page, self.q_dir, self.q_heat = w_page[keep], w_dir[keep], w_heat[keep]
        self.pending = np.zeros(self.P, np.uint32)
        out = dict(
            promoted=pro_n.astype(np.int64), demoted=dem_n.astype(np.int64),
            promoted_ulps=pro_ulps, demoted_ulps=dem_ulps, own_promoted=own_pro, own_demoted=own_dem,
            fmmr=self.a_miss.astype(np.float64), fast_pages=fast_hold,
            queue=np.array([len(self.q_page), len(new_dem) + len(new_pro), int(drain_p.sum()),
                            n_d, int(cancel.sum()), n_drop], np.int64),
            drained_demote=w_page[drain_d], drained_promote=w_page[drain_p],
        )
        c = self.counters
        c["enqueued"] += len(new_dem) + len(new_pro)
        c["drained"] += n_d + int(drain_p.sum())
        c["cancelled"] += int(cancel.sum())
        c["dropped"] += n_drop
        return out

    def queue_counters(self) -> dict:
        return dict(self.counters, depth=len(self.q_page))


def _first_come(want, arrival_key, available: int) -> np.ndarray:
    """Serve ``want`` in order of ``arrival_key`` until ``available`` runs out."""
    order = _stable_argsort(arrival_key)
    ws = want[order]
    grant = np.clip(available - (np.cumsum(ws) - ws), 0, ws)
    out = np.zeros_like(want)
    out[order] = grant
    return out


def _scaled(want, cap, f, div):
    tot = max(want.sum(dtype=f), f(1))
    return np.floor(want * div(min(cap, tot), tot)).astype(np.int64)


def _top(keys, m: int, largest: bool) -> np.ndarray:
    """The ``m`` largest (or smallest) keys, in order."""
    m = min(m, len(keys))
    if m <= 0:
        return keys[:0]
    if largest:
        return -np.sort(-np.partition(keys, len(keys) - m)[len(keys) - m:])
    return np.sort(np.partition(keys, m - 1)[:m])


def _pick(keys, ids, n: int, largest: bool) -> np.ndarray:
    """Ids of the ``n`` hottest (or coldest) pages; equal keys go to the lowest id.
    ``ids`` ascend."""
    if n <= 0:
        return ids[:0]
    if n >= len(ids):
        return ids
    edge = _top(keys, n, largest)[-1]
    strict = keys > edge if largest else keys < edge
    tie = np.flatnonzero(keys == edge)[: n - int(strict.sum())]
    return np.sort(np.concatenate([ids[strict], ids[tie]]))
