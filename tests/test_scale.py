"""Million-page scale push tests (DESIGN.md §10).

Covers the three tentpole mechanisms of the scaling PR:

  * tiled integer cumsums (``core/tiling.py``) — bit-identical to the
    plain scan across the trace-selection threshold, on every axis and
    dtype the tick uses, and the whole fused epoch unchanged when the
    tiling heuristic flips;
  * packed state layouts (``core/types.py``) — dtype-width contracts for
    the i16 owner / i8 queue heat leaves and the ``MAX_TENANT_SLOTS``
    guard, plus the ``state_nbytes`` audit helper;
  * incremental ``OwnerSegments`` (``types.segments_update_host`` +
    the CentralManager delta wiring) — bit-identical to the from-scratch
    sort at T >= 256 under heavy register/allocate/free/unregister churn,
    with the permutation invariants checked after EVERY mutation.

Plus the geometry-parameterized ``scale_colocation`` scenario (its shape
and a manager-grade churn run through it) and the fleet ``live_bytes``
accounting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiling
from repro.core.manager import CentralManager
from repro.core.types import (
    MAX_TENANT_SLOTS,
    TIER_FAST,
    TIER_SLOW,
    MigrationQueue,
    OwnerSegments,
    PageState,
    PolicyParams,
    PolicyState,
    segments_build_host,
    segments_update_host,
    state_nbytes,
)


# ------------------------------------------------------------ tiled cumsum
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32])
@pytest.mark.parametrize(
    "n",
    [
        1,
        tiling.CUMSUM_BLOCK - 1,
        tiling.CUMSUM_BLOCK,
        tiling.CUMSUM_TILE_THRESHOLD,  # last untiled size
        tiling.CUMSUM_TILE_THRESHOLD + 1,  # first tiled size
        tiling.CUMSUM_TILE_THRESHOLD + tiling.CUMSUM_BLOCK // 2,  # ragged pad
        4 * tiling.CUMSUM_TILE_THRESHOLD + 17,
    ],
)
def test_tiled_cumsum_bit_identical_1d(dtype, n):
    rng = np.random.default_rng(n)
    lo = 0 if np.issubdtype(np.dtype(dtype), np.unsignedinteger) else -1000
    x = jnp.asarray(rng.integers(lo, 1000, n), dtype)
    got = tiling.tiled_cumsum(x)
    want = jnp.cumsum(x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiled_cumsum_bit_identical_2d_rows():
    # the [T, C] cutoff-table shape: cumsum along axis=1 with a long row
    rng = np.random.default_rng(0)
    n = tiling.CUMSUM_TILE_THRESHOLD + 3 * tiling.CUMSUM_BLOCK + 7
    x = jnp.asarray(rng.integers(-50, 50, (3, n)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(tiling.tiled_cumsum(x, axis=1)),
        np.asarray(jnp.cumsum(x, axis=1)),
    )
    # non-trailing scanned axis exercises the moveaxis path
    np.testing.assert_array_equal(
        np.asarray(tiling.tiled_cumsum(x.T, axis=0)),
        np.asarray(jnp.cumsum(x.T, axis=0)),
    )


def test_tiled_cumsum_float_falls_back_to_plain_scan():
    # float addition does not reassociate losslessly -> must NOT tile
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=tiling.CUMSUM_TILE_THRESHOLD + 5), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(tiling.tiled_cumsum(x)), np.asarray(jnp.cumsum(x))
    )


def make_scale_state(P: int, T: int, seed: int = 0) -> PolicyState:
    """A production-shaped solo policy state at geometry (P, T): every
    page owned, ~25% fast-resident, owner segments attached (every
    manager-grade state carries them) and a Poisson pending backlog."""
    rng = np.random.default_rng(seed)
    st = PolicyState.create(P, T)
    pages = st.pages._replace(
        owner=jnp.asarray(rng.integers(0, T, P), st.pages.owner.dtype),
        tier=jnp.asarray(
            np.where(rng.random(P) < 0.25, TIER_FAST, TIER_SLOW), jnp.int8),
    )
    tenants = st.tenants._replace(
        active=jnp.ones((T,), bool),
        t_miss=jnp.asarray(rng.uniform(0.05, 1.0, T), jnp.float32),
        arrival=jnp.arange(T, dtype=jnp.int32),
    )
    segs = OwnerSegments.build(np.asarray(pages.owner), T)
    pending = jnp.asarray(rng.poisson(200, P), jnp.uint32)
    return st._replace(pages=pages, tenants=tenants, pending=pending, segs=segs)


def test_full_epoch_identical_across_tiling_threshold():
    """The whole fused tick is bit-identical whichever trace the heuristic
    selects: run one epoch at a tiled size, then force the plain-scan trace
    by raising the threshold, and compare every output leaf."""
    from repro.core import policy

    P, T, R = tiling.CUMSUM_TILE_THRESHOLD + 8192, 64, 512
    st = make_scale_state(P, T, seed=7)
    params = PolicyParams(
        fast_capacity=jnp.int32(P // 4), migration_budget=jnp.int32(R),
        sample_period=jnp.int32(100),
    )

    def one_epoch():
        policy._jitted_epoch_step.cache_clear()  # drop the cached jit trace
        # the tick donates its state: hand each run its own copy
        s2, plan, stats = policy.epoch_step(
            jax.tree.map(jnp.copy, st), params, max_tenants=T, plan_size=R)
        return (
            np.asarray(s2.pages.tier), np.asarray(s2.pages.count),
            np.asarray(plan.promote), np.asarray(plan.demote),
            np.asarray(stats.fmmr_now), np.asarray(stats.fast_pages),
        )

    tiled = one_epoch()
    old = tiling.CUMSUM_TILE_THRESHOLD
    tiling.CUMSUM_TILE_THRESHOLD = P  # next trace keeps the plain scans
    try:
        plain = one_epoch()
    finally:
        tiling.CUMSUM_TILE_THRESHOLD = old
        policy._jitted_epoch_step.cache_clear()
    for a, b in zip(tiled, plain):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------- packed layouts
def test_packed_dtype_contracts():
    pages = PageState.create(64)
    assert pages.owner.dtype == jnp.int16  # MAX_TENANT_SLOTS fits i16
    assert pages.tier.dtype == jnp.int8
    assert pages.count.dtype == jnp.uint32  # NOT narrowable: see docstring
    q = MigrationQueue.create(32)
    assert q.heat.dtype == jnp.int8  # heat bins bounded by num_bins-1
    st = PolicyState.create(256, 16, queue_size=32)
    assert st.pages.owner.dtype == jnp.int16
    assert st.queue.heat.dtype == jnp.int8


def test_max_tenant_slots_guard():
    assert MAX_TENANT_SLOTS == 32767  # i16 positive range
    with pytest.raises(AssertionError):
        PolicyState.create(64, MAX_TENANT_SLOTS + 1)


def test_state_nbytes_counts_leaf_widths():
    st = PolicyState.create(1024, 8)
    n = state_nbytes(st)
    assert n == sum(
        int(np.size(leaf)) * np.dtype(leaf.dtype).itemsize
        for leaf in __import__("jax").tree_util.tree_leaves(st)
        if hasattr(leaf, "dtype")
    )
    # owner at i16 vs the old i32: the delta is exactly 2 bytes/page
    wide = st._replace(pages=st.pages._replace(
        owner=st.pages.owner.astype(jnp.int32)))
    assert state_nbytes(wide) - n == 2 * 1024


# ------------------------------------------------- incremental OwnerSegments
def _assert_segs_valid(order, inv, start, owner, T):
    P = len(owner)
    # permutation + inverse
    assert np.array_equal(np.sort(order), np.arange(P))
    assert np.array_equal(inv[order], np.arange(P))
    # start offsets: monotone, bracketed, consistent with per-tenant counts
    assert start[0] == 0 and len(start) == T + 1
    assert np.all(np.diff(start) >= 0)
    counts = np.bincount(owner[owner >= 0], minlength=T)
    assert np.array_equal(np.diff(start), counts)
    # segment contents: tenant t's window holds exactly its pages, id-sorted
    for t in np.unique(owner[owner >= 0]):
        seg = order[start[t]:start[t + 1]]
        assert np.array_equal(seg, np.flatnonzero(owner == t))
    # unowned tail id-sorted after the owned windows
    tail = order[start[T]:]
    assert np.array_equal(tail, np.flatnonzero(owner < 0))


def test_segments_update_bit_identical_high_tenant_churn():
    """T=320 with heavy mixed churn: every incremental splice must equal
    the from-scratch sort bit for bit, and the permutation invariants must
    hold after every mutation batch."""
    P, T = 8192, 320
    rng = np.random.default_rng(42)
    owner = rng.integers(-1, T, P).astype(np.int16)
    order, inv, start = segments_build_host(owner, T)
    _assert_segs_valid(order, inv, start, owner, T)
    for step in range(40):
        d = int(rng.integers(1, 400))
        changed = rng.choice(P, size=d, replace=False)
        new_owner = owner.copy()
        if step % 3 == 0:  # mass-free wave: pages -> unowned
            new_owner[changed] = -1
        elif step % 3 == 1:  # mass-register wave: one tenant absorbs all
            new_owner[changed] = int(rng.integers(0, T))
        else:  # scattered reassignment
            new_owner[changed] = rng.integers(-1, T, d)
        changed = changed[new_owner[changed] != owner[changed]]
        if changed.size == 0:
            continue
        order, inv, start = segments_update_host(
            order, inv, start, owner, new_owner, changed, T)
        owner = new_owner
        ref_order, ref_inv, ref_start = segments_build_host(owner, T)
        np.testing.assert_array_equal(order, ref_order)
        np.testing.assert_array_equal(inv, ref_inv)
        np.testing.assert_array_equal(start, ref_start)
        _assert_segs_valid(order, inv, start, owner, T)


def test_manager_incremental_segs_through_churn_t256():
    """CentralManager at T=256: interleaved register/allocate/run/free/
    unregister keeps the lazily patched segments identical to a full
    rebuild of the current owner array."""
    P, T = 4096, 256
    m = CentralManager(
        num_pages=P, fast_capacity=P // 4, migration_budget=64,
        max_tenants=T, sample_period=100, seed=0,
    )
    rng = np.random.default_rng(3)
    handles = []
    for _ in range(T // 2):  # initial cohort
        h = m.register(t_miss=0.5)
        m.allocate(h, int(rng.integers(4, 12)))
        handles.append(h)

    def check():
        m._ensure_segs()
        segs = m._state.segs
        assert segs is not None
        owner = np.asarray(m.pages.owner)
        ref = segments_build_host(owner, T)
        np.testing.assert_array_equal(np.asarray(segs.order), ref[0])
        np.testing.assert_array_equal(np.asarray(segs.inv), ref[1])
        np.testing.assert_array_equal(np.asarray(segs.start), ref[2])

    check()
    for step in range(24):
        op = step % 4
        if op == 0 and handles:  # partial free
            h = handles[int(rng.integers(0, len(handles)))]
            owned = np.flatnonzero(np.asarray(m.pages.owner) == int(h))
            if len(owned) > 1:
                m.free(h, owned[: len(owned) // 2])
        elif op == 1:  # depart
            if handles:
                m.unregister(handles.pop(int(rng.integers(0, len(handles)))))
        elif op == 2:  # arrive
            h = m.register(t_miss=float(rng.uniform(0.2, 1.0)))
            m.allocate(h, int(rng.integers(4, 12)))
            handles.append(h)
        else:  # epochs consume the segments on-device
            m.record_access(rng.poisson(3, P).astype(np.int64))
            m.run_epoch()
        check()


# ------------------------------------------------ scale colocation scenario
def test_scale_colocation_geometry():
    from repro.core.scenario import Arrive, Depart, scale_colocation

    sc = scale_colocation(65536, 16, 16)
    arrivals = [e for e in sc.events if isinstance(e, Arrive)]
    departs = [e for e in sc.events if isinstance(e, Depart)]
    assert len(arrivals) == 16 and len(departs) == 4  # churn=0.25
    # peak-concurrency footprints must fit the page pool with headroom
    assert sum(a.spec.n_pages for a in arrivals) <= 65536
    # churn cohort: arrives strictly inside the run, departs later
    churn_names = {d.name for d in departs}
    for a in arrivals:
        if a.spec.name in churn_names:
            assert 0 < a.epoch < min(d.epoch for d in departs)
    with pytest.raises(AssertionError):
        scale_colocation(64, 16, 16)  # geometry too thin


def test_scale_colocation_churn_run_completes():
    """A manager-grade run of the scenario: the churn cohort registers,
    allocates, runs and departs in batch waves, and the run completes all
    three phases with only the core tenants left registered."""
    from repro.core.scenario import Depart, scale_colocation
    from repro.core.simulator import OPTANE, ColocationSim

    P, T, n_epochs = 4096, 8, 8
    sc = scale_colocation(P, T, n_epochs)
    churn = {e.name for e in sc.events if isinstance(e, Depart)}
    depart_at = min(e.epoch for e in sc.events if isinstance(e, Depart))
    mgr = CentralManager(
        num_pages=P, fast_capacity=P // 4, migration_budget=P // 32,
        max_tenants=T, sample_period=100, seed=0,
    )
    res = ColocationSim(mgr, OPTANE, seed=1, policy_chunk=4).run_scenario(sc)
    assert len(res.phases) == 3
    assert len(res.history) == n_epochs
    assert res.steady_state.agg_throughput > 0
    assert churn & set(res.history[depart_at - 1].throughput)
    assert not churn & set(res.history[-1].throughput)
    assert int(np.asarray(mgr._state.tenants.active).sum()) == T - len(churn)


def test_fleet_live_bytes_scales_with_machines():
    from repro.core.fleet import FleetManager

    def mk(k):
        ms = []
        for seed in range(k):
            m = CentralManager(
                num_pages=1024, fast_capacity=256, migration_budget=32,
                max_tenants=8, seed=seed,
            )
            h = m.register(t_miss=0.5)
            m.allocate(h, 128)
            ms.append(m)
        return FleetManager(ms, devices=1)

    f1, f2 = mk(1), mk(2)
    b1, b2 = f1.live_bytes(), f2.live_bytes()
    assert b1 > 0 and b2 == 2 * b1  # per-page leaves stack along K
    # live_bytes is the stacked pytree's audit sum, not an estimate
    assert b1 == state_nbytes(f1._fstate)
