"""Sharded fleet executor: device-partition parity, padding, dirty-tracking.

The sharded entry point's contract (DESIGN.md §6) extends the fleet's: the
machine axis may be partitioned over any number of XLA devices — with K
padded up to a shard multiple by inert machines — and every per-machine row
stays BIT-IDENTICAL to the single-device vmap fleet and to running each
machine alone. The suite runs at whatever device count the host exposes
(``jax.local_device_count()``); the CI ``device_count=4`` leg re-runs it
with real logical sharding via ``--xla_force_host_platform_device_count``.
The padding contract is exercised at every device count through the
``pad_to`` testing hook.

Dirty-tracking contract: a dispatch with no intervening control-plane
operation re-uploads ZERO machine state (no restack, no OwnerSegments
rebuild, no host->device transfer at all when the backlog path is used).
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from repro.core.fleet import FleetManager
from repro.core.manager import CentralManager

import golden_regen


def _mk_manager(seed, budget, queue_size=0, bandwidth=None, latency=0,
                num_pages=1024, fast=256, max_tenants=8, sample_period=100,
                exact_sampling=False):
    kw = dict(
        num_pages=num_pages, fast_capacity=fast, migration_budget=budget,
        max_tenants=max_tenants, sample_period=sample_period, seed=seed,
        queue_size=queue_size, migration_latency=latency,
        exact_sampling=exact_sampling,
    )
    if bandwidth is not None:
        kw["migration_bandwidth"] = bandwidth
    m = CentralManager(**kw)
    hs = []
    for t_miss, n in ((0.1, 300), (0.5, 300), (1.0, 200)):
        h = m.register(t_miss)
        m.allocate(h, n)
        hs.append(h)
    return m, hs


def _configs(queue=False, n=3):
    """n machines (deliberately NOT a multiple of common device counts)
    with heterogeneous traced knobs."""
    if queue:
        return [
            dict(seed=s, budget=32 + 16 * s, queue_size=128,
                 bandwidth=8 + 8 * s, latency=s % 2)
            for s in range(n)
        ]
    return [dict(seed=s, budget=32 + 16 * s) for s in range(n)]


def _assert_machine_equal(fleet_m: CentralManager, solo: CentralManager):
    np.testing.assert_array_equal(fleet_m.tiers(), solo.tiers())
    np.testing.assert_array_equal(fleet_m.owners(), solo.owners())
    np.testing.assert_array_equal(
        np.asarray(fleet_m.tenants.a_miss), np.asarray(solo.tenants.a_miss)
    )


class TestShardedParity:
    @pytest.mark.parametrize("queue", [False, True], ids=["instant", "queue"])
    def test_sharded_matches_vmap_and_solo(self, queue):
        """devices=all (sharded when >1, padded) == devices=1 (plain vmap)
        == solo run_epochs, bitwise, for a K no device count divides."""
        cfgs = _configs(queue)
        K, E, P = len(cfgs), 6, 1024
        rng = np.random.default_rng(0)
        counts = rng.poisson(4, (K, E, P)).astype(np.int64)

        sharded = FleetManager([_mk_manager(**c)[0] for c in cfgs],
                               devices=None, pad_to=4)
        vmapped = FleetManager([_mk_manager(**c)[0] for c in cfgs], devices=1)
        res_s = sharded.run_epochs(E, counts=counts, collect_plans=True)
        res_v = vmapped.run_epochs(E, counts=counts, collect_plans=True)

        assert res_s.num_machines == K  # padding rows are stripped
        for la, lb in zip(jax.tree.leaves(res_s.stats), jax.tree.leaves(res_v.stats)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        np.testing.assert_array_equal(res_s.flags, res_v.flags)
        for la, lb in zip(jax.tree.leaves(res_s.plans), jax.tree.leaves(res_v.plans)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        for m, c in enumerate(cfgs):
            solo, _ = _mk_manager(**c)
            solo.run_epochs(E, counts=counts[m], collect_plans=True)
            _assert_machine_equal(sharded.machines[m], solo)
            _assert_machine_equal(vmapped.machines[m], solo)
            if queue:
                assert sharded.machines[m].queue_counters() == solo.queue_counters()

    def test_padding_machines_stay_inert_across_churn(self):
        """Two dispatches with mid-sweep churn between them: the inert pad
        rows must never bleed into real machines' results."""
        cfgs = _configs(queue=True)
        K, E, P = len(cfgs), 4, 1024
        rng = np.random.default_rng(2)
        c1 = rng.poisson(4, (K, E, P)).astype(np.int64)
        c2 = rng.poisson(6, (K, E, P)).astype(np.int64)

        fleet_ms, fleet_hs = zip(*[_mk_manager(**c) for c in cfgs])
        solo_ms, solo_hs = zip(*[_mk_manager(**c) for c in cfgs])
        fleet = FleetManager(list(fleet_ms), devices=None, pad_to=4)
        assert fleet.num_padded % 4 == 0 and fleet.num_padded > K

        def churn(m, hs):
            owned = np.flatnonzero(np.asarray(m.owners()) == int(hs[1]))
            m.free(hs[1], owned)
            m.unregister(hs[1])
            h = m.register(0.3)
            m.allocate(h, 64)

        fleet.run_epochs(E, counts=c1)
        for m, hs in zip(fleet_ms, fleet_hs):
            churn(m, hs)
        fleet.run_epochs(E, counts=c2)

        for i, (m, hs) in enumerate(zip(solo_ms, solo_hs)):
            m.run_epochs(E, counts=c1[i])
            churn(m, hs)
            m.run_epochs(E, counts=c2[i])
            _assert_machine_equal(fleet_ms[i], m)
            qc = fleet_ms[i].queue_counters()
            assert qc["enqueued"] == (
                qc["drained"] + qc["cancelled"] + qc["dropped"] + qc["depth"]
            )

    def test_device_count_validation(self):
        ms = [_mk_manager(**c)[0] for c in _configs()]
        with pytest.raises(AssertionError):
            FleetManager(ms, devices=jax.local_device_count() + 1)


class TestDirtyTracking:
    def test_noop_dispatch_zero_state_uploads(self):
        """A dispatch with no intervening control-plane op must reuse the
        cached stacked state: zero machines restacked, zero OwnerSegments
        rebuilds — and, on the backlog path, zero host->device transfers
        at all (locked with jax's transfer guard)."""
        fleet = FleetManager([_mk_manager(**c)[0] for c in _configs()])
        P = fleet.num_pages
        counts = np.random.default_rng(0).poisson(
            4, (len(fleet), P)).astype(np.int64)
        fleet.run_epochs(2, counts=counts)
        fleet.run_epochs(2)  # warm the counts=None trace
        before = dict(fleet.upload_stats)
        # global (not context-manager) guard: the dispatch and its uploads
        # run on the fleet's worker thread, which a thread-local guard
        # would not observe
        jax.config.update("jax_transfer_guard_host_to_device", "disallow")
        try:
            fleet.run_epochs(2)
        finally:
            jax.config.update("jax_transfer_guard_host_to_device", "allow")
        after = fleet.upload_stats
        assert after["restacked_machines"] == before["restacked_machines"]
        assert after["seg_rebuilds"] == before["seg_rebuilds"]
        assert after["clean_dispatches"] == before["clean_dispatches"] + 1

    def test_control_plane_op_restacks_only_touched_machine(self):
        fleet = FleetManager([_mk_manager(**c)[0] for c in _configs()])
        counts = np.random.default_rng(1).poisson(
            4, (len(fleet), fleet.num_pages)).astype(np.int64)
        fleet.run_epochs(2, counts=counts)
        h = fleet.machines[1].register(0.4)
        fleet.machines[1].allocate(h, 32)
        before = dict(fleet.upload_stats)
        fleet.run_epochs(2, counts=counts)
        after = fleet.upload_stats
        assert after["restacked_machines"] == before["restacked_machines"] + 1
        assert after["seg_rebuilds"] == before["seg_rebuilds"] + 1

    def test_params_only_change_skips_state_restack(self):
        """set_migration_bandwidth swaps a traced parameter: the params
        leaves restack, the O(P) state arrays must not."""
        fleet = FleetManager(
            [_mk_manager(**c)[0] for c in _configs(queue=True)])
        counts = np.random.default_rng(2).poisson(
            4, (len(fleet), fleet.num_pages)).astype(np.int64)
        fleet.run_epochs(2, counts=counts)
        fleet.machines[0].set_migration_bandwidth(4)
        before = dict(fleet.upload_stats)
        fleet.run_epochs(2, counts=counts)
        after = fleet.upload_stats
        assert after["restacked_machines"] == before["restacked_machines"]

    def test_dirty_results_still_exact(self):
        """Dirty-tracking is an optimization, not a semantic: interleaved
        control-plane ops + dispatches equal the solo sequence bitwise."""
        cfgs = _configs(queue=True)
        fleet_ms = [_mk_manager(**c)[0] for c in cfgs]
        solo_ms = [_mk_manager(**c)[0] for c in cfgs]
        fleet = FleetManager(list(fleet_ms))
        rng = np.random.default_rng(3)
        counts = rng.poisson(4, (3, len(cfgs), 4, fleet.num_pages)).astype(np.int64)
        for burst in range(3):
            if burst == 1:
                for m in (fleet_ms[0], solo_ms[0]):
                    m.set_migration_bandwidth(6)
            if burst == 2:
                for m in (fleet_ms[2], solo_ms[2]):
                    h = m.register(0.2)
                    m.allocate(h, 40)
            fleet.run_epochs(4, counts=counts[burst])
            for i, m in enumerate(solo_ms):
                m.run_epochs(4, counts=counts[burst][i])
        for fm, sm in zip(fleet_ms, solo_ms):
            _assert_machine_equal(fm, sm)
            assert fm.queue_counters() == sm.queue_counters()


class TestShardedGolden:
    @pytest.mark.parametrize("devices", ["all", "one"])
    def test_sharded_fleet_replays_golden_trace(self, devices):
        """The committed fleet golden (generated by the PR 4 vmap fleet)
        must replay bit-for-bit through the sharded executor — K=3 machines
        pad to the device multiple on multi-device hosts."""
        with open(golden_regen.FLEET_TRACE_PATH) as f:
            committed = json.load(f)
        dev = None if devices == "all" else 1
        pad = 4 if devices == "all" else None
        fleet = FleetManager(
            [m for m in golden_regen.make_fleet().machines], devices=dev,
            pad_to=pad,
        )
        counts = golden_regen.policy_counts()
        res = fleet.run_epochs(
            golden_regen.POLICY_EPOCHS,
            counts=np.broadcast_to(counts, (len(fleet),) + counts.shape),
            collect_plans=True,
        )
        for m, machine in enumerate(committed["machines"]):
            records = res.machine(m).unstack()
            tier = fleet.machines[m].tiers()
            for e, want in enumerate(machine["epochs"]):
                got = golden_regen.epoch_record(records[e], tier)
                for key in want:
                    if key == "tier" and e < golden_regen.POLICY_EPOCHS - 1:
                        continue
                    assert want[key] == got[key], (m, e, key)


class TestPipelinedSweep:
    @pytest.mark.parametrize("queue", [False, True], ids=["instant", "queue"])
    def test_pipelined_sweep_matches_serial_and_unpipelined(self, queue):
        """run_sweep(pipeline=True, sharded) == run_sweep(pipeline=False,
        devices=1) == per-machine chunked scenario runs, record for record,
        across mid-sweep churn (arrive/depart/resize) and — in queue mode —
        a bandwidth event landing mid-sweep."""
        from repro.core.scenario import (
            Arrive, Depart, ResizeWorkingSet, Scenario, ScenarioSweep,
            SetMigrationBandwidth, SweepPoint, run_sweep,
        )
        from repro.core.simulator import OPTANE, ColocationSim, WorkloadSpec

        chunk = 4
        events = [
            Arrive(0, WorkloadSpec("kvs", n_pages=380, t_miss=0.2, threads=4,
                                   sets=((0.2, 0.9),))),
            Arrive(0, WorkloadSpec("gap", n_pages=260, t_miss=0.5, threads=8,
                                   sets=((0.2, 0.7),))),
            Arrive(4, WorkloadSpec("gups", n_pages=160, t_miss=1.0, threads=8)),
            ResizeWorkingSet(8, "kvs", 0, 0.3),
            Depart(12, "gups"),
        ]
        if queue:
            events.append(SetMigrationBandwidth(8, 8))
        sc = Scenario(name="sharded_sweep_parity", n_epochs=16,
                      events=tuple(events))
        points = tuple(
            SweepPoint(name=f"m{i}", seed=i, migration_budget=24 + 8 * i)
            for i in range(3)
        )
        kw = dict(
            num_pages=1024, fast_capacity=256, migration_budget=32,
            max_tenants=8, policy_chunk=chunk,
            queue_size=64 if queue else 0,
        )
        piped = run_sweep(ScenarioSweep(scenario=sc, points=points), **kw)
        plain = run_sweep(ScenarioSweep(scenario=sc, points=points),
                          devices=1, pipeline=False, trim_stats=False, **kw)
        assert piped.pipeline and not plain.pipeline
        assert piped.devices >= 1 and plain.devices == 1
        assert set(piped.results) == set(plain.results) == {p.name for p in points}
        for p in points:
            mgr_kw = dict(
                num_pages=1024, fast_capacity=256,
                migration_budget=p.migration_budget, max_tenants=8,
                sample_period=100, seed=p.seed,
            )
            if queue:
                mgr_kw["queue_size"] = 64
            mgr = CentralManager(**mgr_kw)
            sim = ColocationSim(mgr, OPTANE, seed=p.seed, policy_chunk=chunk)
            want = sim.run_scenario(sc)
            for got in (piped.results[p.name], plain.results[p.name]):
                assert len(got.history) == len(want.history)
                for rg, rw in zip(got.history, want.history):
                    assert rg.throughput == rw.throughput
                    assert rg.fmmr_true == rw.fmmr_true
                    assert rg.fast_pages == rw.fast_pages
                    assert rg.migrated_pages == rw.migrated_pages
                    assert rg.queue_depth == rw.queue_depth
                for pg, pw in zip(got.phases, want.phases):
                    assert pg.label == pw.label
                    assert pg.agg_throughput == pw.agg_throughput
                    assert pg.migration_bytes == pw.migration_bytes
