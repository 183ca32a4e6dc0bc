"""Micro-benchmarks: wall time of the hot MaxMem primitives on this host.

(The CPU numbers are not TPU performance claims — they document the
policy-path costs, which are host-side even in deployment: one policy epoch
at production page counts must be << the epoch period.)
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Rows, platform_metadata
from repro.core import policy
from repro.core.manager import CentralManager
from repro.core.types import PageState, PolicyParams, TenantState, TIER_FAST, TIER_SLOW
from repro.kernels.ops import flash_attention, hot_bins, page_move, paged_attention

# Seed-commit (c35e7fc, lexsort ranks + W=4096 window) measurement of
# micro_policy_epoch_64k_pages on the reference CI host — the fixed baseline
# BENCH_policy.json tracks the counting-rank engine against across PRs.
SEED_POLICY_EPOCH_64K_US = 78321.0


def seed_policy_epoch_us(n_pages: int) -> float:
    """Seed-engine reference cost extrapolated to ``n_pages``.

    The seed commit was only measured at 64k pages; its lexsort-rank epoch
    was SUPERLINEAR in P (global sort dominated), so a linear-in-pages
    extrapolation is a conservative UNDERESTIMATE of what the seed would
    cost at larger sizes — every ``speedup_vs_seed`` beyond 64k is a floor,
    never inflated by the model.
    """
    return SEED_POLICY_EPOCH_64K_US * (n_pages / 65536.0)

_POLICY_BENCH_CACHE = None
_FLEET_BENCH_CACHE = None


def _time(fn, n=10, warmup=2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e6


def _time_wall(fn, n=3, warmup=1) -> float:
    """Wall time for host-side loops (already synchronous)."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def _time_wall_min(fn, n=3, warmup=1) -> float:
    """Min-of-reps wall time: the gating convention for noisy shared
    hosts (cf. vectorization_bench) — the minimum is the least polluted
    estimate of the code's actual cost, and far more stable than the mean
    for the smoke-scale legs the CI perf gate re-measures."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _time_min(fn, n=15, warmup=3) -> float:
    """Min-of-reps device timing: used where two programs are COMPARED on
    the same fresh run (the sentinel overhead band) — the minimum cancels
    shared-host noise that a mean folds into the ratio."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _policy_state(rng, P, T):
    pages = PageState.create(P)._replace(
        owner=jnp.asarray(rng.integers(0, T, P), jnp.int32),
        tier=jnp.asarray(np.where(rng.random(P) < 0.25, TIER_FAST, TIER_SLOW), jnp.int8),
    )
    tenants = TenantState.create(T)._replace(
        active=jnp.ones((T,), bool),
        t_miss=jnp.asarray(rng.uniform(0.05, 1.0, T), jnp.float32),
        arrival=jnp.arange(T, dtype=jnp.int32),
    )
    return pages, tenants


def _bench_manager(P, T, R, counts, k=16):
    """(singles_total_us, scan_total_us): k policy ticks through the
    CentralManager API — per-epoch record_access + run_epoch versus one
    fused run_epochs scan dispatch."""
    def mk():
        mgr = CentralManager(
            num_pages=P, fast_capacity=P // 4, migration_budget=R,
            max_tenants=T, sample_period=100,
        )
        for _ in range(T):
            h = mgr.register(t_miss=0.5)
            mgr.allocate(h, P // T)
        return mgr

    mgr_a = mk()

    def singles():
        for _ in range(k):
            mgr_a.record_access(counts)
            mgr_a.run_epoch()

    singles_us = _time_wall(singles)

    mgr_b = mk()

    def scan():
        mgr_b.run_epochs(k, counts=counts)

    scan_us = _time_wall(scan)
    return singles_us, scan_us


def policy_bench() -> dict:
    """Policy-engine timings for BENCH_policy.json (cached per process)."""
    global _POLICY_BENCH_CACHE
    if _POLICY_BENCH_CACHE is not None:
        return _POLICY_BENCH_CACHE
    rng = np.random.default_rng(0)
    T, R, k = 16, 2048, 16
    out = {
        "platform": platform_metadata(),
        "seed_reference": {
            "micro_policy_epoch_64k_pages_us": SEED_POLICY_EPOCH_64K_US,
            "commit": "c35e7fc (lexsort ranks, W=4096 victim window)",
            # speedup_vs_seed beyond 64k divides by this linear-in-pages
            # extrapolation (see seed_policy_epoch_us: the seed engine was
            # superlinear, so the reported speedups are floors)
            "extrapolation": "linear_in_pages",
        },
        "policy_epoch": {},
        "policy_epoch_queue": {},
        "policy_epoch_sentinel": {},
        "run_epochs_k16": {},
        "live_bytes": {},
    }
    for P in (65536, 262144):
        pages, tenants = _policy_state(rng, P, T)
        params = PolicyParams(
            fast_capacity=jnp.int32(P // 4), migration_budget=jnp.int32(R),
            sample_period=jnp.int32(100),
        )
        sampled = jnp.asarray(rng.poisson(2, P), jnp.uint32)
        n_rep = 10 if P <= 65536 else 5
        epoch_us = _time(lambda: policy.policy_epoch(
            pages, tenants, sampled, params, max_tenants=T, plan_size=R), n=n_rep)
        # every size carries speedup_vs_seed (the 256k row used to omit it,
        # which the perf gate's schema check now rejects); beyond 64k the
        # seed cost is the conservative linear extrapolation
        out["policy_epoch"][str(P)] = {
            "us": epoch_us,
            "epochs_per_sec": 1e6 / epoch_us,
            "speedup_vs_seed": seed_policy_epoch_us(P) / epoch_us,
        }

        # queue-mode (bounded data plane) overhead over the instant tick at
        # BOTH engine scales, on manager-grade states (owner segments
        # attached — every production queue state goes through
        # CentralManager and carries them), so the ratio isolates the data
        # plane itself
        from repro.core.types import OwnerSegments, PolicyState

        segs = OwnerSegments.build(np.asarray(pages.owner), T)
        pending = jnp.asarray(rng.poisson(200, P), jnp.uint32)
        istate = PolicyState.create(P, T)._replace(
            pages=pages, tenants=tenants, pending=pending, segs=segs,
        )
        qstate = PolicyState.create(P, T, queue_size=2 * R)._replace(
            pages=pages, tenants=tenants, pending=pending, segs=segs,
        )
        qparams = params._replace(migration_bandwidth=jnp.int32(R // 2))

        def instant_epoch():
            st, _plan, _stats = policy.epoch_step(
                istate, params, max_tenants=T, plan_size=R)
            return st.pages.tier

        def queue_epoch():
            st, _plan, _stats = policy.epoch_step(
                qstate, qparams, max_tenants=T, plan_size=R)
            return st.pages.tier

        i_us = _time(instant_epoch, n=n_rep)
        q_us = _time(queue_epoch, n=n_rep)
        out["policy_epoch_queue"][str(P)] = {
            "us": q_us,
            "instant_us": i_us,
            "overhead_vs_instant": q_us / i_us,
            "queue_size": 2 * R,
            "bandwidth": R // 2,
        }

        # live-bytes audit (packed-layout satellite): array bytes of the
        # solo instant/queue states and of a 4-machine stacked fleet state
        # — measured off the real pytrees (types.state_nbytes), so the i16
        # owner / i8 queue-heat packing shows up as data, not assertion
        from repro.core.fleet import FleetManager
        from repro.core.types import state_nbytes

        fleet4 = FleetManager(
            _fleet_managers(4, P, T, R), devices=1)
        out["live_bytes"][str(P)] = {
            "solo_instant": state_nbytes(istate),
            "solo_queue": state_nbytes(qstate),
            "fleet4_stacked": fleet4.live_bytes(),
            "fleet_machines": 4,
            "bytes_per_page_solo": state_nbytes(istate) / P,
        }
        del fleet4

        if P == 65536:
            # Sentinel overhead band (DESIGN.md §7). Three programs on the
            # SAME manager-grade state: the sentinel compiled OUT entirely
            # (the reference), the production program with the traced flag
            # OFF (what every non-chaos run executes — the perf gate bounds
            # this one's overhead vs the reference), and the flag ON (the
            # chaos-run cost, reported for the §7 cost table).
            on_params = params._replace(sentinel=jnp.int32(1))

            def sentinel_ref():
                st, _plan, _stats = policy.epoch_step(
                    istate, params, max_tenants=T, plan_size=R,
                    compile_sentinel=False)
                return st.pages.tier

            def sentinel_off():
                st, _plan, _stats = policy.epoch_step(
                    istate, params, max_tenants=T, plan_size=R)
                return st.pages.tier

            def sentinel_on():
                st, _plan, _stats = policy.epoch_step(
                    istate, on_params, max_tenants=T, plan_size=R)
                return st.pages.tier

            ref_us = _time_min(sentinel_ref)
            off_us = _time_min(sentinel_off)
            on_us = _time_min(sentinel_on)
            out["policy_epoch_sentinel"][str(P)] = {
                "ref_us": ref_us,  # sentinel compiled out
                "off_us": off_us,  # compiled in, traced flag off
                "on_us": on_us,  # compiled in, traced flag on
                "overhead_off": off_us / ref_us,
                "overhead_on": on_us / ref_us,
            }

        counts = rng.poisson(200, P).astype(np.int64)
        singles_us, scan_us = _bench_manager(P, T, R, counts, k=k)
        out["run_epochs_k16"][str(P)] = {
            "singles_total_us": singles_us,
            "scan_total_us": scan_us,
            "singles_per_epoch_us": singles_us / k,
            "scan_per_epoch_us": scan_us / k,
            "scan_epochs_per_sec": k * 1e6 / scan_us,
            "scan_speedup_vs_singles": singles_us / scan_us,
        }
    _POLICY_BENCH_CACHE = out
    return out


def _fleet_managers(n_machines, n_pages, max_tenants, budget):
    mgrs = []
    for seed in range(n_machines):
        m = CentralManager(
            num_pages=n_pages, fast_capacity=n_pages // 4,
            migration_budget=budget, max_tenants=max_tenants,
            sample_period=100, seed=seed,
        )
        for _ in range(max_tenants):
            h = m.register(t_miss=0.5)
            m.allocate(h, n_pages // max_tenants)
        mgrs.append(m)
    return mgrs


def fleet_bench(n_machines: int = 16, n_pages: int = 65536, n_epochs: int = 16) -> dict:
    """Engine-level fleet timings (cached per process per config).

    Four drivers over the SAME per-machine workload:

      * ``serial_singles`` — the pre-fleet sweep driver: for every machine,
        per-epoch ``record_access`` + ``run_epoch`` + a telemetry snapshot
        read (K x E dispatches and host syncs);
      * ``serial_scan``    — per-machine fused ``run_epochs`` (K dispatches,
        K snapshots);
      * ``fleet``          — ``FleetManager.run_epochs`` on ONE device: one
        vmapped scan dispatch and one stacked snapshot for all machines;
      * ``fleet_sharded``  — the same program with the machine axis
        partitioned over every visible XLA device (``devices`` records how
        many; identical to ``fleet`` on single-device hosts), telemetry
        trimmed to the sweep record fields and the stacked placement read
        through ``stacked_placement`` (the sweep pipeline's fetch path).

    Per-machine results of all four are bit-identical (tests/test_fleet.py,
    tests/test_fleet_sharded.py); only the dispatch/host-sync structure
    differs.
    """
    global _FLEET_BENCH_CACHE
    key = (n_machines, n_pages, n_epochs)
    if _FLEET_BENCH_CACHE is None:
        _FLEET_BENCH_CACHE = {}
    if key in _FLEET_BENCH_CACHE:
        return _FLEET_BENCH_CACHE[key]
    import jax

    from repro.core.fleet import FleetManager

    T = 16
    R = max(n_pages // 32, 8)
    rng = np.random.default_rng(0)
    counts = rng.poisson(200, (n_machines, n_pages)).astype(np.int64)

    # One manager set per driver, built OUTSIDE the timed closures: the
    # gated metric must measure the epoch hot path, not control-plane
    # setup. State advances across reps (steady workload) — the same
    # convention _bench_manager uses.
    singles_ms = _fleet_managers(n_machines, n_pages, T, R)
    scans_ms = _fleet_managers(n_machines, n_pages, T, R)
    fleet_f = FleetManager(_fleet_managers(n_machines, n_pages, T, R), devices=1)
    fleet_s = FleetManager(_fleet_managers(n_machines, n_pages, T, R))

    def singles():
        for i, m in enumerate(singles_ms):
            for _ in range(n_epochs):
                m.record_access(counts[i])
                m.run_epoch()
                m.tiers()  # the sweep driver reads placement every epoch

    def scans():
        for i, m in enumerate(scans_ms):
            m.run_epochs(n_epochs, counts=counts[i])
            m.tiers()

    def fleet():
        fleet_f.run_epochs(n_epochs, counts=counts)
        for m in fleet_f.machines:
            m.tiers()

    def fleet_sharded():
        fleet_s.run_epochs(n_epochs, counts=counts, trim_stats=True)
        fleet_s.stacked_placement()

    reps = 5 if n_pages <= 16384 else 2
    me = n_machines * n_epochs
    out = {"n_machines": n_machines, "n_pages": n_pages,
           "n_epochs": n_epochs, "max_tenants": T, "migration_budget": R,
           "devices": jax.local_device_count()}
    for name, fn in (("serial_singles", singles), ("serial_scan", scans),
                     ("fleet", fleet), ("fleet_sharded", fleet_sharded)):
        total = _time_wall_min(fn, n=reps, warmup=1)
        out[name] = {
            "total_us": total,
            "per_machine_epoch_us": total / me,
            "agg_epochs_per_sec": me * 1e6 / total,
        }
    out["fleet"]["speedup_vs_singles"] = (
        out["serial_singles"]["total_us"] / out["fleet"]["total_us"]
    )
    out["fleet"]["speedup_vs_scan"] = (
        out["serial_scan"]["total_us"] / out["fleet"]["total_us"]
    )
    out["fleet_sharded"]["devices"] = jax.local_device_count()
    out["fleet_sharded"]["speedup_vs_fleet"] = (
        out["fleet"]["total_us"] / out["fleet_sharded"]["total_us"]
    )
    _FLEET_BENCH_CACHE[key] = out
    return out


def run() -> Rows:
    rows = Rows()
    rng = np.random.default_rng(0)

    # policy engine at production scale: 64k pages (128 GB @ 2 MB), 16
    # tenants, plus the 256k-page and fused-scan variants
    pb = policy_bench()
    P, T, R = 65536, 16, 2048
    rows.add(
        "micro_policy_epoch_64k_pages", pb["policy_epoch"]["65536"]["us"],
        f"pages=65536;tenants={T};budget={R};"
        f"speedup_vs_seed={pb['policy_epoch']['65536']['speedup_vs_seed']:.2f}",
    )
    rows.add(
        "micro_policy_epoch_256k_pages", pb["policy_epoch"]["262144"]["us"],
        f"pages=262144;tenants={T};budget={R};"
        f"speedup_vs_seed={pb['policy_epoch']['262144']['speedup_vs_seed']:.2f}",
    )
    for p_key, label in (("65536", "64k"), ("262144", "256k")):
        lb = pb["live_bytes"][p_key]
        rows.add(
            f"micro_policy_live_bytes_{label}", 0.0,
            f"solo_instant={lb['solo_instant']};solo_queue={lb['solo_queue']};"
            f"fleet4_stacked={lb['fleet4_stacked']};"
            f"bytes_per_page={lb['bytes_per_page_solo']:.2f}",
        )
    for p_key, label in (("65536", "64k"), ("262144", "256k")):
        q = pb["policy_epoch_queue"][p_key]
        rows.add(
            f"micro_policy_epoch_{label}_queue_mode", q["us"],
            f"queue={q['queue_size']};bw={q['bandwidth']};"
            f"overhead_vs_instant={q['overhead_vs_instant']:.2f}",
        )
    sb = pb["policy_epoch_sentinel"]["65536"]
    rows.add(
        "micro_policy_epoch_64k_sentinel_off", sb["off_us"],
        f"ref_us={sb['ref_us']:.0f};on_us={sb['on_us']:.0f};"
        f"overhead_off={sb['overhead_off']:.3f};"
        f"overhead_on={sb['overhead_on']:.3f}",
    )
    for p_key, label in (("65536", "64k"), ("262144", "256k")):
        d = pb["run_epochs_k16"][p_key]
        rows.add(
            f"micro_policy_multi_epoch_k16_{label}_pages", d["scan_total_us"],
            f"per_epoch_us={d['scan_per_epoch_us']:.0f};"
            f"speedup_vs_singles={d['scan_speedup_vs_singles']:.2f}",
        )
        rows.add(
            f"micro_policy_single_epochs_k16_{label}_pages", d["singles_total_us"],
            f"per_epoch_us={d['singles_per_epoch_us']:.0f}",
        )

    # fleet engine: 16 machines x 64k pages, one vmapped scan dispatch
    fb = fleet_bench()
    rows.add(
        "micro_fleet_16x64k_per_machine_epoch", fb["fleet"]["per_machine_epoch_us"],
        f"agg_eps={fb['fleet']['agg_epochs_per_sec']:.1f};"
        f"speedup_vs_singles={fb['fleet']['speedup_vs_singles']:.2f};"
        f"speedup_vs_scan={fb['fleet']['speedup_vs_scan']:.2f}",
    )
    fs = fb["fleet_sharded"]
    rows.add(
        "micro_fleet_sharded_16x64k_per_machine_epoch",
        fs["per_machine_epoch_us"],
        f"devices={fs['devices']};agg_eps={fs['agg_epochs_per_sec']:.1f};"
        f"speedup_vs_fleet={fs['speedup_vs_fleet']:.2f}",
    )

    # hot_bins kernel (interpret mode)
    ids = jnp.asarray(rng.integers(0, 4096, 2048), jnp.int32)
    cin = jnp.zeros((4096,), jnp.int32)
    us = _time(lambda: hot_bins(ids, cin, tile=512))
    rows.add("micro_hot_bins_4k_pages_2k_samples", us, "tile=512")

    # page_copy kernel: 64 x 0.5 MB pages
    pool = jnp.asarray(rng.normal(size=(256, 1024, 128)), jnp.float32)
    sid = jnp.asarray(rng.choice(256, 64, replace=False), jnp.int32)
    did = jnp.asarray(rng.choice(256, 64, replace=False), jnp.int32)
    us = _time(lambda: page_move(jnp.copy(pool), sid, did), n=5)
    rows.add("micro_page_move_64x512KB", us, "bytes=" + str(64 * 131072 * 4))

    # flash attention kernel (interpret)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    us = _time(lambda: flash_attention(q, k, v, q_blk=128, kv_blk=128), n=5)
    rows.add("micro_flash_attn_512_interpret", us, "B1_h4_dh64")

    # latent paged attention kernel (interpret): 4 lanes, 16 heads, 640-lane rows
    pool = jax.random.normal(ks[1], (2, 64, 16, 640), jnp.float32)
    qd = jax.random.normal(ks[0], (4, 16, 640), jnp.float32)
    tables = jnp.asarray(rng.permutation(64)[:32].reshape(4, 8), jnp.int32)
    lens = jnp.asarray([128, 96, 64, 32], jnp.int32)
    new = jax.random.normal(ks[2], (4, 640), jnp.float32)
    us = _time(lambda: paged_attention(qd, pool, jnp.int32(1), tables, lens, new, sm_scale=0.07,
                                       v_width=512), n=5)
    rows.add("micro_paged_attn_interpret", us, "B4_pages8x16_latent640")
    return rows


if __name__ == "__main__":
    run().print()
