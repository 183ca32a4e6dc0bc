"""Policy autotuner (repro.launch.hillclimb, DESIGN.md §9).

Locks the tuner's reproducibility contract — same seed => bit-identical
search trajectory AND winner; a mid-generation kill + resume reproduces the
uninterrupted run exactly (PR 6 sweep checkpoints underneath) — plus the
committed-profile round trip (every profile under ``src/repro/configs/
tuned/`` must rebuild a working manager whose traced params match the
profile bit-exactly), the new SweepPoint per-point knob plumbing, the
online hot-swap mechanics (no recompile, no host-RNG perturbation), and
the docs contract: ``docs/PARAMS.md`` documents every ``PolicyParams``
field and the offline search space only tunes documented fields.

It also holds the autotuner's two behavioural claims: each committed
smoke profile replayed against the paper defaults on its own family
weakly dominates them, and the online re-tuner recovers a shifted tenant
in fewer epochs than default params.

The search tests use the built-in ``skewshift`` family. The
``colocation`` and ``thrash`` replays build their scenarios from
``benchmarks/dynamic_workload.py``, importable from the repo root.
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from repro.configs.tuned import (
    load_profile,
    manager_kwargs,
    params_from_profile,
    profile_names,
)
from repro.core.manager import CentralManager
from repro.core.scenario import ScenarioSweep, SkewChange, SweepPoint, run_sweep
from repro.core.simulator import OPTANE, ColocationSim
from repro.core.types import PolicyParams
from repro.launch.hillclimb import (
    SEARCH_SPACE,
    OnlineTuner,
    PolicyAutotuner,
    TunerGeometry,
    default_candidate,
    family_scenario,
    ls_tenants,
    measure_history,
    recovery_epochs,
    resolve_knobs,
    skewshift_scenario,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GEOM = TunerGeometry(n_pages=512, n_epochs=12, fast=64, policy_chunk=4)


def _tuner(**kw):
    base = dict(population=4, generations=2, elites=1, seed=7)
    base.update(kw)
    return PolicyAutotuner("skewshift", GEOM, **base)


def _strip(traj):
    """Trajectory minus float-identity hazards — none expected, so keep all."""
    return [
        {
            "generation": t["generation"],
            "candidates": t["candidates"],
            "agg": t["agg"],
            "ls_p99": t["ls_p99"],
            "scores": t["scores"],
        }
        for t in traj
    ]


# ----------------------------------------------------------- reproducibility
def test_same_seed_same_trajectory_and_winner():
    r1 = _tuner().search()
    r2 = _tuner().search()
    assert not r1.interrupted and not r2.interrupted
    assert _strip(r1.trajectory) == _strip(r2.trajectory)
    assert r1.winner == r2.winner
    assert r1.ref == r2.ref
    # the default candidate is the floor: winner weakly dominates it
    assert r1.winner["agg"] >= r1.ref["agg"] * (1 - 1e-9)
    assert r1.winner["ls_p99"] <= r1.ref["ls_p99"] * (1 + 1e-9)


def test_different_seed_different_population():
    r1 = _tuner(seed=7).search()
    r2 = _tuner(seed=8).search()
    # generation 0 shares candidate 0 (the default) but the sampled rest
    # must differ
    assert r1.trajectory[0]["candidates"][0] == r2.trajectory[0]["candidates"][0]
    assert r1.trajectory[0]["candidates"][1:] != r2.trajectory[0]["candidates"][1:]


def test_kill_resume_reproduces_uninterrupted_run(tmp_path):
    ref = _tuner().search()

    out = str(tmp_path / "tuner")
    t1 = _tuner(out_dir=out, checkpoint_every=4)
    partial = t1.search(stop_after=5)  # killed inside generation 0
    assert partial.interrupted and partial.winner is None
    # the sweep checkpoint exists for generation 0
    assert os.path.isdir(os.path.join(out, "gen000"))

    t2 = _tuner(out_dir=out, checkpoint_every=4)
    resumed = t2.search(resume=True)
    assert not resumed.interrupted
    assert _strip(resumed.trajectory) == _strip(ref.trajectory)
    assert resumed.winner == ref.winner


def test_resume_state_mismatch_rejected(tmp_path):
    out = str(tmp_path / "tuner")
    # tuner state is written after each completed generation
    _tuner(out_dir=out, generations=1).search()
    with pytest.raises(ValueError, match="seed"):
        _tuner(out_dir=out, seed=8).search(resume=True)


# -------------------------------------------------------- committed profiles
def test_profiles_committed():
    # the replays below and the storm bench reference these names;
    # deleting one must be loud
    assert {"colocation_4k", "thrash_4k", "skewshift_4k",
            "storm_64k"} <= set(profile_names())


@pytest.mark.parametrize("name", profile_names())
def test_profile_roundtrip_one_epoch(name):
    prof = load_profile(name)
    params = PolicyParams.from_profile(name)
    # bit-exact round trip through the host meta encoding
    for field in PolicyParams._fields:
        want = prof["params"][field]
        got = getattr(params, field)
        if field == "fair_mode":
            assert got is bool(want)
        else:
            assert float(got) == pytest.approx(float(want), abs=0), field
    # the profile rebuilds a working manager at its tuned geometry...
    mgr = CentralManager(**manager_kwargs(name))
    for f in ("migration_budget", "sample_period", "ewma_lambda",
              "hysteresis", "num_bins", "alloc_headroom",
              "promote_band", "demote_band", "promote_admission",
              "demote_cooldown"):
        assert float(getattr(mgr.params, f)) == pytest.approx(
            float(prof["params"][f]), abs=0), f
    # ...that survives one real epoch
    h = mgr.register(t_miss=0.5)
    mgr.allocate(h, min(64, prof["geometry"]["n_pages"] // 4))
    mgr.run_epoch()
    # the claim the profile commits to: tuned weakly dominates default
    m = prof["metrics"]
    assert m["tuned"]["agg_throughput"] >= m["default"]["agg_throughput"] * (1 - 1e-9)
    assert m["tuned"]["ls_p99_us"] <= m["default"]["ls_p99_us"] * (1 + 1e-9)


def test_profile_loader_errors():
    with pytest.raises(KeyError, match="no tuned profile"):
        load_profile("no_such_profile")
    with pytest.raises(TypeError, match="unknown PolicyParams"):
        params_from_profile(profile_names()[0], not_a_field=1)


def test_profile_override():
    name = profile_names()[0]
    p = params_from_profile(name, sample_period=77)
    assert int(p.sample_period) == 77


def _geometry_from_profile(prof):
    g = prof["geometry"]
    return TunerGeometry(
        n_pages=int(g["n_pages"]),
        n_epochs=int(g["n_epochs"]),
        fast=int(g["fast_capacity"]),
        queue_size=int(g["queue_size"]),
        max_tenants=int(g["max_tenants"]),
        policy_chunk=int(g["policy_chunk"]),
    )


def _tuned_point(prof, name, seed):
    p = prof["params"]
    return SweepPoint(
        name,
        seed=seed,
        migration_budget=int(p["migration_budget"]),
        sample_period=int(p["sample_period"]),
        ewma_lambda=float(p["ewma_lambda"]),
        hysteresis=float(p["hysteresis"]),
        num_bins=int(p["num_bins"]),
        alloc_headroom=int(p["alloc_headroom"]),
    )


def tuned_vs_default(profile):
    """Replay one committed profile against the paper defaults: one
    two-point fleet sweep at the profile's own geometry, same seed, scored
    over the profile's window. Returns ``(default, tuned)``, each an
    ``(agg_throughput, ls_p99_s)`` pair."""
    prof = load_profile(profile)
    geom = _geometry_from_profile(prof)
    scenario = family_scenario(prof["family"], geom)
    seed = int(prof["search"].get("eval_seed", 0))
    default_kw = resolve_knobs(default_candidate(), geom)
    points = (
        SweepPoint("default", seed=seed, **default_kw),
        _tuned_point(prof, "tuned", seed),
    )
    res = run_sweep(
        ScenarioSweep(scenario=scenario, points=points),
        num_pages=geom.n_pages,
        fast_capacity=geom.fast,
        migration_budget=default_kw["migration_budget"],
        max_tenants=geom.max_tenants,
        queue_size=geom.queue_size,
        policy_chunk=geom.policy_chunk,
    )
    window = tuple(prof["search"]["scored_window"])
    ls = ls_tenants(scenario)
    return tuple(
        measure_history(res.results[k].history, window, ls)
        for k in ("default", "tuned")
    )


@pytest.mark.parametrize("family", ["colocation", "thrash", "skewshift"])
def test_committed_smoke_profile_replays(family):
    """The committed ``<family>_4k`` profile, replayed on its own family
    through the fleet sweep, weakly dominates the paper defaults: tuned
    aggregate throughput >= default AND tuned LS p99 <= default. The
    replay is deterministic, so equality passes."""
    profile = f"{family}_4k"
    assert load_profile(profile)["family"] == family
    (d_agg, d_p99), (t_agg, t_p99) = tuned_vs_default(profile)
    assert d_agg > 0 and d_p99 > 0
    assert t_agg >= d_agg, (profile, t_agg, d_agg)
    assert t_p99 <= d_p99, (profile, t_p99, d_p99)


# ------------------------------------------------------- SweepPoint plumbing
def test_manager_hysteresis_kwarg():
    mgr = CentralManager(num_pages=256, fast_capacity=64, migration_budget=8,
                         hysteresis=0.19)
    assert float(mgr.params.hysteresis) == pytest.approx(0.19)


def test_sweep_point_policy_knobs_take_effect():
    scenario = skewshift_scenario(512, 8)
    points = (
        SweepPoint("default", seed=0),
        SweepPoint("tuned", seed=0, ewma_lambda=0.9, hysteresis=0.0,
                   num_bins=9, sample_period=31, alloc_headroom=8),
    )
    res = run_sweep(
        ScenarioSweep(scenario=scenario, points=points),
        num_pages=512, fast_capacity=64, migration_budget=8,
        max_tenants=8, policy_chunk=4,
    )
    hist_d = res.results["default"].history
    hist_t = res.results["tuned"].history
    assert len(hist_d) == len(hist_t) == 8
    # the overridden point must actually behave differently
    agg_d = [sum(r.throughput.values()) for r in hist_d]
    agg_t = [sum(r.throughput.values()) for r in hist_t]
    assert agg_d != agg_t


# ------------------------------------------------------------ recovery metric
def _hist(values, tenant="kvs"):
    return [SimpleNamespace(throughput={tenant: v}) for v in values]


def test_recovery_epochs_dip_then_recover():
    # baseline 100; event at epoch 4; dip appears 2 epochs later (chunked
    # telemetry lag), recovers at epoch index 4 after the event
    h = _hist([100, 100, 100, 100, 100, 100, 40, 60, 100, 100])
    epochs, base = recovery_epochs(h, 4, tenant="kvs")
    assert base == pytest.approx(100.0)
    assert epochs == 4


def test_recovery_epochs_no_dip_is_instant():
    h = _hist([100.0] * 10)
    epochs, _ = recovery_epochs(h, 4, tenant="kvs")
    assert epochs == 0


def test_recovery_epochs_never_recovers():
    h = _hist([100, 100, 100, 100, 100, 10, 10, 10])
    epochs, _ = recovery_epochs(h, 4, tenant="kvs")
    assert epochs == 4  # the whole post-event window (epoch 4 inclusive)


# ------------------------------------------------------------------- online
def _online_sim(n_pages=512, fast=64):
    mgr = CentralManager(num_pages=n_pages, fast_capacity=fast,
                         migration_budget=fast // 2, max_tenants=8)
    mgr.params = mgr.params._replace(migration_budget=jnp.int32(8))
    return ColocationSim(mgr, OPTANE, seed=3, policy_chunk=2)


def test_online_retune_no_host_rng_perturbation():
    sim = _online_sim()
    scenario = skewshift_scenario(512, 6, shift_epoch=3)
    tuner = OnlineTuner(sim, seed=0, triggers=(SkewChange,))
    res = sim.run_scenario(scenario, on_event=tuner.on_event)
    assert len(res.history) == 6
    assert len(tuner.retunes) == 1  # two same-epoch SkewChanges coalesce
    assert tuner.retunes[0]["trigger"].startswith("kvs")  # the event's label
    # the reference leg without the tuner must be identical BEFORE the
    # shift epoch: the burst draws from its own stream
    ref = _online_sim().run_scenario(skewshift_scenario(512, 6, shift_epoch=3))
    for a, b in zip(ref.history[:3], res.history[:3]):
        assert a.throughput == b.throughput


def test_online_swap_is_in_plan_budget():
    sim = _online_sim()
    tuner = OnlineTuner(sim, seed=0)
    # drive a couple of epochs so tenants exist, then retune manually
    scenario = skewshift_scenario(512, 4, shift_epoch=2)
    sim.run_scenario(scenario, on_event=tuner.on_event)
    assert tuner.retunes, "Arrive/SkewChange triggers must have fired"
    plan = sim.backend.plan_size
    for r in tuner.retunes:
        assert 1 <= r["budget"] <= plan  # runtime budget capped by the buffer
    # hot-swap left a working manager behind
    sim.run_epoch()


def online_recovery(n_pages=2048, n_epochs=48, seed=0):
    """Default params against an :class:`OnlineTuner` watching SkewChange
    events on the skewshift probe. Returns the epochs each leg takes for
    the shifted ``kvs`` tenant to regain 95% of its pre-shift throughput,
    ``(default, online)``. Both legs share machine shapes (the plan buffer
    is sized fast/2, so the tuner can raise the budget without a retrace)
    and start from the same default traced params."""
    fast = n_pages // 8
    scenario = skewshift_scenario(n_pages, n_epochs)
    shift = n_epochs // 2

    def make_sim():
        mgr = CentralManager(num_pages=n_pages, fast_capacity=fast,
                             migration_budget=fast // 2, max_tenants=8)
        mgr.params = mgr.params._replace(
            migration_budget=jnp.int32(max(fast // 8, 8)))
        return ColocationSim(mgr, OPTANE, seed=seed, policy_chunk=2)

    res_d = make_sim().run_scenario(scenario)
    sim_o = make_sim()
    tuner = OnlineTuner(sim_o, seed=seed, triggers=(SkewChange,))
    res_o = sim_o.run_scenario(scenario, on_event=tuner.on_event)
    assert tuner.retunes, "the SkewChange must trigger a re-tune"
    rec_d, base_d = recovery_epochs(res_d.history, shift, tenant="kvs")
    rec_o, base_o = recovery_epochs(res_o.history, shift, tenant="kvs")
    # the burst draws from its own RNG stream: identical before the shift
    assert base_o == pytest.approx(base_d, rel=1e-6)
    return rec_d, rec_o


def test_online_retune_recovers_faster():
    """After a SkewChange the online re-tuner brings the shifted LS tenant
    back to 95% of its pre-shift throughput in fewer epochs than default
    params. The observable is that tenant's own throughput: the aggregate
    masks the dip, since a starved LS tenant frees bandwidth for the batch
    tenants."""
    rec_d, rec_o = online_recovery()
    assert rec_o < rec_d, (rec_o, rec_d)


# --------------------------------------------------------------------- docs
def test_params_md_documents_every_field():
    path = os.path.join(REPO, "docs", "PARAMS.md")
    assert os.path.exists(path), "docs/PARAMS.md is the tuning-surface contract"
    with open(path) as f:
        text = f.read()
    for field in PolicyParams._fields:
        assert f"`{field}`" in text, f"PARAMS.md must document {field!r}"


def test_search_space_only_tunes_documented_params():
    assert set(SEARCH_SPACE) <= set(PolicyParams._fields)
    for k, s in SEARCH_SPACE.items():
        assert s["lo"] <= s["default"] <= s["hi"], k
