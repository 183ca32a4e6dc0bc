"""Adversarial storm robustness benchmark.

Runs the storm grid (DESIGN.md §11): every storm family in
``repro.core.scenario.STORM_FAMILIES`` plus the composite adversarial
scenario, each on all four policies with the conservation invariants
checked after every event, and a fifth MaxMem-with-guards leg per family
(hysteresis bands + queue admission + demote cooldown).

The storm geometry deliberately oversubscribes the data plane: queue of
16 slots draining 4 pages/epoch under a selector allowed 64 selections
per epoch. Default MaxMem answers every phase flip with an enqueue storm
— 30-40 enqueues/epoch of which ~90% overflow the FIFO, are dropped, and
are re-selected the next epoch (the drop-requeue cycle). Committed
migrations are unaffected (drain order is FIFO either way), so the
throughput timeline HIDES the storm; the flow counters expose it.

Claims (``main`` exits non-zero when any fails):

1. ``recovery_strict_every_family`` — guarded worst-case churn recovery
   (:func:`repro.core.scenario.churn_recovery_epochs`, epochs after each
   adversarial event until the enqueue/drain balance goes non-positive)
   is STRICTLY fewer epochs than default on every family. Default
   saturates (the storm never subsides); guarded recovers within ~one
   flip period.
2. ``steady_state_within_tol`` — guarded steady-state aggregate
   throughput within 2% of default on every family (measured: equal or
   better — the admitted selections are the hottest candidates, so the
   committed work is at least as useful).
3. ``cancel_ratio_bounded`` — cancelled/drained <= 0.25 on both MaxMem
   legs of every family and guarded drains > 0 (no livelock: the guard
   stack never trades the drop storm for a cancel storm).

CLI: ``python benchmarks/adversarial_bench.py [--smoke] [--json PATH]``
— smoke runs the same 4096-page geometry over 48 epochs instead of 96
(every claim must hold in both; CI runs smoke). ``--json PATH`` also
writes the payload (the CI artifact).
"""
from __future__ import annotations

import json
import sys
import time
from typing import Callable, Dict

import numpy as np

from repro.core.baselines import AutoNUMALike, HeMemStatic, TwoLM
from repro.core.manager import CentralManager
from repro.core.scenario import (
    STORM_FAMILIES,
    ScenarioResult,
    adversarial_scenario,
    churn_recovery_epochs,
    run_scenario,
    storm_health,
    storm_scenario,
)
from repro.core.simulator import OPTANE, ColocationSim
from repro.core.types import TIER_FAST, TIER_NONE, TIER_SLOW

# ---- storm geometry (validated: claims hold at 48 and 96 epochs) -----------
N_PAGES = 4096
QUEUE_SIZE = 16
BANDWIDTH = 4
LATENCY = 1

# the guard profile under test: bands absorb the boundary straddle,
# admission pins per-direction enqueues to half the drain bandwidth,
# cooldown tombstones reheat-cancelled demotions
GUARDS = dict(
    promote_band=0.12,
    demote_band=0.04,
    promote_admission=BANDWIDTH // 2,
    demote_cooldown=4,
)

STEADY_TOL = 0.02
CANCEL_RATIO_BOUND = 0.25


def storm_backends(n_pages: int, seed: int = 0) -> Dict[str, Callable]:
    """All four policies plus the guarded MaxMem leg on identical machine
    geometry (fast = P/8). MaxMem runs the bounded data plane (the storm
    regime needs a finite queue); the instant-apply baselines take the
    same storms as robustness legs — invariants checked, throughput
    reported, no queue to storm."""
    fast = n_pages // 8
    budget = max(fast // 8, 8)
    parts = {0: fast // 3, 1: fast // 3, 2: fast // 3}
    mm_kw = dict(
        num_pages=n_pages, fast_capacity=fast, migration_budget=budget,
        max_tenants=16, sample_period=1, exact_sampling=True, seed=seed,
        queue_size=QUEUE_SIZE, migration_bandwidth=BANDWIDTH,
        migration_latency=LATENCY,
    )
    return {
        "maxmem": lambda: CentralManager(**mm_kw),
        "maxmem_guarded": lambda: CentralManager(**mm_kw, **GUARDS),
        "hemem": lambda: HeMemStatic(
            n_pages, fast, partitions=parts, hot_threshold=8,
            migration_budget=budget, seed=seed),
        "autonuma": lambda: AutoNUMALike(n_pages, fast, seed=seed),
        "twolm": lambda: TwoLM(n_pages, fast, seed=seed),
    }


def _fast_cap(backend) -> int:
    if hasattr(backend, "params"):
        return int(backend.params.fast_capacity)
    return backend.fast_capacity


def check_invariants(sim, event=None) -> None:
    """Conservation invariants every backend must uphold mid-storm (the
    same checks ``tests/test_scenarios.py`` runs; re-asserted here so the
    claims are only ever read off runs that uphold them at bench scale)."""
    backend = sim.backend
    tier = np.asarray(backend.tiers())
    owner = np.asarray(backend.owners())
    ctx = f"after {event}" if event is not None else "after epoch"
    assert set(np.unique(tier).tolist()) <= {TIER_NONE, TIER_SLOW, TIER_FAST}, ctx
    owned = owner >= 0
    assert (tier[owned] != TIER_NONE).all(), f"owned page unplaced {ctx}"
    assert (tier[~owned] == TIER_NONE).all(), f"unowned page placed {ctx}"
    assert int((tier == TIER_FAST).sum()) <= _fast_cap(backend), (
        f"fast over capacity {ctx}")
    if hasattr(backend, "queue_counters"):
        c = backend.queue_counters()
        assert c["enqueued"] == (
            c["drained"] + c["cancelled"] + c["dropped"] + c["depth"]
        ), f"queue conservation broken {ctx}: {c}"


def _storm(family: str, n_pages: int, n_epochs: int):
    if family == "composite":
        return adversarial_scenario(n_pages, n_epochs,
                                    fast_capacity=n_pages // 8)
    return storm_scenario(family, n_pages, n_epochs)


def _event_starts(res: ScenarioResult):
    return [s for s, _e, _l in res.scenario.phase_spans() if s > 0]


def run_family(family: str, n_epochs: int, seed: int = 4) -> Dict:
    """One grid row: the storm on all five legs, invariants on every
    event, flow/recovery observables on the two MaxMem legs."""
    sc = _storm(family, N_PAGES, n_epochs)
    out: Dict[str, Dict] = {}
    for name, mk in storm_backends(N_PAGES).items():
        chunk = 4 if name.startswith("maxmem") else 1
        sim = ColocationSim(mk(), OPTANE, seed=seed, policy_chunk=chunk)
        t0 = time.time()
        res = run_scenario(sim, sc, on_event=check_invariants)
        check_invariants(sim)
        wall = time.time() - t0
        row = {
            "steady_state_agg_throughput": res.steady_state.agg_throughput,
            "wall_s": round(wall, 2),
        }
        if name.startswith("maxmem"):
            starts = _event_starts(res)
            recs = {str(s): churn_recovery_epochs(res.history, s)
                    for s in starts}
            health = storm_health(res)
            row.update(
                churn_recovery=recs,
                worst_churn_recovery=max(recs.values()) if recs else 0,
                storm_health=health,
                cancel_ratio=health["cancel_ratio"],
            )
        out[name] = row
    return {
        "scenario": {
            "name": sc.name, "n_pages": N_PAGES, "n_epochs": n_epochs,
            "events": [type(e).__name__ + "@" + str(e.epoch)
                       for e in sc.events],
        },
        "policies": out,
    }


def evaluate_claims(families: Dict[str, Dict]) -> Dict:
    strict, tol_ok, cancel_ok = True, True, True
    for fam, row in families.items():
        d = row["policies"]["maxmem"]
        g = row["policies"]["maxmem_guarded"]
        strict &= g["worst_churn_recovery"] < d["worst_churn_recovery"]
        tol_ok &= (g["steady_state_agg_throughput"]
                   >= d["steady_state_agg_throughput"] * (1 - STEADY_TOL))
        for leg in (d, g):
            cancel_ok &= leg["cancel_ratio"] <= CANCEL_RATIO_BOUND
        cancel_ok &= g["storm_health"]["drained"] > 0
    return {
        "recovery_strict_every_family": bool(strict),
        "steady_state_within_tol": bool(tol_ok),
        "steady_tol": STEADY_TOL,
        "cancel_ratio_bounded": bool(cancel_ok),
        "cancel_ratio_bound": CANCEL_RATIO_BOUND,
    }


def adversarial_bench(smoke: bool = False) -> Dict:
    n_epochs = 48 if smoke else 96
    grid = tuple(STORM_FAMILIES) + ("composite",)
    families = {fam: run_family(fam, n_epochs) for fam in grid}
    return {
        "smoke": smoke,
        "geometry": {
            "n_pages": N_PAGES, "n_epochs": n_epochs,
            "queue_size": QUEUE_SIZE, "bandwidth": BANDWIDTH,
            "latency": LATENCY, "guards": GUARDS,
        },
        "families": families,
        "claims": evaluate_claims(families),
    }


def main(argv) -> int:
    smoke = "--smoke" in argv
    out = argv[argv.index("--json") + 1] if "--json" in argv else None
    t0 = time.time()
    payload = adversarial_bench(smoke=smoke)
    for fam, row in payload["families"].items():
        d = row["policies"]["maxmem"]
        g = row["policies"]["maxmem_guarded"]
        print(f"adversarial_{fam},0.000,"
              f"worst_default={d['worst_churn_recovery']};"
              f"worst_guarded={g['worst_churn_recovery']};"
              f"enq_default={d['storm_health']['enqueued']};"
              f"enq_guarded={g['storm_health']['enqueued']};"
              f"cancel_ratio_guarded={g['cancel_ratio']};"
              f"agg_ratio={g['steady_state_agg_throughput'] / d['steady_state_agg_throughput']:.4f}")
    c = payload["claims"]
    print(f"adversarial_claims,0.000," + ";".join(
        f"{k}={v}" for k, v in c.items()))
    print(f"adversarial_wall,{(time.time() - t0) * 1e6:.0f},"
          f"{'smoke' if smoke else 'full'}")
    if out:
        with open(out, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"wrote {out}")
    rc = 0
    if not c["recovery_strict_every_family"]:
        print("FAIL: guarded MaxMem did not recover strictly faster than "
              "default on every storm family")
        rc = 1
    if not c["steady_state_within_tol"]:
        print("FAIL: guarded steady-state aggregate outside tolerance")
        rc = 1
    if not c["cancel_ratio_bounded"]:
        print("FAIL: cancelled/drained ratio above bound (livelock risk)")
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
