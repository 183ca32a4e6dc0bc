"""Fleet-driven policy autotuner (DESIGN.md §9).

"From Good to Great" (PAPERS.md) shows tiering systems leave large factors
on the table at default parameters. This module searches the traced
``PolicyParams`` surface (docs/PARAMS.md is the field reference;
``SEARCH_SPACE`` below is the machine-readable subset the tuner explores)
using the sharded fleet as a parallel evaluator: every generation of
candidate configurations becomes one :class:`~repro.core.scenario.
ScenarioSweep` — one machine per candidate, every machine replaying the
SAME scenario schedule — advanced by ``run_sweep`` in one vmapped/sharded
dispatch per chunk. Because every searched knob is a traced leaf, the whole
population shares one compiled fleet program: the grid is free.

Two modes:

* **offline** (:class:`PolicyAutotuner`) — evolutionary search (elite-keep
  + uniform crossover + clamped mutation, seeded ``numpy`` Generators, so
  the full trajectory is deterministic) over a scenario family from
  ``benchmarks/dynamic_workload.py``. Winners are committed as named
  profiles under ``src/repro/configs/tuned/`` and load back through
  ``PolicyParams.from_profile("thrash_4k")``. The paper-default candidate
  is always index 0 of generation 0, and the winner must weakly dominate
  it (aggregate throughput ≥ default AND LS p99 ≤ default), so the
  tuned-vs-default claim holds by construction at the tuned geometry
  (``tests/test_autotune.py::test_committed_smoke_profile_replays``
  replays it).
* **online** (:class:`OnlineTuner`) — a controller attached to a live
  ``ColocationSim`` that watches phase telemetry (Arrive / SkewChange /
  ShiftWorkingSet events), re-dispatches a small tuning burst mid-run
  (candidate params × frozen access distribution through a throwaway
  ``FleetManager``) and hot-swaps the winning params into the live
  manager. Params are traced, so the swap never recompiles; the burst
  draws from its own seeded RNG stream, so the host run's randomness is
  untouched and default-vs-online legs stay comparable.

Search is resumable (PR 6 checkpoints): the tuner persists its own state
after every generation and forwards ``checkpoint_every`` to each
generation's ``run_sweep``, so a kill mid-generation resumes bit-identically
to the uninterrupted run.

Quickstart::

    PYTHONPATH=src:. python -m repro.launch.hillclimb --scenario thrash --smoke
    PYTHONPATH=src:. python -m repro.launch.hillclimb --scenario colocation \
        --smoke --commit-profile
"""
from __future__ import annotations

import argparse
import json
import math
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.manager import CentralManager
from repro.core.scenario import (
    Arrive,
    Scenario,
    ScenarioSweep,
    ShiftWorkingSet,
    SkewChange,
    SweepPoint,
    adversarial_scenario,
    recovery_epochs,
    run_sweep,
)
from repro.core.simulator import WorkloadSpec

# --------------------------------------------------------------- search space
#
# The knobs the offline tuner explores — each a traced ``PolicyParams`` leaf
# reachable through ``SweepPoint`` (so a generation needs no recompile).
# ``frac`` knobs are fractions of the fast tier and resolve to page counts
# at SweepPoint construction, which lets one candidate transfer across
# geometries; ``log=True`` searches/mutates multiplicatively. ``default``
# is the paper/engine default (docs/PARAMS.md documents every field,
# including the ones deliberately NOT searched here and why).
SEARCH_SPACE: Dict[str, Dict] = {
    "sample_period": dict(kind="int", lo=25, hi=400, log=True, default=100),
    "ewma_lambda": dict(kind="float", lo=0.1, hi=0.9, log=False, default=0.5),
    "hysteresis": dict(kind="float", lo=0.0, hi=0.2, log=False, default=0.08),
    "num_bins": dict(kind="int", lo=4, hi=10, log=False, default=6),
    "migration_budget": dict(
        kind="frac", lo=1 / 64, hi=1 / 4, log=True, default=1 / 8
    ),
    "alloc_headroom": dict(kind="frac", lo=0.0, hi=1 / 8, log=False, default=0.0),
}

P99_WEIGHT = 4.0  # score = tput gain − weight · relative LS-p99 regression


@dataclass(frozen=True)
class TunerGeometry:
    """The shape knobs of one tuning run — everything that would force a
    retrace if it varied across candidates, so it is fixed per search and
    recorded in the committed profile."""

    n_pages: int
    n_epochs: int
    fast: int
    queue_size: int = 0
    max_tenants: int = 8
    policy_chunk: int = 8


# ------------------------------------------------------------- candidates
Candidate = Dict[str, float]  # knob -> value in search units (JSON-stable)


def default_candidate() -> Candidate:
    return {k: float(s["default"]) for k, s in SEARCH_SPACE.items()}


def sample_candidate(rng: np.random.Generator) -> Candidate:
    cand = {}
    for k, s in SEARCH_SPACE.items():
        if s["log"]:
            lo, hi = math.log(max(s["lo"], 1e-9)), math.log(s["hi"])
            cand[k] = float(math.exp(rng.uniform(lo, hi)))
        else:
            cand[k] = float(rng.uniform(s["lo"], s["hi"]))
    return cand


def mutate(cand: Candidate, rng: np.random.Generator, scale: float = 0.25) -> Candidate:
    out = dict(cand)
    for k, s in SEARCH_SPACE.items():
        if rng.random() >= 0.6:  # per-knob mutation probability
            continue
        if s["log"]:
            v = out[k] * math.exp(float(rng.normal(0.0, scale)))
        else:
            v = out[k] + float(rng.normal(0.0, scale * (s["hi"] - s["lo"])))
        out[k] = float(min(max(v, s["lo"]), s["hi"]))
    return out


def crossover(a: Candidate, b: Candidate, rng: np.random.Generator) -> Candidate:
    return {k: float(a[k] if rng.random() < 0.5 else b[k]) for k in SEARCH_SPACE}


def resolve_knobs(cand: Candidate, geom: TunerGeometry) -> Dict[str, object]:
    """Candidate (search units) -> concrete ``SweepPoint`` overrides."""
    kw: Dict[str, object] = {}
    for k, v in cand.items():
        s = SEARCH_SPACE[k]
        if s["kind"] == "frac":
            pages = int(round(v * geom.fast))
            if k == "migration_budget":
                kw[k] = max(2, min(pages, geom.fast))
            else:
                kw[k] = max(0, min(pages, geom.fast // 2))
        elif s["kind"] == "int":
            kw[k] = int(round(min(max(v, s["lo"]), s["hi"])))
        else:
            kw[k] = float(min(max(v, s["lo"]), s["hi"]))
    return kw


# ---------------------------------------------------------------- scoring
def ls_tenants(scenario: Scenario) -> List[str]:
    """Latency-sensitive tenants = Arrive specs with a real FMMR target."""
    return sorted(
        {
            ev.spec.name
            for ev in scenario.events
            if isinstance(ev, Arrive) and ev.spec.t_miss < 1.0
        }
    )


def measure_history(
    history: Sequence, window: Tuple[int, int], ls_names: Sequence[str]
) -> Tuple[float, float]:
    """(mean aggregate ops/s, mean LS p99 seconds) over ``window`` epochs."""
    recs = list(history[window[0] : window[1]])
    if not recs:
        return 0.0, 0.0
    agg = float(np.mean([sum(r.throughput.values()) for r in recs]))
    vals = [r.p99[nm] for r in recs for nm in ls_names if nm in r.p99]
    return agg, float(np.mean(vals)) if vals else 0.0


def scalarize(
    agg: float, ls_p99: float, ref_agg: float, ref_p99: float,
    p99_weight: float = P99_WEIGHT,
) -> float:
    """Throughput gain over the reference minus a one-sided p99 penalty —
    p99 *improvements* are not rewarded (the paper's QoS framing: meet the
    target, spend the rest on aggregate throughput)."""
    gain = agg / max(ref_agg, 1e-12)
    pen = max(0.0, ls_p99 / max(ref_p99, 1e-12) - 1.0)
    return float(gain - p99_weight * pen)


# recovery_epochs (the Jenga-style responsiveness metric this tuner scores
# online candidates on) moved to ``repro.core.scenario`` in the adversarial
# hardening pass; it is re-imported above so every existing call site —
# benchmarks, tests, the online tuner — keeps working unchanged.
assert recovery_epochs is not None  # re-exported from repro.core.scenario


# ------------------------------------------------------- scenario families
# Built-in responsiveness probe — no benchmarks/ import, so tests and the
# online bench can run with only ``src`` on the path.
def skewshift_scenario(n_pages: int, n_epochs: int, shift_epoch: Optional[int] = None) -> Scenario:
    """Two LS tenants + one BE; mid-run the KVS tenant's accesses jump to a
    previously-cold scatter (``SkewChange`` set 0 -> set 1). The learned
    heat map is instantly stale and the recovery slope is governed by the
    migration budget + sampling rate — the probe the online tuner is
    scored on (epochs-to-recover, :func:`recovery_epochs`)."""
    kvs = (3 * n_pages) // 8
    gap = n_pages // 4
    shift = n_epochs // 2 if shift_epoch is None else shift_epoch
    return Scenario(
        name=f"skewshift_{n_pages // 1024}k",
        n_epochs=n_epochs,
        events=(
            Arrive(0, WorkloadSpec(
                "kvs", kvs, t_miss=0.2, threads=4,
                sets=((0.18, 0.9), (0.18, 0.0)), value_bytes=16384,
            )),
            Arrive(0, WorkloadSpec(
                "gapbs", gap, t_miss=0.4, threads=8, sets=((0.2, 0.85),),
            )),
            Arrive(0, WorkloadSpec("gups", n_pages // 4, threads=6)),
            SkewChange(shift, "kvs", 0, 0.0),
            SkewChange(shift, "kvs", 1, 0.9),
        ),
        description="hot-set jump responsiveness probe (online autotuner)",
    )


# family -> needs the bounded data plane (queue-mode shapes)
FAMILY_BOUNDED = {"thrash": True, "adversarial": True}
FAMILY_MAX_TENANTS = {"sweep": 16}
FAMILIES = ("colocation", "thrash", "skewshift", "faults", "sweep", "adversarial")


def family_geometry(
    family: str,
    *,
    smoke: bool = False,
    n_pages: Optional[int] = None,
    n_epochs: Optional[int] = None,
) -> TunerGeometry:
    """Mirror ``benchmarks/dynamic_workload.py`` geometry conventions:
    fast tier = P/8 (the paper's 128G/1T box), default budget = fast/8.
    The queue (when the family is bounded) is sized for the LARGEST budget
    in the search range — queue size is a shape, so it is fixed across
    candidates and both bench legs."""
    if n_pages is None:
        n_pages = 4096 if smoke else 65536
    if n_epochs is None:
        n_epochs = 16 if smoke else 96
    fast = n_pages // 8
    return TunerGeometry(
        n_pages=n_pages,
        n_epochs=n_epochs,
        fast=fast,
        queue_size=fast // 2 if FAMILY_BOUNDED.get(family, False) else 0,
        max_tenants=FAMILY_MAX_TENANTS.get(family, 8),
        policy_chunk=4 if smoke else 8,
    )


def family_scenario(family: str, geom: TunerGeometry) -> Scenario:
    if family == "skewshift":
        return skewshift_scenario(geom.n_pages, geom.n_epochs)
    if family == "adversarial":
        # composite storm (core/scenario.py): boundary straddle phase-locked
        # with a ping-pong flipper — src-only path, like skewshift
        return adversarial_scenario(
            geom.n_pages, geom.n_epochs, fast_capacity=geom.fast
        )
    try:
        from benchmarks import dynamic_workload as dw
    except ImportError as e:  # pragma: no cover - depends on caller's path
        raise ImportError(
            f"scenario family {family!r} lives in benchmarks/dynamic_workload.py; "
            "run from the repo root with PYTHONPATH=src:."
        ) from e
    makers: Dict[str, Callable] = {
        "colocation": dw.colocation_scenario,
        "thrash": dw.thrash_scenario,
        "faults": dw.faults_scenario,
        "sweep": dw.sweep_scenario,
    }
    if family not in makers:
        raise KeyError(f"unknown scenario family {family!r}; choose from {FAMILIES}")
    return makers[family](geom.n_pages, geom.n_epochs)


def scale_tag(n_pages: int) -> str:
    return f"{n_pages // 1024}k"


# ---------------------------------------------------------------- offline
@dataclass
class TunerResult:
    family: str
    interrupted: bool
    winner: Optional[Dict]  # {candidate, resolved, agg, ls_p99, score, generation, index}
    ref: Optional[Dict]  # default-candidate measures {agg, ls_p99}
    trajectory: List[Dict] = field(default_factory=list)


class PolicyAutotuner:
    """Offline population search over ``SEARCH_SPACE`` with the fleet as
    the evaluator (one sweep point per candidate, one dispatch per chunk).

    Candidate 0 of generation 0 is ALWAYS the paper-default configuration;
    its measures become the reference for scoring and for the weak-
    domination winner rule (tuned throughput ≥ default AND tuned LS p99 ≤
    default). The simulator and the search are both seeded, so the same
    ``seed`` reproduces the trajectory bit-for-bit.
    """

    def __init__(
        self,
        family: str,
        geom: TunerGeometry,
        scenario: Optional[Scenario] = None,
        *,
        population: int = 8,
        generations: int = 4,
        elites: int = 2,
        seed: int = 0,
        eval_seed: int = 0,
        p99_weight: float = P99_WEIGHT,
        out_dir: Optional[str] = None,
        checkpoint_every: Optional[int] = None,
        devices=None,
        pipeline: bool = True,
        verbose: bool = False,
    ):
        assert population >= 2 and generations >= 1 and 1 <= elites < population
        self.family = family
        self.geom = geom
        self.scenario = scenario if scenario is not None else family_scenario(family, geom)
        self.population = population
        self.generations = generations
        self.elites = elites
        self.seed = seed
        self.eval_seed = eval_seed
        self.p99_weight = p99_weight
        self.out_dir = out_dir
        self.checkpoint_every = checkpoint_every
        self.devices = devices
        self.pipeline = pipeline
        self.verbose = verbose
        # the steady window the paper figures compare on: skip the opening
        # quarter (arrivals + first convergence) and score the rest
        self.window = (geom.n_epochs // 4, geom.n_epochs)
        self.ls_names = ls_tenants(self.scenario)
        if out_dir is not None:
            os.makedirs(out_dir, exist_ok=True)

    # ------------------------------------------------------------ state io
    def _state_path(self) -> Optional[str]:
        return None if self.out_dir is None else os.path.join(self.out_dir, "tuner_state.json")

    def _save_state(self, next_gen: int, population, trajectory, ref) -> None:
        path = self._state_path()
        if path is None:
            return
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "family": self.family,
                    "seed": self.seed,
                    "next_generation": next_gen,
                    "population": population,
                    "trajectory": trajectory,
                    "ref": ref,
                },
                f,
            )
        os.replace(tmp, path)

    def _load_state(self) -> Optional[Dict]:
        path = self._state_path()
        if path is None or not os.path.exists(path):
            return None
        with open(path) as f:
            state = json.load(f)
        if state["family"] != self.family or state["seed"] != self.seed:
            raise ValueError(
                f"tuner state at {path} is for family={state['family']!r} "
                f"seed={state['seed']}; this run is {self.family!r}/{self.seed}"
            )
        return state

    def _log(self, msg: str) -> None:
        if self.verbose:
            print(f"[hillclimb:{self.family}] {msg}", flush=True)

    # ---------------------------------------------------------- evaluation
    def _evaluate(self, gen, population, *, resume=False, stop_after=None):
        """One generation = one ScenarioSweep. Returns [(agg, ls_p99)] per
        candidate, or None if the sweep was stopped early (kill simulation
        / checkpoint-resume tests)."""
        geom = self.geom
        points = tuple(
            SweepPoint(name=f"c{i:02d}", seed=self.eval_seed, **resolve_knobs(c, geom))
            for i, c in enumerate(population)
        )
        sweep = ScenarioSweep(scenario=self.scenario, points=points)
        ckpt_kw: Dict[str, object] = {}
        if self.out_dir is not None and self.checkpoint_every is not None:
            gen_dir = os.path.join(self.out_dir, f"gen{gen:03d}")
            os.makedirs(gen_dir, exist_ok=True)
            ckpt_kw = dict(
                checkpoint_every=self.checkpoint_every,
                checkpoint_dir=gen_dir,
                resume=resume,
                stop_after=stop_after,
            )
        res = run_sweep(
            sweep,
            num_pages=geom.n_pages,
            fast_capacity=geom.fast,
            migration_budget=resolve_knobs(default_candidate(), geom)["migration_budget"],
            max_tenants=geom.max_tenants,
            queue_size=geom.queue_size,
            policy_chunk=geom.policy_chunk,
            devices=self.devices,
            pipeline=self.pipeline,
            **ckpt_kw,
        )
        if any(len(r.history) < geom.n_epochs for r in res.results.values()):
            return None  # stopped at a checkpoint boundary before the end
        return [
            measure_history(res.results[p.name].history, self.window, self.ls_names)
            for p in points
        ]

    # ----------------------------------------------------------- evolution
    def _evolve(self, population, scores, rng: np.random.Generator):
        order = sorted(range(len(population)), key=lambda i: (-scores[i], i))
        keep = [dict(population[i]) for i in order[: self.elites]]
        parents = order[: max(2, len(order) // 2)]  # top half breeds
        children = []
        while len(keep) + len(children) < self.population:
            pa = population[parents[int(rng.integers(len(parents)))]]
            pb = population[parents[int(rng.integers(len(parents)))]]
            children.append(mutate(crossover(pa, pb, rng), rng))
        return keep + children

    def _pick_winner(self, trajectory, ref) -> Dict:
        """Best-scoring candidate that weakly dominates the default (ties
        resolve to the earliest generation/index, so the default itself is
        the floor)."""
        best = None
        for rec in trajectory:
            for i, cand in enumerate(rec["candidates"]):
                agg, p99 = rec["agg"][i], rec["ls_p99"][i]
                if agg < ref["agg"] * (1 - 1e-9) or p99 > ref["ls_p99"] * (1 + 1e-9):
                    continue
                entry = {
                    "candidate": dict(cand),
                    "resolved": resolve_knobs(cand, self.geom),
                    "agg": agg,
                    "ls_p99": p99,
                    "score": rec["scores"][i],
                    "generation": rec["generation"],
                    "index": i,
                }
                if best is None or entry["score"] > best["score"] + 1e-12:
                    best = entry
        assert best is not None, "default candidate must qualify as winner floor"
        return best

    # -------------------------------------------------------------- search
    def search(self, *, resume: bool = False, stop_after: Optional[int] = None) -> TunerResult:
        """Run (or resume) the population search.

        ``stop_after`` forwards to each generation's ``run_sweep`` as the
        kill-simulation hook: the sweep returns a partial result at the
        first checkpoint past that epoch and the tuner stops with
        ``interrupted=True`` — call ``search(resume=True)`` to continue
        bit-identically (PR 6 checkpoint machinery underneath).
        """
        state = self._load_state() if resume else None
        gen0, trajectory, ref, population = 0, [], None, None
        if state is not None:
            gen0 = state["next_generation"]
            population = [dict(c) for c in state["population"]]
            trajectory = state["trajectory"]
            ref = state["ref"]
        if population is None:
            rng0 = np.random.default_rng([self.seed, 0])
            population = [default_candidate()] + [
                sample_candidate(rng0) for _ in range(self.population - 1)
            ]
        for gen in range(gen0, self.generations):
            measures = self._evaluate(
                gen, population, resume=resume and gen == gen0, stop_after=stop_after
            )
            if measures is None:
                self._log(f"gen {gen}: stopped early (stop_after={stop_after})")
                return TunerResult(self.family, True, None, ref, trajectory)
            if ref is None:  # candidate 0 of generation 0 is the default
                ref = {"agg": measures[0][0], "ls_p99": measures[0][1]}
            scores = [
                scalarize(a, p, ref["agg"], ref["ls_p99"], self.p99_weight)
                for a, p in measures
            ]
            trajectory.append(
                {
                    "generation": gen,
                    "candidates": [dict(c) for c in population],
                    "agg": [a for a, _ in measures],
                    "ls_p99": [p for _, p in measures],
                    "scores": scores,
                    "best_index": int(np.argmax(scores)),
                }
            )
            self._log(
                f"gen {gen}: best score {max(scores):.4f} "
                f"(agg {measures[int(np.argmax(scores))][0]:,.0f} ops/s)"
            )
            # stateless per-generation RNG: resuming at generation g draws
            # the same stream without serializing generator state
            rng = np.random.default_rng([self.seed, 1, gen])
            population = self._evolve(population, scores, rng)
            self._save_state(gen + 1, population, trajectory, ref)
        winner = self._pick_winner(trajectory, ref)
        self._log(
            f"winner: gen {winner['generation']} c{winner['index']:02d} "
            f"{winner['resolved']} (+{100 * (winner['agg'] / ref['agg'] - 1):.1f}% agg)"
        )
        return TunerResult(self.family, False, winner, ref, trajectory)

    # -------------------------------------------------------------- commit
    def commit_profile(self, result: TunerResult, name: Optional[str] = None) -> str:
        """Write the winner as a named profile under ``configs/tuned/``."""
        from repro.configs.tuned import save_profile
        from repro.runtime.fault_tolerance import _params_to_meta

        assert not result.interrupted and result.winner is not None
        geom, w = self.geom, result.winner
        kw = w["resolved"]
        mgr = CentralManager(
            num_pages=geom.n_pages,
            fast_capacity=geom.fast,
            migration_budget=kw["migration_budget"],
            max_tenants=geom.max_tenants,
            num_bins=kw["num_bins"],
            sample_period=kw["sample_period"],
            ewma_lambda=kw["ewma_lambda"],
            hysteresis=kw["hysteresis"],
            alloc_headroom=kw["alloc_headroom"],
            queue_size=geom.queue_size,
        )
        prof = {
            "name": name or f"{self.family}_{scale_tag(geom.n_pages)}",
            "family": self.family,
            "geometry": {
                "n_pages": geom.n_pages,
                "n_epochs": geom.n_epochs,
                "fast_capacity": geom.fast,
                "queue_size": geom.queue_size,
                "max_tenants": geom.max_tenants,
                "policy_chunk": geom.policy_chunk,
            },
            "params": _params_to_meta(mgr.params),
            "metrics": {
                "default": {
                    "agg_throughput": result.ref["agg"],
                    "ls_p99_us": result.ref["ls_p99"] * 1e6,
                },
                "tuned": {
                    "agg_throughput": w["agg"],
                    "ls_p99_us": w["ls_p99"] * 1e6,
                },
            },
            "search": {
                "seed": self.seed,
                "eval_seed": self.eval_seed,
                "generations": self.generations,
                "population": self.population,
                "score": w["score"],
                "scored_window": list(self.window),
                "generation": w["generation"],
                "index": w["index"],
            },
        }
        return save_profile(prof)


# ----------------------------------------------------------------- online
class OnlineTuner:
    """Mid-run re-tuner: on a phase-telemetry trigger, evaluate a small
    burst of candidate params against the CURRENT policy state and frozen
    access distribution, then hot-swap the winner into the live manager.

    The burst clones the manager's (immutable) state pytree into K
    throwaway ``CentralManager`` shells — one per candidate — and advances
    them ``burst_epochs`` through a single-device ``FleetManager`` dispatch
    with access counts drawn from the tuner's own seeded RNG (the live
    sim's stream is swapped out and restored, so attaching the controller
    never perturbs the host run). Scoring mirrors the simulator's chunk
    record: per-epoch tenant FMMR -> closed-loop latency fixed point ->
    aggregate throughput, charged with each candidate's own migration
    traffic, with the offline tuner's one-sided LS-p99 penalty PLUS a QoS-
    deficit term (mean excess of measured LS FMMR over its target — the
    policy's own objective). The deficit term DOMINATES (default weight 10,
    the paper's lexicographic QoS framing: meet LS targets first, spend the
    remainder on throughput) because during recovery both other terms
    mislead — a starved LS tenant *raises* aggregate throughput (its
    bandwidth goes to the batch tenants), and a recovering one *raises*
    measured p99 (more traffic inflates the contended slow-op latency while
    the mixture quantile stays pinned to it until the miss ratio is tiny). Candidate 0 is "keep the
    current params", so a swap only happens on a strict improvement. Every searched knob is a traced leaf and shapes
    never change, so the swap costs one params restack — no recompile.

    The manager's ``plan_size`` (static migration-plan buffer) caps how far
    ``migration_budget`` can be tuned UP at runtime — construct the live
    manager with the budget headroom you want the controller to have.
    """

    TRIGGERS = (Arrive, SkewChange, ShiftWorkingSet)

    def __init__(
        self,
        sim,
        *,
        knobs: Tuple[str, ...] = ("migration_budget", "sample_period", "ewma_lambda"),
        candidates: int = 6,
        burst_epochs: int = 8,
        seed: int = 0,
        p99_weight: float = P99_WEIGHT,
        qos_weight: float = 10.0,
        triggers: Optional[Tuple[type, ...]] = None,
    ):
        assert candidates >= 2 and burst_epochs >= 2
        self.sim = sim
        if triggers is not None:
            self.TRIGGERS = tuple(triggers)
        self.knobs = knobs
        self.candidates = candidates
        self.burst_epochs = burst_epochs
        self.seed = seed
        self.p99_weight = p99_weight
        self.qos_weight = qos_weight
        self.retunes: List[Dict] = []

    # `run_scenario(..., on_event=tuner.on_event)` wiring
    def on_event(self, sim, ev) -> None:
        if isinstance(ev, self.TRIGGERS) and sim is self.sim and sim.tenants:
            self.retune(trigger=ev.label())

    def _perturb(self, cur, rng: np.random.Generator):
        import jax.numpy as jnp

        plan = self.sim.backend.plan_size
        rep = {}
        for k in self.knobs:
            if k == "migration_budget":
                v = int(round(int(cur.migration_budget) * math.exp(rng.normal(0.0, 0.7))))
                rep[k] = jnp.int32(min(max(v, 1), plan))
            elif k == "sample_period":
                v = int(round(int(cur.sample_period) * math.exp(rng.normal(0.0, 0.5))))
                rep[k] = jnp.int32(min(max(v, 5), 2000))
            elif k == "ewma_lambda":
                rep[k] = jnp.float32(min(max(float(cur.ewma_lambda) + rng.normal(0.0, 0.15), 0.05), 0.95))
            elif k == "hysteresis":
                rep[k] = jnp.float32(min(max(float(cur.hysteresis) + rng.normal(0.0, 0.05), 0.0), 0.3))
            elif k == "alloc_headroom":
                v = int(round(int(cur.alloc_headroom) + rng.normal(0.0, plan / 4)))
                rep[k] = jnp.int32(min(max(v, 0), int(cur.fast_capacity) // 2))
            else:
                raise KeyError(f"online tuner cannot perturb {k!r}")
        return cur._replace(**rep)

    def _candidate_params(self, rng: np.random.Generator):
        import jax.numpy as jnp

        cur = self.sim.backend.params
        plan = self.sim.backend.plan_size
        out = [cur]
        # deterministic recovery play: full plan-buffer budget + faster
        # sampling, the aggressive config a phase change usually wants
        out.append(
            cur._replace(
                migration_budget=jnp.int32(plan),
                sample_period=jnp.int32(max(10, int(cur.sample_period) // 2)),
            )
        )
        while len(out) < self.candidates:
            out.append(self._perturb(cur, rng))
        return out

    def _burst(self, cands, rng: np.random.Generator):
        from repro.core.fleet import FleetManager

        sim, mgr = self.sim, self.sim.backend
        mgr._ensure_segs()  # clones share the segs-complete state pytree
        state = mgr._state
        clones = []
        for p in cands:
            c = CentralManager(
                num_pages=mgr.num_pages,
                fast_capacity=int(mgr.params.fast_capacity),
                migration_budget=mgr.plan_size,
                max_tenants=mgr.max_tenants,
                queue_size=mgr.queue_size,
            )
            c._state = state
            c._segs_owner = None  # do NOT rebuild segs from the empty init owner
            c.params = p
            c.epoch_index = mgr.epoch_index
            clones.append(c)
        arrays = sim._arrays()
        names, M, page_mask, threads, bpo = arrays
        tier = np.asarray(mgr.tiers())
        saved_rng = sim.rng  # burst draws must not advance the host stream
        sim.rng = rng
        try:
            counts, _ctx = sim._chunk_prepare(arrays, tier)
        finally:
            sim.rng = saved_rng
        fleet = FleetManager(clones, devices=1)
        res = fleet.run_epochs(self.burst_epochs, counts=np.tile(counts, (len(cands), 1)))

        handles = [sim.handles[nm] for nm in names]
        fmmr = np.asarray(res.stats.fmmr_now)[:, :, handles]  # [K, k, n]
        moved = (
            np.asarray(res.stats.promoted) + np.asarray(res.stats.demoted)
        ).sum(axis=-1)  # [K, k] selection traffic (commit upper bound)
        m = sim.machine
        fast_op = m.fast.latency_ns * 1e-9 + bpo / (m.fast.bandwidth_GBps * 1e9)
        ls = [i for i, nm in enumerate(names) if sim.tenants[nm].spec.t_miss < 1.0]
        targets = np.array([sim.tenants[names[i]].spec.t_miss for i in ls], float)
        # terminal-state scoring: the burst asks "where will this candidate
        # have taken the machine by the end of the horizon", so only the
        # last epoch counts — scoring the transient would charge the
        # migration investment against exactly the candidates that make it
        start = self.burst_epochs - 1
        measures = []
        for ki in range(len(cands)):
            aggs, p99s, deficits = [], [], []
            for e in range(start, self.burst_epochs):
                miss = fmmr[ki, e]
                lat, slow_op = sim._latencies(
                    miss, float(moved[ki, e]) * m.page_bytes, threads, bpo
                )
                aggs.append((threads / lat).sum())
                if ls:
                    p99s.append(
                        np.mean(
                            [
                                sim._mixture_quantile(0.99, miss[i], fast_op[i], slow_op[i])
                                for i in ls
                            ]
                        )
                    )
                    deficits.append(np.maximum(miss[ls] - targets, 0.0).mean())
            measures.append(
                (
                    float(np.mean(aggs)),
                    float(np.mean(p99s)) if p99s else 0.0,
                    float(np.mean(deficits)) if deficits else 0.0,
                )
            )
        ref_agg, ref_p99 = measures[0][0], measures[0][1]
        scores = [
            scalarize(a, p, ref_agg, ref_p99, self.p99_weight) - self.qos_weight * d
            for a, p, d in measures
        ]
        return int(np.argmax(scores)), scores, measures  # ties keep current

    def retune(self, trigger: str = "manual"):
        """Run one tuning burst now; hot-swap on strict improvement.
        Returns the params left installed on the live manager."""
        sim = self.sim
        if self.retunes and self.retunes[-1]["epoch"] == len(sim.history):
            return sim.backend.params  # coalesce same-epoch event storms
        rng = np.random.default_rng([self.seed, 23, len(self.retunes)])
        cands = self._candidate_params(rng)
        best, scores, measures = self._burst(cands, rng)
        if best != 0:
            sim.backend.params = cands[best]  # traced leaves: no recompile
        self.retunes.append(
            {
                "epoch": len(sim.history),
                "trigger": trigger,
                "chosen": best,
                "scores": scores,
                "measures": measures,
                "budget": int(sim.backend.params.migration_budget),
                "sample_period": int(sim.backend.params.sample_period),
            }
        )
        return sim.backend.params


# -------------------------------------------------------------------- CLI
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Fleet-driven policy autotuner (DESIGN.md §9)"
    )
    ap.add_argument("--scenario", default="thrash", choices=FAMILIES,
                    help="scenario family to tune")
    ap.add_argument("--smoke", action="store_true", help="toy geometry (~seconds)")
    ap.add_argument("--pages", type=int, default=None, help="override page count")
    ap.add_argument("--epochs", type=int, default=None, help="override epoch count")
    ap.add_argument("--population", type=int, default=8)
    ap.add_argument("--generations", type=int, default=4)
    ap.add_argument("--elites", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=None,
                    help="state + sweep checkpoints here (enables --resume)")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="epochs between sweep checkpoints inside a generation")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--stop-after", type=int, default=None,
                    help="kill-simulation: stop the current generation at the "
                         "first checkpoint past this epoch")
    ap.add_argument("--commit-profile", action="store_true",
                    help="write the winner under src/repro/configs/tuned/")
    ap.add_argument("--profile-name", default=None)
    ap.add_argument("--devices", type=int, default=None)
    args = ap.parse_args(argv)

    geom = family_geometry(
        args.scenario, smoke=args.smoke, n_pages=args.pages, n_epochs=args.epochs
    )
    tuner = PolicyAutotuner(
        args.scenario,
        geom,
        population=args.population,
        generations=args.generations,
        elites=args.elites,
        seed=args.seed,
        out_dir=args.out_dir,
        checkpoint_every=args.checkpoint_every,
        devices=args.devices,
        verbose=True,
    )
    result = tuner.search(resume=args.resume, stop_after=args.stop_after)
    if result.interrupted:
        print("search interrupted at a checkpoint; rerun with --resume")
        return 2
    w, ref = result.winner, result.ref
    print(f"\nscenario family : {args.scenario} ({geom.n_pages} pages x {geom.n_epochs} epochs)")
    print(f"default         : agg {ref['agg']:,.0f} ops/s  LS p99 {ref['ls_p99'] * 1e6:.1f} us")
    print(f"tuned           : agg {w['agg']:,.0f} ops/s  LS p99 {w['ls_p99'] * 1e6:.1f} us")
    print(f"delta           : {100 * (w['agg'] / max(ref['agg'], 1e-12) - 1):+.2f}% agg, "
          f"{100 * (w['ls_p99'] / max(ref['ls_p99'], 1e-12) - 1):+.2f}% p99")
    print(f"winning knobs   : {w['resolved']}")
    if args.commit_profile:
        path = tuner.commit_profile(result, name=args.profile_name)
        print(f"profile written : {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
