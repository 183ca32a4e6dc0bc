"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import time
from typing import Dict, List

from repro.core.baselines import AutoNUMALike, HeMemStatic, TwoLM
from repro.core.manager import CentralManager
from repro.core.simulator import OPTANE, ColocationSim, MachineSpec, WorkloadSpec

# Canonical scaled-down machine: 1 page = 1 "GB-like" unit. The paper's box
# has 128 GB fast (DAX) + 768 GB slow; we use 4 pages per "GB" for fidelity
# at simulator cost: 512 fast + 3072 slow pages.
FAST_PAGES = 512
SLOW_PAGES = 3072
TOTAL_PAGES = FAST_PAGES + SLOW_PAGES
MIGRATION_BUDGET = 32  # ~6% of fast capacity per epoch (paper: 4 GB/s on 128 GB
# DRAM ~ 3%). Budgets >~25% of fast capacity destabilize the control loop:
# the one-epoch measurement lag + lambda=0.5 EWMA forms a period-2 limit
# cycle with rotating starvation.


def make_maxmem(fair_mode: bool = False, budget: int = MIGRATION_BUDGET,
                sample_period: int = 100, seed: int = 0) -> CentralManager:
    return CentralManager(
        num_pages=TOTAL_PAGES,
        fast_capacity=FAST_PAGES,
        migration_budget=budget,
        max_tenants=8,
        sample_period=sample_period,
        fair_mode=fair_mode,
        seed=seed,
    )


# HeMem's absolute hotness threshold, calibrated so it SEPARATES the KVS
# hot set from cold data (Fig. 5-7, where HeMem is the static upper bound)
# but CANNOT separate hot from warm in the GUPS gradient workload (Fig. 3,
# where every set exceeds it) — exactly the paper's characterization.
HEMEM_THRESHOLD = 8000


def make_hemem(partitions: Dict[int, int], threshold: int = HEMEM_THRESHOLD) -> HeMemStatic:
    return HeMemStatic(
        num_pages=TOTAL_PAGES,
        fast_capacity=FAST_PAGES,
        partitions=partitions,
        hot_threshold=threshold,
        migration_budget=MIGRATION_BUDGET,
    )


def make_autonuma() -> AutoNUMALike:
    return AutoNUMALike(num_pages=TOTAL_PAGES, fast_capacity=FAST_PAGES)


def make_2lm() -> TwoLM:
    return TwoLM(num_pages=TOTAL_PAGES, fast_capacity=FAST_PAGES)


class Rows:
    """CSV accumulator: name,us_per_call,derived."""

    def __init__(self):
        self.rows: List[str] = []

    def add(self, name: str, us_per_call: float, derived: str):
        self.rows.append(f"{name},{us_per_call:.3f},{derived}")

    def extend(self, other: "Rows"):
        self.rows.extend(other.rows)

    def print(self):
        for r in self.rows:
            print(r)


def timed(fn):
    t0 = time.time()
    out = fn()
    return out, (time.time() - t0) * 1e6
