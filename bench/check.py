"""Decides ``correct`` from the numbers a driver's comparison gives
(``bench/drivers/<driver>.py``: ``CHECKS`` and ``compare``), each against
the limit ``bench/checks/<cell>.json`` gives it."""
from __future__ import annotations

import json
from typing import Dict, Sequence

from bench.generator import BENCH


def load_limits(cell: str, checks: Sequence[str]) -> Dict[str, float]:
    limits = json.loads((BENCH / "checks" / f"{cell}.json").read_text())["limits"]
    missing = set(checks) - set(limits)
    if missing:
        raise KeyError(f"checks/{cell}.json lacks limits for {sorted(missing)}")
    return {k: limits[k] for k in checks}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(numbers[k] <= v for k, v in limits.items())


def lines(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    return {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
