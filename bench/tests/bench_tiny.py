"""A deployment cut down to a size the CPU tests can hold, and a one-call
driver of the harness on it (no look for a chip)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.generator import load_json  # noqa: E402

PAGES = 4096
CELLS = {"growth": "paper_fig8.growth", "arrivals": "paper_fig8.arrivals"}


def tiny_config(name: str = "paper_fig8") -> dict:
    """``name``'s deployment at 4,096 pages, 512 fast, budget 64: tenants and
    access laws as published, sizes and sample rates scaled with the box."""
    cfg = load_json("configs", name)
    scale = PAGES / cfg["manager"]["num_pages"]
    cfg["manager"] = dict(cfg["manager"], num_pages=PAGES, fast_capacity=512, migration_budget=64,
                          migration_bandwidth=64, queue_size=128)
    cfg["access_model"] = dict(cfg["access_model"],
                               sample_every=cfg["access_model"]["sample_every"] / scale)
    for t in cfg["tenants"]:
        t["pages"] = int(t["pages"] * scale)
    return cfg


def tiny_mix(name: str, warmup: int = None) -> dict:
    mix = load_json("traffic", name)
    return mix if warmup is None else dict(mix, warmup_epochs=warmup)


def measure(cfg=None, mix=None, seed: int = 2**31 + 7, seconds: float = 0.5, trace: bool = False,
            limits=None, cell: str = "paper_fig8.growth"):
    from bench import run

    lines = []
    return run.measure(
        cell, cfg or tiny_config(), mix or tiny_mix("growth"), [], seed, seconds, trace,
        time.time(), limits=limits, log=lines.append,
    ), lines
