"""The one traffic generator of the benchmark.

A deployment (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``) go in; the cell's whole schedule comes out,
drawn from the seed before any epoch runs:

* the tenants of the deployment, in the order they first arrive;
* the mix's ``cycle``: phases of so many epochs that repeat from epoch 0.
  Each phase names the tenants present and may override their access law's
  parameters. A tenant present in a phase and absent in the one before
  arrives at its first epoch; one absent that was present departs there;
* a ring of per-page access-sample counts (``u32[P]`` each), ``draws`` per
  phase, which epoch ``e`` replays as ``counts(e)``;
* each page's contents (``content_rows``), 128 float32 per page that are exact
  integers below 2**24, so a byte-exact comparison needs no tolerance. A
  tenant that arrives again gets the contents of the other parity.

A tenant's samples per epoch follow the deployment's ``access_model``: its
threads over the latency of one operation on the slow tier (latency plus its
value at the tier's bandwidth), over the epoch, one access in
``sample_every`` sampled. The access law, ``bench/laws/<kind>.py``, spreads
them over the tenant's pages; per-page counts are Poisson with that rate, as
``core/simulator.py`` draws them.
"""
from __future__ import annotations

import importlib
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
U32 = np.uint32


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``; an unknown name is an error."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    return json.loads(path.read_text())


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream): any whole seed, 64 bits or more."""
    return np.random.default_rng(np.random.SeedSequence([seed % (1 << 64), *stream]))


def law(kind: str):
    """The access law ``bench/laws/<kind>.py``."""
    try:
        return importlib.import_module(f"bench.laws.{kind}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no access law {kind!r} (bench/laws/{kind}.py)") from e


def samples_per_epoch(t: dict, model: dict) -> float:
    """Access samples a tenant reports per epoch (see the module docstring)."""
    op_s = model["slow_latency_ns"] * 1e-9 + t["value_bytes"] / (model["slow_GBps"] * 1e9)
    return t["threads"] / op_s * model["epoch_s"] / model["sample_every"]


# ------------------------------------------------------------------ contents
_K = [U32(0x9E3779B1), U32(0x85EBCA77), U32(0xC2B2AE3D), U32(0x27D4EB2F),
      U32(0x2C1B3C6D), U32(0x297A2D39)]


def _hash(pages, parity, lane, lo, hi):
    """u32 hash of (page, parity, lane, seed); numpy or jax.numpy u32 arrays."""
    x = pages * _K[0] + lane * _K[1] + parity * _K[2] + (lo ^ (hi * _K[3]))
    x = x ^ (x >> U32(15))
    x = x * _K[4]
    x = x ^ (x >> U32(12))
    x = x * _K[5]
    return x ^ (x >> U32(15))


def _seed_words(seed: int):
    s = seed % (1 << 64)
    return U32(s & 0xFFFFFFFF), U32(s >> 32)


def content_rows(seed: int, pages: np.ndarray, parity: np.ndarray, row_elems: int) -> np.ndarray:
    """f32[len(pages), row_elems]: page ``p``'s contents in generation parity
    ``parity``. Host (numpy) form; :func:`content_rows_jnp` is the same on device."""
    lo, hi = _seed_words(seed)
    p = np.asarray(pages, np.int64).astype(U32)[:, None]
    g = np.asarray(parity, np.int64).astype(U32)[:, None]
    with np.errstate(over="ignore"):
        x = _hash(p, g, np.arange(row_elems, dtype=U32)[None, :], lo, hi)
    return (x >> U32(8)).astype(np.float32)


def content_rows_jnp(seed_words, pages, parity, row_elems: int):
    """Device form of :func:`content_rows` (``seed_words`` = u32[2])."""
    import jax.numpy as jnp

    x = _hash(pages.astype(jnp.uint32)[:, None], parity.astype(jnp.uint32)[:, None],
              jnp.arange(row_elems, dtype=jnp.uint32)[None, :], seed_words[0], seed_words[1])
    return (x >> 8).astype(jnp.float32)


def seed_words(seed: int) -> np.ndarray:
    return np.asarray(_seed_words(seed), U32)


# ------------------------------------------------------------------ schedule
@dataclass
class Schedule:
    cfg: dict
    mix: dict
    seed: int
    tenants: List[dict]
    start: np.ndarray  # i64[n_tenants] first page of each tenant's range
    ring: np.ndarray  # u32[R, P] access-sample counts
    phase_start: np.ndarray = field(default_factory=lambda: np.zeros(1, np.int64))

    @property
    def warmup_epochs(self) -> int:
        return int(self.mix["warmup_epochs"])

    @property
    def cycle(self) -> List[dict]:
        return self.mix["cycle"]

    @property
    def cycle_epochs(self) -> int:
        return int(self.phase_start[-1])

    @property
    def draws(self) -> int:
        return int(self.mix["draws"])

    def index(self, name: str) -> int:
        return [t["name"] for t in self.tenants].index(name)

    def phase_of(self, e: int) -> int:
        pos = e % self.cycle_epochs
        return int(np.searchsorted(self.phase_start, pos, side="right")) - 1

    def present(self, phase: int) -> List[str]:
        return [t["name"] for t in self.tenants if t["name"] in self.cycle[phase]["tenants"]]

    def ring_index(self, e: int) -> int:
        ph = self.phase_of(e)
        return ph * self.draws + (e % self.cycle_epochs - int(self.phase_start[ph])) % self.draws

    def counts(self, e: int) -> np.ndarray:
        return self.ring[self.ring_index(e)]

    def events_at(self, e: int) -> Tuple[List[str], List[str]]:
        """(departures, arrivals) at the start of epoch ``e``, in tenant order.
        The tenants of epoch 0 arrive in set-up, not as events."""
        pos = e % self.cycle_epochs
        if e == 0 or pos not in set(self.phase_start[:-1].tolist()):
            return [], []
        ph = self.phase_of(e)
        now, before = set(self.present(ph)), set(self.present(ph - 1))
        order = [t["name"] for t in self.tenants]
        return ([n for n in order if n in before - now], [n for n in order if n in now - before])

    def initial(self) -> List[str]:
        return self.present(0)

    def generation(self, name: str, e: int) -> int:
        """Arrivals of tenant ``name`` as events up to the start of epoch ``e``."""
        K = len(self.cycle)
        arrive = [k for k in range(K)
                  if name in self.cycle[k]["tenants"] and name not in self.cycle[k - 1]["tenants"]]
        full, pos = divmod(e, self.cycle_epochs)
        n = full * len(arrive) + sum(1 for k in arrive if self.phase_start[k] <= pos)
        return n - (0 in arrive)  # the arrival at epoch 0 is set-up, not an event

    def pages_of(self, name: str) -> np.ndarray:
        i = self.index(name)
        return np.arange(self.start[i], self.start[i] + self.tenants[i]["pages"])


def build_schedule(cfg: dict, mix: dict, seed: int) -> Schedule:
    tenants = [dict(t) for t in cfg["tenants"]]
    P = int(cfg["manager"]["num_pages"])
    sizes = np.array([t["pages"] for t in tenants], np.int64)
    if sizes.sum() > P:
        raise ValueError(f"{cfg['name']}: tenants need {sizes.sum()} pages, the box has {P}")
    names = {t["name"] for t in tenants}
    for ph in mix["cycle"]:
        unknown = set(ph["tenants"]) - names
        if unknown:
            raise ValueError(f"mix names tenants not in {cfg['name']}: {sorted(unknown)}")
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    phase_start = np.concatenate([[0], np.cumsum([int(p["epochs"]) for p in mix["cycle"]])])
    sched = Schedule(cfg, mix, seed, tenants, start, np.empty((0, P), U32), phase_start)
    if sched.draws > min(int(p["epochs"]) for p in mix["cycle"]):
        raise ValueError("draws exceeds the epochs of a phase")

    model = cfg["access_model"]
    perm: Dict[int, np.ndarray] = {
        i: rng_for(seed, 11, i).permutation(t["pages"]) for i, t in enumerate(tenants)}
    ring = np.empty((len(mix["cycle"]) * sched.draws, P), U32)
    rng = rng_for(seed, 13)
    for k, ph in enumerate(mix["cycle"]):
        for d in range(sched.draws):
            rate = np.zeros(P)
            for i, t in enumerate(tenants):
                if t["name"] not in ph["tenants"]:
                    continue
                params = dict(t["access"], **ph["tenants"][t["name"]])
                w = law(params["kind"]).weights(t["pages"], params, perm[i])
                s = start[i]
                rate[s : s + t["pages"]] = samples_per_epoch(t, model) * w
            ring[k * sched.draws + d] = rng.poisson(rate).astype(U32)
    sched.ring = ring
    return sched
