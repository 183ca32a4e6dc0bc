"""Drives a deployment of the MaxMem manager with its page pool: set-up, the
epochs the window runs, and the comparison with the plain reference that
decides ``correct``. A configuration names this driver with
``"driver": "manager_pool"``; its ``manager`` entry is passed whole to
``CentralManager`` and to the reference it names (``bench/reference/<name>.py``).

One epoch, as the window times it, is: the departures and arrivals the mix
puts at this epoch (``free`` + ``unregister``; ``register`` + ``allocate``
and the arrival's contents written through ``PagePool.write_pages``),
``record_access`` of the epoch's access samples, ``run_epoch`` (the fused
policy tick, then the drained pages moved through ``page_move``), and the
wait until the page pool is ready, so that the moved bytes are in place. The
small per-epoch telemetry the comparison reads is fetched with one
``device_get`` inside the epoch. Each step sits in a ``TraceAnnotation``
span named ``bench.<step>``.

The comparison replays the whole schedule the run drove, set-up epochs
included, through the reference. At each epoch the reference makes its own
selection sizes (how many pages each tenant promotes and demotes) and counts
where the run's differ, then follows the run's sizes, as a served model's
reference is run over the served tokens: a size that rounding moves by a page
does not derail the rest of the replay. Every other quantity is compared
exactly. The numbers, each with the limit ``bench/checks/<cell>.json`` gives:

* ``quota_mismatches``: (epoch, tenant, side) selection sizes that differ
  from the reference's under each rounding of its float32 divisions in
  ``ULPS`` (to nearest, or one or two ulps down or up: the chip's divider is
  not correctly rounded, ``bench/witness.py`` shows it);
* ``fmmr_gap``: the widest gap between the run's and the reference's FMMR
  (EWMA) of any tenant after any epoch;
* ``holding_mismatches``: (epoch, tenant) fast-page holdings that differ;
* ``queue_mismatches``: epochs whose queue counts (depth, enqueued, drained
  each way, cancelled, dropped) differ, plus the cumulative counters at the end;
* ``drain_mismatches``: epochs whose drained page ids, in drain order, differ;
* ``alloc_mismatches``: allocations that returned other pages;
* ``placement_mismatches``: pages whose final tier or owner differs;
* ``byte_mismatches``: pages whose frame is not in their tier, is shared, or
  whose 512 bytes there are not the bytes written to them.
"""
from __future__ import annotations

import importlib
import sys
import time
from collections import defaultdict
from functools import partial
from typing import Dict

import numpy as np

from bench.generator import Schedule, content_rows, content_rows_jnp, seed_words

FILL_BLOCK = 8192  # pool rows written per step of the set-up fill
# roundings of the reference's float32 divisions a selection size may follow:
# the chip's quotients lie within 2 ulps of the correctly rounded ones
ULPS = (0, -1, 1, -2, 2)
CHECKS = (
    "quota_mismatches", "fmmr_gap", "holding_mismatches", "queue_mismatches",
    "drain_mismatches", "alloc_mismatches", "placement_mismatches", "byte_mismatches",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """The deployment under test: a ``CentralManager`` with a page pool, the
    tenants of epoch 0 arrived, every page's contents in its frame."""

    def __init__(self, sched: Schedule, pool: bool = True):
        import jax

        from repro.core.manager import CentralManager

        cfg = self.cfg = sched.cfg
        self.sched = sched
        self.jax = jax
        self.pool = pool  # without it, only the policy runs (bench/witness.py)
        self.mgr = CentralManager(**cfg["manager"], seed=sched.seed % (1 << 32),
                                  data_plane_elems=cfg["row_elems"] if pool else None)
        self.allocs = []
        self.handles = {}
        for name in sched.initial():
            self._arrive(name, write=False)
        arriving = {n for e in range(1, sched.cycle_epochs + 1) for n in sched.events_at(e)[1]}
        self.arrival_rows = {}  # contents of the arrivals, by (tenant, generation parity)
        if pool:
            self._fill_pool()
            self.arrival_rows = {
                (n, g): content_rows(sched.seed, pages, np.full(len(pages), g), cfg["row_elems"])
                for n in sorted(arriving) for pages in [sched.pages_of(n)] for g in (0, 1)
            }
        self.epoch = 0
        self.epochs = []  # per-epoch telemetry, host copies
        self.span_s = defaultdict(float)

    def _fill_pool(self) -> None:
        """Write every allocated page's contents into its frame: one jitted
        call on the device, from the seed."""
        jax, jnp = self.jax, self.jax.numpy
        pool = self.mgr.pool
        page_of_frame = np.full(pool.trash + 1, -1, np.int32)
        owned = np.flatnonzero(pool.frame >= 0)
        page_of_frame[pool.frame[owned]] = owned
        rows = self.cfg["row_elems"]

        @partial(jax.jit, donate_argnums=0)
        def fill(buf, pages, words):
            # block by block into the donated pool, so the pool is never held twice
            n = buf.shape[0]
            block = min(FILL_BLOCK, n)

            def body(i, b):
                lo = jnp.minimum(i * block, n - block)
                pg = jax.lax.dynamic_slice(pages, (lo,), (block,))
                data = content_rows_jnp(words, pg, jnp.zeros_like(pg), rows)
                data = jnp.where((pg >= 0)[:, None], data, 0.0).astype(b.dtype)
                return jax.lax.dynamic_update_slice(b, data, (lo, 0))

            return jax.lax.fori_loop(0, -(-n // block), body, buf)

        words = jnp.asarray(seed_words(self.sched.seed))
        pool.pool = fill(pool.pool, jnp.asarray(page_of_frame), words)
        jax.block_until_ready(pool.pool)

    def _span(self, name: str):
        return _Span(self, name)

    def _arrive(self, name: str, write: bool = True) -> None:
        t = self.sched.tenants[self.sched.index(name)]
        h = self.handles[name] = self.mgr.register(t["t_miss"])
        ids = np.asarray(self.mgr.allocate(h, t["pages"]))
        self.allocs.append(ids)
        if write and self.pool:
            rows = self.arrival_rows[name, self.sched.generation(name, self.epoch) % 2]
            start = self.sched.start[self.sched.index(name)]
            if not np.array_equal(ids, self.sched.pages_of(name)):
                # the manager placed the arrival elsewhere: write what the check expects there
                rows = rows[np.clip(ids - start, 0, t["pages"] - 1)]
            self.mgr.pool.write_pages(ids, rows)

    def _depart(self, name: str) -> None:
        self.mgr.unregister(self.handles.pop(name))  # frees the tenant's pages first

    def step(self) -> None:
        """One epoch (see the module docstring)."""
        jax, mgr, e = self.jax, self.mgr, self.epoch
        gone, come = self.sched.events_at(e)
        if gone or come:
            with self._span("churn"):
                for name in gone:
                    self._depart(name)
                for name in come:
                    self._arrive(name)
        with self._span("record_access"):
            mgr.record_access(self.sched.counts(e))
        with self._span("run_epoch"):
            res = mgr.run_epoch()
        with self._span("pool_sync"):
            jax.block_until_ready(mgr.pool.pool if self.pool else res.stats.fmmr_ewma)
        with self._span("telemetry"):
            st, q = res.stats, res.stats.queue
            small = jax.device_get((st.promoted, st.demoted, st.fmmr_ewma, st.fast_pages,
                                    q.depth, q.enqueued, q.drained_promote, q.drained_demote,
                                    q.cancelled, q.dropped))
            dd = np.asarray(q.drained_demote_ids)
            dp = np.asarray(q.drained_promote_ids)
        self.epochs.append({
            "promoted": small[0].astype(np.int64), "demoted": small[1].astype(np.int64),
            "fmmr": small[2], "fast_pages": small[3].astype(np.int64),
            "queue": np.array([int(x) for x in small[4:]], np.int64),
            "drained_demote": dd[dd >= 0].astype(np.int64),
            "drained_promote": dp[dp >= 0].astype(np.int64),
        })
        self.epoch += 1

    def record(self) -> dict:
        """What the comparison reads, once the window has closed."""
        mgr = self.mgr
        return {
            "allocs": self.allocs, "epochs": self.epochs,
            "tier": np.asarray(mgr.tiers()).astype(np.int8),
            "owner": np.asarray(mgr.owners()).astype(np.int64),
            "counters": mgr.queue_counters(),
            "frame": mgr.pool.frame.copy() if self.pool else None,
            "pool": np.asarray(mgr.pool.pool) if self.pool else None,
        }

    def info(self) -> dict:
        from repro.core.types import state_nbytes

        return {"queue": self.mgr.queue_counters(), "state_nbytes": state_nbytes(self.mgr._state)}

    def moved_pages(self) -> int:
        return self.mgr.pool.moved_pages if self.pool else 0


class _Span:
    def __init__(self, cell: Cell, name: str):
        self.cell, self.name = cell, name

    def __enter__(self):
        self.ann = self.cell.jax.profiler.TraceAnnotation(f"bench.{self.name}")
        self.ann.__enter__()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        self.cell.span_s[self.name] += time.perf_counter() - self.t
        self.ann.__exit__(*exc)


def run_window(cell: Cell, seconds: float) -> dict:
    """Epochs back to back until ``seconds`` have passed; the last epoch
    finishes, and the rate counts all the time and all the epochs."""
    jax = cell.jax
    cell.span_s.clear()
    moved0 = cell.moved_pages()
    e0 = cell.epoch
    attempted = failed = 0
    error = None
    lat = []
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            attempted += 1
            ts = time.perf_counter()
            try:
                cell.step()
            except Exception as exc:  # an epoch that raises ends the window and fails the run
                failed += 1
                error = repr(exc)
                break
            lat.append(time.perf_counter() - ts)
        t1 = time.perf_counter()
    return {
        "attempted": attempted, "failed": failed, "error": error,
        "completed": cell.epoch - e0, "window_s": t1 - t0, "epoch_s": np.asarray(lat),
        "moved_pages": cell.moved_pages() - moved0,
        "span_s": dict(cell.span_s), "first_epoch": e0,
    }


# ------------------------------------------------------------------ comparison
def make_reference(cfg: dict, ftype=np.float32):
    mod = importlib.import_module(f"bench.reference.{cfg['reference']}")
    return mod.Manager(**cfg["manager"], ftype=ftype)


class _Tenants:
    """The reference's slots by tenant name, through arrivals and departures."""

    def __init__(self, ref, sched: Schedule):
        self.ref, self.sched, self.slot = ref, sched, {}

    def arrive(self, name: str) -> np.ndarray:
        t = self.sched.tenants[self.sched.index(name)]
        s = self.slot[name] = self.ref.register(t["t_miss"])
        return self.ref.allocate(s, t["pages"])

    def depart(self, name: str) -> None:
        self.ref.unregister(self.slot.pop(name))

    def events(self, e: int):
        gone, come = self.sched.events_at(e)
        for name in gone:
            self.depart(name)
        return [self.arrive(name) for name in come]


def control_record(sched: Schedule, epochs: int, ftype) -> dict:
    """A run's record made by the reference itself at precision ``ftype``,
    put in the program's place (the control; it has no data plane)."""
    ref = make_reference(sched.cfg, ftype)
    ten = _Tenants(ref, sched)
    rec = {"allocs": [ten.arrive(n) for n in sched.initial()], "epochs": []}
    for e in range(epochs):
        rec["allocs"] += ten.events(e)
        ref.record(sched.counts(e))
        out = ref.epoch()
        rec["epochs"].append({k: out[k] for k in ("promoted", "demoted", "fmmr", "fast_pages", "queue",
                                                  "drained_demote", "drained_promote")})
    rec.update(tier=ref.tier.copy(), owner=ref.owner.copy(), counters=ref.queue_counters(),
               frame=None, pool=None)
    return rec


def compare(sched: Schedule, record: dict, ulps=ULPS) -> Dict[str, float]:
    """The numbers of :data:`CHECKS` for ``record`` (see the module
    docstring). ``ulps`` are the roundings of the reference's divisions a
    selection size may follow; ``(0,)`` compares it exactly."""
    ref = make_reference(sched.cfg)
    ten = _Tenants(ref, sched)
    n = dict.fromkeys(CHECKS, 0)
    n["fmmr_gap"] = 0.0
    n["quota_exact"] = 0  # sizes that differ from the reference's rounded to nearest
    n["quota_past_1ulp"] = 0  # sizes no rounding within one ulp gives
    n["quota_detail"] = []
    allocs = iter(record["allocs"])

    def check_alloc(ids):
        got = next(allocs, None)
        n["alloc_mismatches"] += int(got is None or not np.array_equal(np.asarray(got), ids))

    for name in sched.initial():
        check_alloc(ten.arrive(name))
    for e, run in enumerate(record["epochs"]):
        for ids in ten.events(e):
            check_alloc(ids)
        ref.record(sched.counts(e))
        out = ref.epoch(forced={"promoted": np.asarray(run["promoted"], np.int64),
                                "demoted": np.asarray(run["demoted"], np.int64)}, ulps=ulps)
        for side in ("promoted", "demoted"):
            far = out[side + "_ulps"]
            n["quota_mismatches"] += int((far > max(map(abs, ulps))).sum())
            n["quota_exact"] += int((far > 0).sum())
            n["quota_past_1ulp"] += int((far > 1).sum())
            n["quota_detail"] += [(e, int(t), side, int(out[side][t]), int(out["own_" + side][t]),
                                   int(far[t])) for t in np.flatnonzero(far > 0)]
        gap = np.abs(out["fmmr"] - np.asarray(run["fmmr"], np.float64)).max()
        n["fmmr_gap"] = max(n["fmmr_gap"], float(gap) if np.isfinite(gap) else float("inf"))
        n["holding_mismatches"] += int((out["fast_pages"] != run["fast_pages"]).sum())
        n["queue_mismatches"] += int(not np.array_equal(out["queue"], run["queue"]))
        n["drain_mismatches"] += int(
            not np.array_equal(out["drained_demote"], run["drained_demote"])
            or not np.array_equal(out["drained_promote"], run["drained_promote"]))
    n["alloc_mismatches"] += sum(1 for _ in allocs)  # allocations the reference never made
    n["queue_mismatches"] += int(ref.queue_counters() != record["counters"])
    n["placement_mismatches"] = int((ref.tier != record["tier"]).sum()
                                    + (ref.owner != np.asarray(record["owner"], np.int64)).sum())
    if record.get("pool") is not None:
        last = max(len(record["epochs"]) - 1, 0)
        n["byte_mismatches"] = byte_mismatches(sched, ref, record["frame"], record["pool"], last)
    return n


def byte_mismatches(sched: Schedule, ref, frame: np.ndarray, pool: np.ndarray, last_epoch: int,
                    block: int = 1 << 16) -> int:
    """Pages whose frame is wrong for their tier or shared, or whose bytes
    there differ from what was written to them."""
    F, P = sched.cfg["manager"]["fast_capacity"], sched.cfg["manager"]["num_pages"]
    frame = np.asarray(frame, np.int64)
    owned = ref.owner >= 0
    fast = ref.tier == 1
    bad = owned & (frame < 0)
    bad |= owned & fast & (frame >= F)
    bad |= owned & ~fast & ((frame < F) | (frame >= F + P))
    bad |= ~owned & (frame >= 0)
    used = frame[owned & (frame >= 0)]
    uniq, cnt = np.unique(used, return_counts=True)
    bad |= owned & np.isin(frame, uniq[cnt > 1])
    parity = np.zeros(P, np.int64)
    for t in sched.tenants:
        parity[sched.pages_of(t["name"])] = sched.generation(t["name"], last_epoch) % 2
    check = np.flatnonzero(owned & ~bad)
    rows = sched.cfg["row_elems"]
    words = pool.view(np.uint32) if pool.dtype == np.float32 else None
    for lo in range(0, len(check), block):
        ids = check[lo : lo + block]
        want = content_rows(sched.seed, ids, parity[ids], rows).view(np.uint32)
        got = words[frame[ids]] if words is not None else None
        if got is None:
            bad[ids] = True
        else:
            bad[ids[(got != want).any(axis=1)]] = True
    return int(bad.sum())
