#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the entry points users call.

    python chip_smoke.py [--seed N]     # one chip: manager + serving phases
    python chip_smoke.py --chips 4      # four chips: the sharded fleet only

One chip runs two phases:

  (a) ``CentralManager`` at the paper box's geometry (458,752 pages,
      65,536 fast, 16 tenants) with the queue data plane and a page pool of
      128 f32 per page. Zipf-skewed access counts drawn from the seed drive a
      few ``run_epochs`` chunks; drained migrations move page rows through the
      compiled ``page_move`` kernel. Checks: queue conservation, the pool's
      frame invariants, and that every written sample row reads back
      bit-equal after migration.
  (b) ``repro.launch.serve`` at qwen2.5-3b's published widths (random
      weights from the seed): two tenants, 8 lanes, 512-token prompts, 32 new
      tokens each. Checks: finite logits of the expected shape at every
      step, every request finished, and every KV page a migration plan named
      reads back bit-equal across the move.

``--chips 4`` runs only the path that spans chips: a ``run_sweep`` of 8
paper-geometry machines sharded over 4 devices, against the same sweep on
one device; per-machine results must be identical and the sweep supervisor
must record no fallback.

Timings are host-clock seconds after ``block_until_ready`` (compilation
included) and are information, not metrics. The last line of standard
output is one JSON object naming the device; it is printed only when every
phase ran and every check passed. Without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PAPER_PAGES, PAPER_FAST, PAPER_TENANTS = 458_752, 65_536, 16


def log(msg: str) -> None:
    print(msg, flush=True)


def zipf_counts(rng, owner_pages, accesses: int, num_pages: int, s: float = 1.1):
    """[P] access counts: each tenant's accesses spread Zipf(s) over a random
    permutation of its pages."""
    import numpy as np

    counts = np.zeros(num_pages, np.int64)
    for pages in owner_pages:
        w = 1.0 / np.arange(1, len(pages) + 1) ** s
        counts[rng.permutation(pages)] = rng.multinomial(accesses, w / w.sum())
    return counts


def manager_phase(seed: int, num_pages: int = PAPER_PAGES, fast: int = PAPER_FAST,
                  tenants: int = PAPER_TENANTS, chunks: int = 3, k: int = 4) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.manager import CentralManager

    rng = np.random.default_rng(seed)
    t0 = time.time()
    budget = max(fast // 64, 8)
    mgr = CentralManager(
        num_pages=num_pages, fast_capacity=fast, migration_budget=budget,
        max_tenants=tenants, queue_size=2 * budget, migration_bandwidth=budget,
        data_plane_elems=128, seed=seed,
    )
    # even tenants best-effort, odd latency-sensitive: first-touch gives the
    # low tenants the fast tier, so the later LS tenants must be promoted
    share = num_pages // tenants
    owned = []
    for i in range(tenants):
        h = mgr.register(0.1 if i % 2 else 1.0)
        owned.append(mgr.allocate(h, share))
    counts = zipf_counts(rng, owned, accesses=50 * share, num_pages=num_pages)
    # sample rows: each tenant's hottest pages (likely to move) + random ones
    hot = [p[np.argsort(-counts[p])[:64]] for p in owned]
    sample = np.unique(np.concatenate(hot + [rng.choice(num_pages, 1024, replace=False)]))
    rows = rng.standard_normal((len(sample), 128)).astype(np.float32)
    mgr.pool.write_pages(sample, rows)
    jax.block_until_ready(mgr.pool.pool)
    frames0 = mgr.pool.frame[sample].copy()
    log(f"phase a: setup {time.time() - t0:.3f}s pages={num_pages} fast={fast} "
        f"tenants={tenants} sample_rows={len(sample)}")
    for c in range(chunks):
        t = time.time()
        mgr.run_epochs(k, counts=counts)
        jax.block_until_ready((mgr.pool.pool, mgr.pages.tier))
        log(f"phase a: chunk {c} ({k} epochs) {time.time() - t:.3f}s "
            f"moved_pages_total={mgr.pool.moved_pages}")
    q = mgr.queue_counters()
    assert q["enqueued"] == q["drained"] + q["cancelled"] + q["dropped"] + q["depth"], q
    mgr.pool.check(mgr.tiers())
    got = np.asarray(mgr.pool.pool[jnp.asarray(mgr.pool.frame[sample])])
    assert got.tobytes() == rows.tobytes(), "page rows changed across migration"
    moved = int((mgr.pool.frame[sample] != frames0).sum())
    assert mgr.pool.moved_pages > 0 and moved > 0, "no page moved through page_move"
    log(f"phase a: ok queue={q} sample_rows_moved={moved} "
        f"moved_pages_total={mgr.pool.moved_pages}")
    return {"moved_pages": mgr.pool.moved_pages, "sample_moved": moved}


def serving_phase(seed: int, serve_argv=()) -> dict:
    import numpy as np

    from repro.launch import serve

    t0 = time.time()
    args = serve.parse_args(["--seed", str(seed), *serve_argv])
    cfg, params = serve.load_model(args)
    eng = serve.build_engine(cfg, params, args)
    log(f"phase b: model {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size} kv_dtype={eng.kv.k_pool.dtype} "
        f"kv_pool={eng.kv.k_pool.shape} setup {time.time() - t0:.3f}s")
    seen = {"plans": 0, "pages_checked": 0, "pages_moved": 0, "steps": 0}
    migrate = eng.kv.migrate

    def checked_migrate(plan, manager):
        ids = np.concatenate([np.asarray(plan.promote), np.asarray(plan.demote)])
        ids = np.unique(ids[ids >= 0])
        before = [tuple(a.tobytes() for a in eng.kv.read_page(p)) for p in ids]
        slots = eng.kv.slot_of[ids].copy()
        n = migrate(plan, manager)
        for p, b in zip(ids, before):
            after = tuple(a.tobytes() for a in eng.kv.read_page(p))
            assert after == b, f"KV page {p} changed across migration"
        seen["plans"] += 1
        seen["pages_checked"] += len(ids)
        seen["pages_moved"] += int((eng.kv.slot_of[ids] != slots).sum())
        return n

    eng.kv.migrate = checked_migrate

    def check_logits(e):
        seen["steps"] += 1
        assert e.last_logits.shape == (args.lanes, cfg.vocab_size), e.last_logits.shape
        assert np.isfinite(e.last_logits).all(), "non-finite logits"

    serve.submit_requests(eng, args)
    t = time.time()
    serve.run_to_completion(eng, on_step=check_logits, log=lambda m: log("phase b: " + m))
    log(f"phase b: served {len(eng.finished)} requests in {seen['steps']} decode "
        f"steps {time.time() - t:.3f}s")
    assert len(eng.finished) == args.requests, len(eng.finished)
    assert all(len(r.generated) == args.new_tokens for r in eng.finished)
    assert seen["pages_moved"] > 0, "no KV page moved through page_move"
    log(f"phase b: ok {seen}")
    return seen


def fleet_phase(seed: int, num_pages: int = PAPER_PAGES, fast: int = PAPER_FAST,
                tenants: int = PAPER_TENANTS, machines: int = 8, epochs: int = 16,
                devices: int = 4) -> dict:
    import jax

    from repro.core.scenario import ScenarioSweep, SweepPoint, run_sweep, scale_colocation

    sweep = ScenarioSweep(
        scenario=scale_colocation(num_pages, tenants, epochs),
        points=tuple(SweepPoint(name=f"m{i}", seed=seed + i) for i in range(machines)),
    )
    out = {}
    for n in (devices, 1):
        t = time.time()
        res = run_sweep(
            sweep, num_pages=num_pages, fast_capacity=fast,
            migration_budget=max(fast // 64, 8), max_tenants=tenants,
            queue_size=max(fast // 32, 16), policy_chunk=max(epochs // 4, 1),
            devices=n, dispatch_timeout=900.0,
        )
        assert res.devices == n, (res.devices, n)
        assert res.fallbacks == 0, f"sweep supervisor fell back {res.fallbacks}x"
        out[n] = res.to_jsonable()["machines"]
        log(f"fleet: {machines} machines x {num_pages} pages on {n} device(s) "
            f"{time.time() - t:.3f}s")
    assert out[devices] == out[1], "per-machine results differ between layouts"
    log(f"fleet: ok {machines} machines identical on {devices} devices vs 1 "
        f"(visible devices {len(jax.devices())})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository is not here ({e})", file=sys.stderr)
        return 2
    compile_cache.enable()
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})", file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} visible", file=sys.stderr)
        return 3
    assert not ops.interpret(), "Pallas kernels would run in the interpreter"
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    t0 = time.time()
    if args.chips == 4:
        fleet_phase(args.seed)
    else:
        manager_phase(args.seed)
        serving_phase(args.seed)
    stats = devs[0].memory_stats() or {}
    log(f"total {time.time() - t0:.3f}s peak_hbm_bytes={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
