#!/usr/bin/env python3
"""Where the program's selection sizes differ from the reference's, and on
which backend: the program's policy runs the cell's schedule on the backend
JAX has (the chip, or the CPU with ``JAX_PLATFORMS=cpu``), at the cell's full
size and without the page pool, and is compared with the reference exactly
(its divisions rounded to nearest) and under the bracket of roundings the
cell's check allows (``ULPS`` of the driver: up to two ulps either way).

    python3 bench/witness.py --workload <cell> --epochs <n> --seeds <s> [<s> ...]

Prints one JSON line per seed: the backend, ``quota_exact`` (sizes that
differ from the reference's rounded to nearest), ``quota_past_1ulp`` (sizes no
rounding within one ulp gives), ``quota_mismatches`` (sizes no rounding in the
bracket gives), ``fmmr_gap``, and each differing size as (epoch, tenant, side,
program, reference, the fewest ulps that give it; 99 for none).

    python3 bench/witness.py --divide <n>

divides ``n`` pairs of float32 drawn from a fixed seed with ``jnp.divide``
on the backend and prints how many quotients lie 0, 1, 2 or more ulps from
the correctly rounded ones numpy gives.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--epochs", type=int)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--divide", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    if args.divide:
        return divide(args.divide)

    from bench.generator import build_schedule, load_json
    from bench.run import driver_of, find, load_benchmark
    from repro.launch import compile_cache

    compile_cache.enable()
    cell = find(load_benchmark()["workloads"], args.workload, "workload")
    cfg, mix = load_json("configs", cell["config"]), load_json("traffic", cell["traffic"])
    drv = driver_of(cfg)
    for seed in args.seeds:
        t = time.time()
        sched = build_schedule(cfg, mix, seed)
        c = drv.Cell(sched, pool=False)
        for _ in range(args.epochs):
            c.step()
        rec = c.record()
        del c
        n = drv.compare(sched, rec)
        print(json.dumps({
            "backend": jax.devices()[0].platform, "workload": args.workload, "seed": seed,
            "epochs": args.epochs, "seconds": round(time.time() - t, 1),
            **{k: n[k] for k in ("quota_exact", "quota_past_1ulp", "quota_mismatches", "fmmr_gap",
                                 "holding_mismatches", "drain_mismatches", "placement_mismatches")},
            "detail": n["quota_detail"][:40],
        }), flush=True)
    return 0


def divide(n: int) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    x = rng.integers(1, 1 << 17, n).astype(np.float32)  # counts and sums, as the policy has them
    y = rng.integers(1, 1 << 17, n).astype(np.float32)
    x[: n // 2] *= rng.random(n // 2, dtype=np.float32)  # and fractions
    want = np.divide(x, y)
    got = np.asarray(jax.jit(jnp.divide)(jnp.asarray(x), jnp.asarray(y)))
    ulps = np.abs(got.view(np.int32).astype(np.int64) - want.view(np.int32).astype(np.int64))
    print(json.dumps({"backend": jax.devices()[0].platform, "pairs": n,
                      "ulps": {str(k): int((np.minimum(ulps, 3) == k).sum()) for k in range(4)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
