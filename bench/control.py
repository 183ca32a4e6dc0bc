#!/usr/bin/env python3
"""The control of ``correct``: the plain reference computed in a lower
precision than the deployment states (bfloat16 for its float32 FMMR and
reallocation arithmetic), put in the program's place and judged by the same
comparison as a run. It has to come out not correct.

    python3 bench/control.py --workload <cell> --epochs <n> --seeds <s> [<s> ...]

``--epochs`` counts the cell's warm-up epochs too; give as many as a run
drives. Prints, per seed, every number compared beside its limit, and one
last JSON line with all readings.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell: dict, epochs: int, seed: int, ftype) -> dict:
    from bench.generator import build_schedule, load_json
    from bench.run import driver_of

    cfg = load_json("configs", cell["config"])
    drv = driver_of(cfg)
    sched = build_schedule(cfg, load_json("traffic", cell["traffic"]), seed)
    n = drv.compare(sched, drv.control_record(sched, epochs, ftype))
    n.pop("quota_detail", None)
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--epochs", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    import ml_dtypes

    from bench import check
    from bench.generator import load_json
    from bench.run import driver_of, find, load_benchmark

    cell = find(load_benchmark()["workloads"], args.workload, "workload")
    limits = check.load_limits(cell["name"], driver_of(load_json("configs", cell["config"])).CHECKS)
    out = {}
    for seed in args.seeds:
        t = time.time()
        n = readings(cell, args.epochs, seed, ml_dtypes.bfloat16)
        fails = [k for k in limits if n[k] > limits[k]]
        print(f"seed {seed} ({time.time() - t:.1f} s): "
              + ", ".join(f"{k}={n[k]} (limit {v})" for k, v in limits.items())
              + f" -> correct={not fails}", flush=True)
        out[seed] = n
    print(json.dumps({"workload": args.workload, "epochs": args.epochs,
                      "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
