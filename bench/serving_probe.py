#!/usr/bin/env python3
"""Probes of a serving cell on the chip, beside ``bench/run.py``.

    python3 bench/serving_probe.py --workload <cell> --seed <n> --seconds <s> --kv-dtype float8_e4m3fn
    python3 bench/serving_probe.py --workload <cell> --seed <n> --seconds <s> --rates 1.5,2.5,3.5
    python3 bench/serving_probe.py --workload <cell> --seed <n> --seconds <s> --split

With ``--kv-dtype`` it is the control: the cell run as ``bench/run.py``
runs it, its KV pool stored in that dtype (lower than the configuration
states), compared with the reference under the cell's limits; it must come
out not correct. Its result line is ``bench/run.py``'s.

With ``--rates`` it is the sweep that finds the highest request rate the
engine sustains: after the warm-up, one window of ``--seconds`` per rate, in
the order given, arrivals Poisson at that rate from the window's start. Per
rate it prints one JSON line: requests arrived and finished per second, the
queue before and after, and epochs per second. A rate is sustained while
the queue does not grow.

With ``--split`` it runs the window under the profiler and prints where an
epoch's time goes, in ms per epoch on the trace's clock: ``module_ms``, the
device time of each compiled program (``jit_paged_decode_step``,
``jit_paged_prefill``, the tick's ``jit__epoch_step_impl``, ``jit_page_move``,
...); ``span_idle_ms``, the device-idle time given to the innermost host
span open (the engine's ``serve.*`` and the manager's ``maxmem.*`` spans,
else the benchmark's ``bench.step``); ``span_self_ms``, each span's host
time less its child spans; and the window's engine counters.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kv-dtype")
    ap.add_argument("--rates")
    ap.add_argument("--split", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, run
    from bench.generator import build_schedule, load_json
    from repro.launch import compile_cache

    bench = run.load_benchmark()
    cell = run.find(bench["workloads"], args.workload, "workload")
    cfg, mix = load_json("configs", cell["config"]), load_json("traffic", cell["traffic"])
    drv = run.driver_of(cfg)
    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("serving_probe: no TPU", file=sys.stderr)
        return 3
    if args.kv_dtype:
        cfg["serving"] = dict(cfg["serving"], kv_dtype=args.kv_dtype)
        limits = check.load_limits(cell["name"], drv.CHECKS)
        peaks = run.load_peaks(jax.devices()[0].device_kind)
        res = run.measure(cell["name"], cfg, mix, [], args.seed, args.seconds, False, T_START,
                          limits=limits, peaks=peaks)
        print(json.dumps(res), flush=True)
        return 0
    sched = build_schedule(cfg, mix, args.seed)
    c = drv.Cell(sched)
    for _ in range(sched.warmup_epochs):
        c.step()
    if args.split:
        print(json.dumps(split(drv, c, args.seconds)), flush=True)
        return 0
    for rate in map(float, args.rates.split(",")):
        c.requests.gap, c.requests.next_at = 1.0 / rate, 0.0
        before, queued = c.tally(), len(c.eng.queue)
        taken = sum(c.requests.taken.values())
        win = drv.run_window(c, args.seconds)
        after = c.tally()
        s = win["window_s"]
        print(json.dumps({"rate_per_s": rate, "window_s": s,
                          "arrived_per_s": (sum(c.requests.taken.values()) - taken) / s,
                          "finished_per_s": (after["finished"] - before["finished"]) / s,
                          "queue_before": queued, "queue_after": len(c.eng.queue),
                          "epochs_per_s": win["completed"] / s,
                          "tokens_decoded_per_s": (after["tokens_decoded"] - before["tokens_decoded"]) / s,
                          "compiles": win["compiles"]}), flush=True)
    return 0


def split(drv, c, seconds: float) -> dict:
    """The ``--split`` table (module docstring)."""
    import re
    import shutil
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from bench import devtrace, progtrace

    logdir = tempfile.mkdtemp(prefix="serving_split_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        win = drv.run_window(c, seconds)
    finally:
        jax.profiler.stop_trace()
    path = devtrace.find_xplane(logdir)
    events = devtrace.read_xplane(path)
    events["program"] = sorted(
        ((e.name, e.start_ns, e.end_ns, None) for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:") for line in plane.lines for e in line.events
         if e.name.startswith(("serve.", "maxmem."))), key=lambda e: e[1])
    shutil.rmtree(logdir, ignore_errors=True)
    n = max(win["completed"], 1)
    lo, hi = devtrace.window(events)
    modules = {}
    for name, s, t in devtrace.clip(events["modules"], lo, hi):
        key = re.sub(r"\(\d+\)$", "", name)
        modules[key] = modules.get(key, 0.0) + (t - s) / 1e6 / n
    per = lambda d: {k: v / 1e6 / n for k, v in sorted(d.items(), key=lambda kv: -kv[1])}  # noqa: E731
    return {"epochs": win["completed"], "window_ms": (hi - lo) / 1e6 / n,
            "busy_ms": devtrace.busy_ns(events) / 1e6 / n,
            "module_ms": dict(sorted(modules.items(), key=lambda kv: -kv[1])),
            "span_idle_ms": per(progtrace.idle_by_program_span(events)),
            "span_self_ms": per(progtrace.self_by_span(events)),
            "serve": win["serve"], "compiles": win["compiles"]}


if __name__ == "__main__":
    sys.exit(main())
