#!/usr/bin/env python3
"""One cell of the on-chip benchmark of the MaxMem manager.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a deployment,
``bench/configs/<config>.json``, and a traffic mix, ``bench/traffic/<mix>.json``;
``bench/generator.py`` draws the cell's whole schedule from the seed before any
epoch runs, spreading each tenant's accesses by its law, ``bench/laws/<kind>.py``.
The deployment names its driver, ``bench/drivers/<driver>.py``: set-up builds
the deployment through the program's own calls, fills every page's contents on
the device, and runs the mix's warm-up epochs, which compile every program the
window runs. The window then runs epochs back to back for ``--seconds``. With
``--trace 1`` the window runs under the profiler and the per-layer metrics are
read from its trace; with ``--trace 0`` the end-to-end metrics are reported.
Each metric is read by ``bench/metrics/<name>.py``.

After the window, the driver replays the schedule through the plain reference
the deployment names and ``bench/check.py`` decides ``correct``; each number
compared is printed with its limit (``bench/checks/<cell>.json``) as the last
lines on standard error and under ``checks`` in the result. The last line of
standard output is one JSON object. Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end metrics,
    or with a trace its per-layer ones."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_peaks(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def driver_of(cfg: dict):
    """The driver ``bench/drivers/<driver>.py`` the configuration names."""
    return importlib.import_module(f"bench.drivers.{cfg['driver']}")


def measure(cell: str, cfg: dict, mix: dict, metrics: list, seed: int, seconds: float,
            trace: bool, t_start: float, limits=None, peaks=None, log=None) -> dict:
    """Run one cell and return the result line's object."""
    import jax
    import numpy as np

    from bench import check, devtrace
    from bench.generator import build_schedule

    drv = driver_of(cfg)
    log = log or drv.log
    limits = limits or check.load_limits(cell, drv.CHECKS)
    marks = {"start": time.time()}
    sched = build_schedule(cfg, mix, seed)
    marks["schedule"] = time.time()
    c = drv.Cell(sched)
    marks["deployment"] = time.time()
    for _ in range(sched.warmup_epochs):
        c.step()
    marks["warmup"] = time.time()
    setup_s = marks["warmup"] - t_start
    names = list(marks)
    phases = {"process": marks["start"] - t_start,
              **{b: marks[b] - marks[a] for a, b in zip(names, names[1:])}}
    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        win = drv.run_window(c, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    devs = jax.devices()
    stats = [d.memory_stats() or {} for d in devs]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0) or None
    events = None
    if trace:
        events = devtrace.read_xplane(devtrace.find_xplane(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
    info = {
        "epochs": win["completed"], "warmup_epochs": sched.warmup_epochs, "setup_phases_s": phases,
        "moved_pages": win["moved_pages"], **c.info(),
        "epoch_ms": {q: float(np.percentile(win["epoch_s"], q)) * 1e3 for q in (50, 95, 100)}
        if len(win["epoch_s"]) else {},
        "slowest_epoch": int(np.argmax(win["epoch_s"])) + win["first_epoch"] if len(win["epoch_s"]) else None,
        "span_ms_per_epoch": {k: v * 1e3 / max(win["completed"], 1) for k, v in win["span_s"].items()},
        "bench_ms_per_epoch": (win["window_s"] - sum(win["span_s"].values())) * 1e3 / max(win["completed"], 1),
        "error": win["error"],
    }
    rec = c.record()
    del c
    gc.collect()
    numbers = drv.compare(sched, rec)
    del rec
    for k in sorted(set(numbers) - set(limits)):  # what the driver reports besides its checks
        v = numbers.pop(k)
        info[k] = sorted(v, key=lambda d: -d[-1])[:20] if isinstance(v, list) else v
    correct = check.verdict(numbers, limits) and win["failed"] == 0 and win["completed"] > 0
    run = types.SimpleNamespace(cfg=cfg, window=win, trace=events, setup_s=setup_s,
                                memory_peak_bytes=peak, peaks=peaks)
    out_metrics = {}
    if devs[0].platform == "tpu":  # a CPU run never reports a device metric
        for m in metrics:
            v = importlib.import_module(f"bench.metrics.{m['name']}").read(run)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": win["attempted"], "failed": win["failed"],
              "metrics": out_metrics, "device": device}
    if events is not None and devtrace.window(events) is not None:
        device["busy_s"] = devtrace.busy_ns(events) / 1e9
        device["window_s"] = devtrace.window_ns(events) / 1e9
        idle = sorted(devtrace.idle_by_span(events).items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[n, ns / 1e9] for n, ns in devtrace.top_ops(events)],
            "idle_gaps": [[n, ns / 1e9] for n, ns in idle],
        }
    result["checks"] = check.lines(numbers, limits)
    log("info " + json.dumps(info, default=int))
    for k, v in result["checks"].items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import check
        from bench.generator import load_json
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"bench: the system under test is not here ({e})", file=sys.stderr)
        return 2
    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    cfg = load_json("configs", cell["config"])
    mix = load_json("traffic", cell["traffic"])
    limits = check.load_limits(cell["name"], driver_of(cfg).CHECKS)
    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devs[0].platform!r})", file=sys.stderr)
        return 3
    if len(devs) < cell["chips"]:
        print(f"bench: the cell needs {cell['chips']} chips, {len(devs)} visible", file=sys.stderr)
        return 3
    peaks = load_peaks(devs[0].device_kind)
    result = measure(cell["name"], cfg, mix, cell_metrics(bench, cell["name"], bool(args.trace)),
                     args.seed, args.seconds, bool(args.trace), T_START, limits=limits, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
