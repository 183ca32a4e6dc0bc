#!/usr/bin/env python3
"""The program's own marks in a device trace, and the per-layer numbers they give.

    python3 bench/progtrace.py --workload <cell> --seed <n> --seconds <s>

runs a cell's set-up and window as ``bench/run.py --trace 1`` does, under the
profiler, and prints one JSON object on standard output. Besides what
``bench/devtrace.py`` keeps (device ops and programs, the benchmark's
``bench.*`` spans), it reads two marks the program puts in the trace:

* ``program``: the manager's host spans (``maxmem.*`` annotations,
  ``core/manager.py`` and ``core/dataplane.py``), each as ``(name, start_ns,
  end_ns, epoch)``: ``epoch`` is the ``epoch`` the enclosing
  ``maxmem.run_epoch`` span carries (the manager's epoch index), or None
  outside one;
* ``scoped_ops``: each op of the policy tick's program as ``(stage, start_ns,
  end_ns)``, ``stage`` the ``tick.<stage>`` named scope of the op
  (``core/policy.py``), or ``tick.unscoped`` where it has none.

A TPU's trace names each op by its HLO text without metadata, and its stats
hold no ``op_name``, so the stage is looked up by the op's name in the tick's
compiled HLO text (``hlo_stages``), taken after the window from the program
the manager ran (``tick_hlo``). A fusion carries the metadata of its root op,
so it counts for that op's stage.

The printed object holds:

* ``metrics``: the numbers of ``METRICS``, each in ms per epoch: device time
  of a tick stage (``tick_*_ms``), or device-idle time given to program spans
  (the rest), each idle nanosecond to the innermost program span open on the
  host at that moment (``idle_by_program_span``);
* ``window_ms``: the window's length per epoch on the trace's clock;
* ``coverage``: the share of ``tick_ms`` (the tick program's device time)
  that the six stages hold, and the share of ``host_ms`` (device idle inside
  the benchmark's call spans) that the program's spans hold, in %;
* ``tick_stage_ms``: device ms per epoch of each stage, ``tick.unscoped``
  included;
* ``span_ms``: for each span, the benchmark's and the program's, the
  device-idle ms per epoch given to it as the innermost span (``idle``) and
  its host time per epoch less its child spans (``self``);
* ``compiles``: the programs compiled or loaded inside the window, with the
  epoch and function of each (``at``): a warm window has none;
* ``slowest_epoch`` (from the host clock) and ``slowest_epoch_spans``, the
  three spans that held the most host time around it, in ms.

``bench/run.py`` reports none of these: its result line reads only what
``devtrace.read_xplane`` keeps.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

PROGRAM = "maxmem."  # prefix of the manager's own host spans
TICK = "epoch_step"  # part of the tick program's name (as ``tick_ms`` reads it)
STAGES = ("tick.sample", "tick.bins", "tick.fmmr", "tick.select", "tick.queue", "tick.sentinel")
UNSCOPED = "tick.unscoped"
# the jax.monitoring event of each program built (compiled, or loaded from the cache)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the benchmark's spans around the manager's calls, whose idle ``host_ms`` reads
CALLS = ("bench.churn", "bench.record_access", "bench.run_epoch")
_STAGE = re.compile(r"\btick\.(?:sample|bins|fmmr|select|queue|sentinel)\b")
_HLO_OP = re.compile(r'^\s*(?:ROOT )?(%[\w.\-]+) = .*?op_name="([^"]*)"', re.M)

CONTROL = ("maxmem.register", "maxmem.unregister", "maxmem.allocate", "maxmem.free",
           "maxmem.snapshot", "maxmem.segs", "maxmem.pool.on_allocate", "maxmem.pool.on_free")
# name: ("stage", tick stage) for device time, or ("idle", program spans) for device idle
METRICS = {
    "tick_sample_ms": ("stage", "tick.sample"),
    "tick_bins_ms": ("stage", "tick.bins"),
    "tick_select_ms": ("stage", "tick.select"),
    "tick_queue_ms": ("stage", "tick.queue"),
    "epoch_fetch_ms": ("idle", ("maxmem.fetch",)),
    "pool_execute_ms": ("idle", ("maxmem.pool.execute",)),
    "control_plane_ms": ("idle", CONTROL),
    "write_pages_ms": ("idle", ("maxmem.pool.write_pages",)),
}


# ------------------------------------------------------------------ reading
def hlo_stages(text: str) -> Dict[str, str]:
    """``{"%name": "tick.<stage>"}`` for each instruction of a compiled HLO
    text whose metadata names a tick stage."""
    out = {}
    for m in _HLO_OP.finditer(text):
        stage = _STAGE.search(m.group(2))
        if stage:
            out[m.group(1)] = stage.group(0)
    return out


def read_xplane(path: str, hlo: str = "") -> dict:
    """``devtrace.read_xplane``'s lists of an ``.xplane.pb``, and ``program``
    and ``scoped_ops``; ``hlo`` is the tick's compiled HLO text."""
    from jax.profiler import ProfileData

    out = devtrace.read_xplane(path)
    stage_of = hlo_stages(hlo)
    ticks = [(s, t) for n, s, t in out["modules"] if TICK in n]
    out["scoped_ops"] = [(stage_of.get(n, UNSCOPED), s, t) for n, s, t in out["ops"]
                         if _inside((n, s, t), ticks)]
    program = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM):
                        epoch = None
                        if e.name == PROGRAM + "run_epoch":
                            epoch = int(dict(e.stats)["epoch"])
                        program.append((e.name, e.start_ns, e.end_ns, epoch))
    program.sort(key=lambda e: e[1])
    out["program"] = _inherit_epochs(program)
    return out


def _inside(event, intervals) -> bool:
    """Whether ``event`` starts inside one of the sorted, disjoint ``intervals``."""
    k = bisect.bisect_right(intervals, (event[1], float("inf"))) - 1
    return k >= 0 and event[1] < intervals[k][1]


def _inherit_epochs(program):
    """Give each span the epoch of the ``maxmem.run_epoch`` span it nests in."""
    out, open_ = [], []  # open_: (end, epoch) of the run_epoch spans around
    for n, s, t, epoch in program:
        while open_ and open_[-1][0] <= s:
            open_.pop()
        if n == PROGRAM + "run_epoch":
            open_.append((t, epoch))
        out.append((n, s, t, epoch if epoch is not None or not open_ else open_[-1][1]))
    return out


def tick_hlo(mgr) -> str:
    """The compiled HLO text of the policy tick ``mgr`` runs: the names of the
    ops a device trace shows, each with the metadata of its ``tick.<stage>``
    scope. Built again from the same arguments, it is the program the window
    ran (loaded from the compilation cache where one is on)."""
    from repro.core import policy

    return policy._jitted_epoch_step().lower(
        mgr._state, mgr.params, max_tenants=mgr.max_tenants, plan_size=mgr.plan_size,
        exact_sampling=mgr.exact_sampling, count_clamp=policy.COUNT_CLAMP,
    ).compile().as_text()


# --------------------------------------------------------------- reductions
def _innermost(events, lo: float, hi: float, outer) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` pieces that tile ``[lo, hi]``: each piece goes to
    the innermost of the nested ``events`` open there (of two that overlap
    without nesting, the later from its start on), or to ``outer`` where none
    is."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []  # (name, end) of the events open at ``at``
    at = lo

    def emit(upto, name):
        nonlocal at
        if upto > at:
            segs.append((at, upto, name))
            at = upto

    clipped = [(e[0], max(e[1], lo), min(e[2], hi)) for e in events
               if e[2] > lo and e[1] < hi and e[2] > e[1]]
    for n, s, t in sorted(clipped, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            emit(*stack.pop()[::-1])
        while stack and stack[-1][1] < t:  # overlaps without nesting: it ends the open one
            emit(s, stack.pop()[0])
        emit(s, stack[-1][0] if stack else outer)
        stack.append((n, t))
    while stack:
        emit(*stack.pop()[::-1])
    emit(hi, outer)
    return segs


def _overlap_by_name(segs, intervals) -> Dict[str, float]:
    """Nanoseconds of the sorted, disjoint ``intervals`` inside each named piece."""
    out: Dict[str, float] = defaultdict(float)
    k = 0
    for a, b in intervals:
        while k < len(segs) and segs[k][1] <= a:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < b:
            ov = min(b, segs[j][1]) - max(a, segs[j][0])
            if ov > 0:
                out[segs[j][2]] += ov
            j += 1
    return dict(out)


def host_pieces(events, lo: Optional[float] = None, hi: Optional[float] = None):
    """The window (or ``[lo, hi]``) cut by the innermost host span open in
    each piece: a program span, else the benchmark's step span around it,
    else ``bench.window``."""
    wlo, whi = devtrace.window(events)
    steps = [e[:3] for e in events["spans"] if e[0] != "bench.window"]
    steps += [e[:3] for e in events.get("program", [])]
    return _innermost(steps, wlo if lo is None else lo, whi if hi is None else hi, "bench.window")


def idle_by_program_span(events) -> Dict[str, float]:
    """Device-idle nanoseconds of the window, each given to the innermost
    program span (``maxmem.*``) open on the host at that moment, else to the
    benchmark's step span around it, else to ``bench.window``."""
    return _overlap_by_name(host_pieces(events), devtrace.idle_gaps(events))


def self_by_span(events, lo: Optional[float] = None,
                 hi: Optional[float] = None) -> Dict[str, float]:
    """Host self time of each span over the window (or ``[lo, hi]``): its
    duration less the part its child spans cover."""
    out: Dict[str, float] = defaultdict(float)
    for s, t, n in host_pieces(events, lo, hi):
        out[n] += t - s
    return dict(out)


def stage_ns(events) -> Dict[str, float]:
    """Device nanoseconds of the tick's ops by ``tick.<stage>`` scope
    (``tick.unscoped`` for ops without one); an op nested in another (the
    body of a loop) counts once, for the innermost."""
    lo, hi = devtrace.window(events)
    out: Dict[str, float] = defaultdict(float)
    for s, t, n in _innermost(events.get("scoped_ops", []), lo, hi, None):
        if n is not None:
            out[n] += t - s
    return dict(out)


def epoch_bounds(events, epoch: int) -> Optional[Tuple[float, float]]:
    """The host interval around the manager's epoch ``epoch``: from the end
    of the previous epoch's ``maxmem.run_epoch`` span to the start of the
    next one's (the window's ends where there is none), so the epoch's steps
    before and after its tick are in. None where the trace holds no such
    epoch."""
    runs = [e for e in events.get("program", []) if e[0] == PROGRAM + "run_epoch"]
    k = next((i for i, e in enumerate(runs) if e[3] == epoch), None)
    if k is None:
        return None
    lo, hi = devtrace.window(events)
    a = runs[k - 1][2] if k > 0 else lo
    b = runs[k + 1][1] if k + 1 < len(runs) else hi
    return max(a, lo), min(b, hi)


def metrics(events, completed: int) -> Dict[str, float]:
    """``METRICS`` in ms per epoch over ``completed`` epochs; a metric whose
    stage or spans the trace does not hold is left out."""
    stages, idle = stage_ns(events), idle_by_program_span(events)
    held = {e[0] for e in events.get("program", [])}
    out = {}
    for name, (kind, what) in METRICS.items():
        if kind == "stage" and stages.get(what):
            out[name] = stages[what] / 1e6 / completed
        elif kind == "idle" and held & set(what):
            out[name] = sum(idle.get(n, 0.0) for n in what) / 1e6 / completed
    return out


def coverage(events) -> Dict[str, Optional[float]]:
    """In %: the share of the tick program's device time that the six stages
    hold, and the share of the device idle inside the benchmark's call spans
    (what ``host_ms`` reads) that the program's spans hold."""
    tick = devtrace.module_ns(events, TICK)
    stages = stage_ns(events)
    calls = sum(devtrace.idle_by_span(events).get(c, 0.0) for c in CALLS)
    program = sum(v for k, v in idle_by_program_span(events).items() if k.startswith(PROGRAM))
    return {"tick_stages": 100 * sum(stages.get(s, 0.0) for s in STAGES) / tick if tick else None,
            "program_idle": 100 * program / calls if calls else None}


def tables(events, completed: int, slowest: Optional[int]) -> dict:
    """Device ms per epoch of each tick stage; for each host span the
    device-idle ms per epoch given to it and its host self time per epoch;
    and the three spans that held the most host time around the epoch
    ``slowest``, most first."""
    per = 1e6 * max(completed, 1)
    idle = idle_by_program_span(events)
    own = self_by_span(events)
    out = {
        "tick_stage_ms": {k: v / per for k, v in sorted(stage_ns(events).items())},
        "span_ms": {k: {"idle": idle.get(k, 0.0) / per, "self": own[k] / per} for k in sorted(own)},
    }
    bounds = None if slowest is None else epoch_bounds(events, slowest)
    if bounds:
        held = self_by_span(events, *bounds)
        out["slowest_epoch_spans"] = [[n, ns / 1e6] for n, ns in
                                      sorted(held.items(), key=lambda kv: -kv[1])[:3]]
    return out


# --------------------------------------------------------------------- run
def measure(cfg: dict, mix: dict, seed: int, seconds: float) -> dict:
    """Set up a cell and run its window under the profiler; the object the
    module docstring describes."""
    import jax
    import numpy as np

    from bench import run
    from bench.generator import build_schedule

    drv = run.driver_of(cfg)
    sched = build_schedule(cfg, mix, seed)
    c = drv.Cell(sched)
    for _ in range(sched.warmup_epochs):
        c.step()
    compiles = []  # [epoch, function] of each program compiled (or loaded) inside the window

    def on_compile(event, duration_secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append([c.epoch, kw.get("fun_name")])

    logdir = tempfile.mkdtemp(prefix="bench_progtrace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        win = drv.run_window(c, seconds)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.profiler.stop_trace()
    try:
        events = read_xplane(devtrace.find_xplane(logdir), tick_hlo(c.mgr))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    n = win["completed"]
    slowest = int(np.argmax(win["epoch_s"])) + win["first_epoch"] if len(win["epoch_s"]) else None
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "epochs": n, "error": win["error"],
           "compiles": {"count": len(compiles), "at": compiles[:20]}, "slowest_epoch": slowest}
    if n and devtrace.window(events) is not None:
        out.update(window_ms=devtrace.window_ns(events) / 1e6 / n, metrics=metrics(events, n),
                   coverage=coverage(events), **tables(events, n, slowest))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import run
    from bench.generator import load_json
    from repro.launch import compile_cache

    cell = run.find(run.load_benchmark()["workloads"], args.workload, "workload")
    compile_cache.enable()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = measure(load_json("configs", cell["config"]), load_json("traffic", cell["traffic"]),
                  args.seed, args.seconds)
    out["wall_s"] = time.time() - T_START
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
