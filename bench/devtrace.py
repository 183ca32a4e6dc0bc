"""From the profiler's trace to the few event lists the metric readers reduce.

``read_xplane`` keeps three lists, each of ``(name, start_ns, end_ns)``:

* ``ops``: the operations on device 0 (the ``XLA Ops`` line of its plane);
* ``modules``: the compiled programs on device 0 (the ``XLA Modules`` line),
  named after the jitted function (``jit__epoch_step_impl``, ``jit_page_move``);
* ``spans``: the benchmark's own host spans (``bench.*`` annotations).

Device and host events share the profiler's clock. The reductions below are
plain interval arithmetic on those lists, checked on a small recorded trace
by ``bench/tests/test_bench_trace.py``.
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, found {len(paths)}")
    return paths[0]


def _device_plane(planes):
    """Device 0's plane (``/device:TPU:0``), or None in a trace without one."""
    devs = [p for p in planes if re.fullmatch(r"/device:(TPU|GPU):\d+", p.name)]
    devs.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return devs[0] if devs else None


def read_xplane(path: str) -> Dict[str, List[Event]]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    out = {"ops": [], "modules": [], "spans": []}
    dev = _device_plane(planes)
    if dev is not None:
        for line in dev.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key:  # an op's name is its HLO text: keep "%fusion.316" of "%fusion.316 = ..."
                out[key] += [(e.name.split(" = ", 1)[0], e.start_ns, e.end_ns) for e in line.events]
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [(e.name, e.start_ns, e.end_ns) for e in line.events
                                 if e.name.startswith("bench.")]
    for v in out.values():
        v.sort(key=lambda e: e[1])
    return out


def load(path: str) -> Dict[str, List[Event]]:
    with open(path) as fh:
        return {k: [tuple(e) for e in v] for k, v in json.load(fh).items()}


# ------------------------------------------------------------ reductions
def window(events) -> Optional[Tuple[float, float]]:
    w = [e for e in events["spans"] if e[0] == "bench.window"]
    return (w[0][1], w[0][2]) if w else None


def clip(evs: List[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(t, hi)) for n, s, t in evs if t > lo and s < hi]


def union(intervals) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, t in sorted((s, t) for s, t in intervals if t > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(events) -> float:
    lo, hi = window(events)
    return sum(t - s for s, t in union((s, t) for _, s, t in clip(events["ops"], lo, hi)))


def window_ns(events) -> float:
    lo, hi = window(events)
    return hi - lo


def module_ns(events, part: str) -> float:
    """Device time of the programs whose name contains ``part``."""
    lo, hi = window(events)
    return sum(t - s for n, s, t in clip(events["modules"], lo, hi) if part in n)


def idle_gaps(events) -> List[Tuple[float, float]]:
    lo, hi = window(events)
    gaps, at = [], lo
    for s, t in union((s, t) for _, s, t in clip(events["ops"], lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def idle_by_span(events) -> Dict[str, float]:
    """Device-idle nanoseconds of the window, by the step span the host was
    in (the steps follow one another; ``bench.window``: between steps)."""
    lo, hi = window(events)
    steps = [e for e in clip(events["spans"], lo, hi) if e[0] != "bench.window"]
    out: Dict[str, float] = defaultdict(float)
    k = 0
    for a, b in idle_gaps(events):
        while k < len(steps) and steps[k][2] <= a:
            k += 1
        inside, j = 0.0, k
        while j < len(steps) and steps[j][1] < b:
            ov = min(b, steps[j][2]) - max(a, steps[j][1])
            if ov > 0:
                out[steps[j][0]] += ov
                inside += ov
            j += 1
        out["bench.window"] += (b - a) - inside
    return dict(out)


def top_ops(events, k: int = 10) -> List[Tuple[str, float]]:
    lo, hi = window(events)
    tot: Dict[str, float] = defaultdict(float)
    for n, s, t in clip(events["ops"], lo, hi):
        tot[n] += t - s
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]
