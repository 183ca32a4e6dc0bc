"""The reduction from trace events to per-layer metrics, on a hand-made trace
with known answers and on a small trace recorded on a TPU v5e."""
import types
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on the path)
from bench import devtrace
from bench.metrics import host_ms, idle_share, page_move_ms, page_move_roofline, tick_ms

RECORDED = Path(__file__).parent / "data" / "trace_v5e_shift.json"

# window 0..100; two epochs: record_access 0-10, run_epoch 10-60, 60-100 idle host
HAND = {
    "spans": [("bench.window", 0, 100), ("bench.record_access", 0, 10), ("bench.run_epoch", 10, 45),
              ("bench.record_access", 50, 55), ("bench.run_epoch", 55, 95)],
    "modules": [("jit__epoch_step_impl(1)", 12, 40), ("jit_page_move(2)", 41, 44),
                ("jit__epoch_step_impl(1)", 57, 85), ("jit_page_move(2)", 86, 90)],
    "ops": [("%a", 12, 30), ("%b", 25, 40), ("%c", 41, 44), ("%a", 57, 85), ("%c", 86, 90)],
}


def _run(events, completed, moved=0):
    return types.SimpleNamespace(
        trace=events, window={"completed": completed, "moved_pages": moved},
        cfg={"row_elems": 128}, peaks={"hbm_bytes_per_s": 819e9})


def test_hand_made_trace():
    assert devtrace.union([(12, 30), (25, 40), (41, 44)]) == [(12, 40), (41, 44)]
    assert devtrace.busy_ns(HAND) == 28 + 3 + 28 + 4
    assert devtrace.idle_gaps(HAND) == [(0, 12), (40, 41), (44, 57), (85, 86), (90, 100)]
    idle = devtrace.idle_by_span(HAND)
    assert idle == {"bench.record_access": 10 + 5, "bench.run_epoch": 2 + 1 + 1 + 2 + 1 + 5,
                    "bench.window": 5 + 5}
    assert sum(idle.values()) == 100 - devtrace.busy_ns(HAND)
    run = _run(HAND, completed=2, moved=1000)
    assert tick_ms.read(run) == pytest.approx((28 + 28) / 2 / 1e6)
    assert page_move_ms.read(run) == pytest.approx((3 + 4) / 2 / 1e6)
    assert idle_share.read(run) == pytest.approx(100 - 63)
    assert host_ms.read(run) == pytest.approx((15 + 12) / 2 / 1e6)
    least = 2 * 1000 * 512 / 819e9
    assert page_move_roofline.read(run) == pytest.approx(100 * least / 7e-9)
    assert devtrace.top_ops(HAND)[0] == ("%a", 46)


def test_recorded_v5e_trace():
    ev = devtrace.load(str(RECORDED))
    lo, hi = devtrace.window(ev)
    epochs = sum(1 for n, s, t in ev["spans"] if n == "bench.run_epoch" and lo <= s and t <= hi)
    assert epochs >= 3
    busy = devtrace.busy_ns(ev)
    assert 0 < busy < devtrace.window_ns(ev)
    ticks = [t - s for n, s, t in devtrace.clip(ev["modules"], lo, hi) if "epoch_step" in n]
    assert len(ticks) >= epochs - 1
    # the tick program runs inside the run_epoch spans, and its ops inside the program
    assert devtrace.module_ns(ev, "epoch_step") <= busy * 1.01
    idle = devtrace.idle_by_span(ev)
    assert sum(idle.values()) == pytest.approx(devtrace.window_ns(ev) - busy)
    run = _run(ev, completed=epochs, moved=1024 * epochs)
    assert 0 < idle_share.read(run) < 100
    assert 0 < page_move_roofline.read(run) < 100


def test_xplane_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.run_epoch"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = devtrace.read_xplane(devtrace.find_xplane(str(tmp_path)))
    assert [n for n, _, _ in ev["spans"]].count("bench.run_epoch") == 2
    assert devtrace.window(ev) is not None
    assert ev["ops"] == [] and ev["modules"] == []  # a CPU trace has no device plane
