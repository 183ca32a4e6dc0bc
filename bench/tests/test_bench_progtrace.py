"""The program's spans and tick stages in a trace (``bench/progtrace.py``): on
hand-made events with known answers, on a trace of a small manager recorded on
the CPU, on a small trace recorded on a TPU v5e, and on a small cell run."""
import types
from pathlib import Path

import pytest

import bench_tiny
from bench import devtrace, progtrace
from bench.metrics import host_ms

SCOPED = Path(__file__).parent / "data" / "trace_v5e_arrivals_scoped.json"

# window 0..200, one epoch: a departure (unregister -> free -> snapshot, on_free),
# record_access, run_epoch (dispatch, two fetches, pool.execute), pool_sync
NESTED = {
    "spans": [("bench.window", 0, 200), ("bench.churn", 0, 30), ("bench.record_access", 30, 40),
              ("bench.run_epoch", 40, 150), ("bench.pool_sync", 150, 160)],
    "program": [("maxmem.unregister", 2, 20, None), ("maxmem.free", 4, 18, None),
                ("maxmem.snapshot", 5, 8, None), ("maxmem.pool.on_free", 12, 16, None),
                ("maxmem.record_access", 31, 38, None), ("maxmem.run_epoch", 41, 148, 7),
                ("maxmem.dispatch", 42, 50, 7), ("maxmem.fetch", 52, 100, 7),
                ("maxmem.fetch", 101, 105, 7), ("maxmem.pool.execute", 110, 140, 7)],
    "modules": [("jit__epoch_step_impl(1)", 55, 95), ("jit_page_move(2)", 120, 135)],
    "ops": [("%w", 55, 70), ("%a", 57, 60), ("%b", 70, 95), ("%c", 120, 135), ("%d", 155, 158)],
    # %w is a loop around %a; %b carries no scope
    "scoped_ops": [("tick.bins", 55, 70), ("tick.select", 57, 60), ("tick.unscoped", 70, 95)],
}


def test_idle_goes_to_the_innermost_program_span():
    idle = progtrace.idle_by_program_span(NESTED)
    assert idle == {
        "bench.churn": 2 + 10, "maxmem.unregister": 2 + 2, "maxmem.free": 1 + 4 + 2,
        "maxmem.snapshot": 3, "maxmem.pool.on_free": 4, "bench.record_access": 1 + 2,
        "maxmem.record_access": 7, "bench.run_epoch": 1 + 2,
        "maxmem.run_epoch": 1 + 2 + 1 + 5 + 8, "maxmem.dispatch": 8, "maxmem.fetch": 3 + 5 + 4,
        "maxmem.pool.execute": 10 + 5, "bench.pool_sync": 5 + 2, "bench.window": 40,
    }
    assert sum(idle.values()) == 200 - devtrace.busy_ns(NESTED)
    # the benchmark's own attribution reads the same events as it always has
    assert devtrace.idle_by_span(NESTED) == {
        "bench.churn": 30, "bench.record_access": 10, "bench.run_epoch": 15 + 25 + 15,
        "bench.pool_sync": 5 + 2, "bench.window": 40}
    own = progtrace.self_by_span(NESTED)
    assert own["maxmem.free"] == 14 - 3 - 4 and own["maxmem.fetch"] == 48 + 4
    assert sum(own.values()) == 200
    stages = progtrace.stage_ns(NESTED)
    assert stages == {"tick.bins": 15 - 3, "tick.select": 3, "tick.unscoped": 25}
    held = progtrace.self_by_span(NESTED, *progtrace.epoch_bounds(NESTED, 7))
    assert max(held, key=held.get) == "maxmem.fetch"
    assert progtrace.epoch_bounds(NESTED, 8) is None


def test_program_metrics_on_a_hand_made_trace():
    got = progtrace.metrics(NESTED, completed=1)
    assert got == pytest.approx({
        "epoch_fetch_ms": 12 / 1e6, "pool_execute_ms": 15 / 1e6,
        "control_plane_ms": (4 + 7 + 3 + 4) / 1e6, "tick_bins_ms": 12 / 1e6,
        "tick_select_ms": 3 / 1e6})
    # what the trace does not hold is left out, as for a program without spans or scopes
    assert not {"write_pages_ms", "tick_sample_ms", "tick_queue_ms"} & set(got)
    bare = {k: v for k, v in NESTED.items() if k not in ("program", "scoped_ops")}
    assert progtrace.metrics(bare, completed=1) == {}
    program_idle = 4 + 7 + 3 + 4 + 7 + 17 + 8 + 12 + 15  # the maxmem.* idle above
    assert progtrace.coverage(NESTED) == pytest.approx(
        {"tick_stages": 100 * 15 / 40, "program_idle": 100 * program_idle / (30 + 10 + 55)})


def test_program_spans_of_a_cpu_trace(tmp_path):
    import jax
    import numpy as np

    from repro.core.manager import CentralManager

    mgr = CentralManager(num_pages=256, fast_capacity=32, migration_budget=16, max_tenants=4,
                         queue_size=32, migration_bandwidth=16, sample_period=1,
                         data_plane_elems=128)
    a = mgr.register(0.3)
    mgr.allocate(a, 96)
    counts = np.random.default_rng(0).poisson(3.0, 256).astype(np.uint32)
    mgr.record_access(counts)
    mgr.run_epoch()  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        b = mgr.register(1.0)
        ids = mgr.allocate(b, 40)
        mgr.pool.write_pages(ids, np.ones((len(ids), 128), np.float32))
        for _ in range(2):
            mgr.record_access(counts)
            mgr.run_epoch()
        mgr.unregister(b)
    jax.profiler.stop_trace()
    ev = progtrace.read_xplane(devtrace.find_xplane(str(tmp_path)))
    names = [e[0] for e in ev["program"]]
    read = set(progtrace.CONTROL) | {"maxmem.fetch", "maxmem.pool.execute",
                                     "maxmem.pool.write_pages"}
    assert read | {"maxmem.run_epoch", "maxmem.dispatch", "maxmem.record_access"} <= set(names)
    assert [e[3] for e in ev["program"] if e[0] == "maxmem.run_epoch"] == [1, 2]
    assert not any(n.startswith(progtrace.PROGRAM) for n, _, _ in ev["spans"])

    def within(child, parents):
        return [p for p in ev["program"]
                if p[0] in parents and p[1] <= child[1] and child[2] <= p[2]]

    for e in ev["program"]:
        if e[0] in ("maxmem.dispatch", "maxmem.fetch", "maxmem.pool.execute", "maxmem.segs"):
            (run_epoch,) = within(e, ("maxmem.run_epoch",))
            assert e[3] == run_epoch[3]  # the spans of one epoch share its id
        if e[0] in ("maxmem.pool.on_free", "maxmem.snapshot"):
            assert within(e, ("maxmem.free", "maxmem.allocate", "maxmem.unregister"))
        if e[0] == "maxmem.pool.on_allocate":
            assert within(e, ("maxmem.allocate",))
    assert within(next(e for e in ev["program"] if e[0] == "maxmem.free"), ("maxmem.unregister",))
    assert names.count("maxmem.fetch") == 2 * 8  # queue counts, drained ids, flags; each epoch


def test_recorded_v5e_trace_with_program_spans_and_tick_stages():
    """Eleven epochs of ``paper_fig8.arrivals`` on a TPU v5 lite (epochs 55-65
    of seed 2147483999: GUPS departs at 56 and arrives at 64), read by
    ``progtrace.read_xplane`` with the tick's compiled HLO text; times from
    the window's start."""
    ev = devtrace.load(str(SCOPED))
    epochs = [e[3] for e in ev["program"] if e[0] == "maxmem.run_epoch"]
    assert epochs == list(range(55, 66))
    got = progtrace.metrics(ev, completed=len(epochs))
    assert set(got) == set(progtrace.METRICS)
    assert all(v > 0 for v in got.values()), got
    cover = progtrace.coverage(ev)
    # the six stages hold at least 95% of the tick program's device time
    assert cover["tick_stages"] >= 95
    stages = progtrace.stage_ns(ev)
    tick = devtrace.module_ns(ev, "epoch_step")
    assert sum(stages.get(s, 0) for s in progtrace.STAGES) >= 0.95 * tick
    # the program's spans hold at least 90% of the idle time host_ms reads
    assert cover["program_idle"] >= 90
    idle = progtrace.idle_by_program_span(ev)
    program = sum(v for k, v in idle.items() if k.startswith(progtrace.PROGRAM))
    run = types.SimpleNamespace(trace=ev, window={"completed": len(epochs)})
    assert program >= 0.9 * host_ms.read(run) * 1e6 * len(epochs)
    assert sum(idle.values()) == pytest.approx(devtrace.window_ns(ev) - devtrace.busy_ns(ev))


def test_tick_hlo_names_every_stage():
    """The tick's compiled HLO text maps its ops to all six stages."""
    from bench.drivers import manager_pool
    from bench.generator import build_schedule

    sched = build_schedule(bench_tiny.tiny_config(), bench_tiny.tiny_mix("arrivals"), 2**31 + 3)
    cell = manager_pool.Cell(sched, pool=False)
    stages = progtrace.hlo_stages(progtrace.tick_hlo(cell.mgr))
    assert set(stages.values()) == set(progtrace.STAGES)
    assert all(name.startswith("%") for name in stages)


def test_traced_cell_splits_the_epoch_by_program_span():
    out = progtrace.measure(bench_tiny.tiny_config(), bench_tiny.tiny_mix("arrivals"),
                            seed=2**31 + 7, seconds=1.0)
    assert out["epochs"] > 0 and out["error"] is None
    assert out["compiles"] == {"count": 0, "at": []}  # the warm-up built every program
    spans = out["span_ms"]
    for name in ("maxmem.run_epoch", "maxmem.dispatch", "maxmem.fetch", "maxmem.pool.execute",
                 "maxmem.record_access", "bench.telemetry"):
        assert spans[name]["self"] > 0
    # self times tile the window: per epoch they add up to the window's length
    assert sum(v["self"] for v in spans.values()) == pytest.approx(out["window_ms"])
    assert out["slowest_epoch_spans"][0][1] > 0
    assert out["tick_stage_ms"] == {}  # a CPU trace has no device ops


def test_compiles_in_the_window_are_counted(monkeypatch):
    import jax

    from bench.drivers import manager_pool

    step = manager_pool.Cell.step
    warmup = bench_tiny.tiny_mix("growth")["warmup_epochs"]

    def cold(self):
        if self.epoch == warmup + 2:
            jax.clear_caches()  # every program is built again inside the window
        step(self)

    monkeypatch.setattr(manager_pool.Cell, "step", cold)
    out = progtrace.measure(bench_tiny.tiny_config(), bench_tiny.tiny_mix("growth"),
                            seed=2**31 + 7, seconds=1.0)
    compiles = out["compiles"]
    assert compiles["count"] >= 2
    assert {e for e, _ in compiles["at"]} == {warmup + 2}
    assert any("epoch_step" in fn for _, fn in compiles["at"])
