"""``correct`` on a small deployment on the CPU: a sound run passes, and the
control and each fault the cells can have make it come out false."""
import json

import jax
import ml_dtypes
import numpy as np
import pytest

import bench_tiny
from bench import check
from bench.drivers import manager_pool
from bench.generator import build_schedule

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("mix", ["growth", "arrivals"])
def test_sound_run_is_correct_and_prints_no_device_metric(mix):
    cell = bench_tiny.CELLS[mix]
    res, lines = bench_tiny.measure(bench_tiny.tiny_config(), bench_tiny.tiny_mix(mix), cell=cell)
    assert list(res) == KEYS  # checks come last
    json.dumps(res)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["metrics"] == {}
    assert all(v["value"] == 0 for v in res["checks"].values())
    assert lines[-len(manager_pool.CHECKS):] == [
        f"check {k} = {res['checks'][k]['value']} (limit {res['checks'][k]['limit']})"
        for k in manager_pool.CHECKS]
    info = json.loads(lines[-len(manager_pool.CHECKS) - 1][len("info "):])
    assert info["quota_exact"] == 0 and info["moved_pages"] > 0


def test_traced_run_reads_the_window():
    res, _ = bench_tiny.measure(trace=True)
    assert res["correct"] is True
    assert res["device"]["window_s"] > 0
    assert "device_ops" in res["breakdown"] and "idle_gaps" in res["breakdown"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("mix", ["growth", "arrivals"])
def test_control_in_lower_precision_is_not_correct(mix):
    cfg = bench_tiny.tiny_config()
    sched = build_schedule(cfg, bench_tiny.tiny_mix(mix), 2**31 + 5)
    limits = check.load_limits(bench_tiny.CELLS[mix], manager_pool.CHECKS)
    same = manager_pool.compare(sched, manager_pool.control_record(sched, 60, np.float32))
    assert all(same[k] == 0 for k in manager_pool.CHECKS) and same["quota_exact"] == 0
    low = manager_pool.compare(sched, manager_pool.control_record(sched, 60, ml_dtypes.bfloat16))
    assert not check.verdict(low, limits), low


def _stale_step(monkeypatch):
    """The tick returns the state it was given (copied before donation)."""
    from repro.core import policy

    step = policy.epoch_step

    def stale(state, params, **kw):
        keep = jax.tree.map(lambda a: a.copy(), state)
        _, plan, stats = step(state, params, **kw)
        return keep, plan, stats

    monkeypatch.setattr(policy, "epoch_step", stale)


def _half_batch(monkeypatch):
    """Half of each access report is left out."""
    from repro.core.manager import CentralManager

    record = CentralManager.record_access

    def half(self, counts):
        c = np.array(counts, copy=True)
        c[1::2] = 0
        record(self, c)

    monkeypatch.setattr(CentralManager, "record_access", half)


def _altered_move(monkeypatch):
    """``page_move`` alters one row it writes."""
    from repro.kernels import ops

    move = ops.page_move

    def altered(pool, src, dst):
        out = move(pool, src, dst)
        return out.at[dst[0]].add(1.0)

    monkeypatch.setattr(ops, "page_move", altered)


@pytest.mark.parametrize("mix", ["growth", "arrivals"])
@pytest.mark.parametrize("fault", [_stale_step, _half_batch, _altered_move])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault, mix):
    fault(monkeypatch)
    res, _ = bench_tiny.measure(mix=bench_tiny.tiny_mix(mix), cell=bench_tiny.CELLS[mix])
    assert res["correct"] is False, res["checks"]


def test_arrival_contents_written_wrong_are_not_correct(monkeypatch):
    """An arrival whose contents land altered in the pool."""
    from repro.core.dataplane import PagePool

    write = PagePool.write_pages

    def altered(self, page_ids, rows):
        rows = np.array(rows, copy=True)
        rows[len(rows) // 2, 0] += 1.0
        write(self, page_ids, rows)

    monkeypatch.setattr(PagePool, "write_pages", altered)
    # GUPS arrives in the warm-up and stays through the window, so its pages are read back
    mix = dict(bench_tiny.tiny_mix("arrivals"), warmup_epochs=6)
    mix["cycle"] = [dict(mix["cycle"][1], epochs=4), dict(mix["cycle"][0], epochs=100000)]
    res, _ = bench_tiny.measure(mix=mix, cell=bench_tiny.CELLS["arrivals"])
    assert res["correct"] is False and res["checks"]["byte_mismatches"]["value"] > 0, res["checks"]
