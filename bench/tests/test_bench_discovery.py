"""The harness finds every part of a cell by name, and a new configuration,
traffic mix and per-layer metric are taken as new files plus new entries,
with no file that is there edited."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np

import bench_tiny
from bench import check, run
from bench.generator import load_json


def test_every_entry_resolves():
    bench = run.load_benchmark()
    assert bench["command"] == ["python3", "bench/run.py"] and bench["paths"] == ["bench"]
    for cfg in bench["configs"]:
        doc = json.loads((bench_tiny.ROOT / cfg["file"]).read_text())
        assert doc["name"] == cfg["name"] and sorted(doc["reduced"]) == sorted(cfg["reduced"])
        assert (bench_tiny.ROOT / "bench" / "reference" / f"{doc['reference']}.py").is_file()
        assert callable(run.driver_of(doc).Cell)
        for t in doc["tenants"]:
            law = __import__(f"bench.laws.{t['access']['kind']}", fromlist=["weights"])
            assert callable(law.weights)
    names = {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["config"] in names
        load_json("traffic", cell["traffic"])
        check.load_limits(cell["name"], run.driver_of(load_json("configs", cell["config"])).CHECKS)
        for trace in (False, True):
            for m in run.cell_metrics(bench, cell["name"], trace):
                assert callable(__import__(f"bench.metrics.{m['name']}", fromlist=["read"]).read)
    assert {m["name"] for m in run.cell_metrics(bench, "paper_fig8.growth", False)} >= {
        "machine_epochs_per_s", "setup_s"}


def test_new_files_need_no_edit(tmp_path):
    """A new configuration with other manager knobs, a traffic mix, an access
    law and a per-layer metric, as new files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(bench_tiny.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = run.load_benchmark()
    cfg = bench_tiny.tiny_config()
    cfg["name"] = "tiny_colo"
    cfg["manager"].update(ewma_lambda=0.25, hysteresis=0.05, num_bins=5)
    cfg["tenants"][2]["access"] = {"kind": "two_level", "first_half_share": 0.8}
    (root / "bench" / "configs" / "tiny_colo.json").write_text(json.dumps(cfg))
    (root / "bench" / "laws" / "two_level.py").write_text(textwrap.dedent("""
        import numpy as np

        def weights(n, law, perm):
            w = np.empty(n)
            w[perm[: n // 2]] = law["first_half_share"] / (n // 2)
            w[perm[n // 2:]] = (1 - law["first_half_share"]) / (n - n // 2)
            return w
    """))
    mix = dict(load_json("traffic", "arrivals"), warmup_epochs=3)
    (root / "bench" / "traffic" / "calm.json").write_text(json.dumps(mix))
    (root / "bench" / "checks" / "tiny_colo.calm.json").write_text(
        (bench_tiny.ROOT / "bench" / "checks" / "paper_fig8.arrivals.json").read_text())
    (root / "bench" / "metrics" / "epochs_seen.py").write_text(
        "def read(run):\n    return float(run.window['completed'])\n")
    bench["configs"].append({"name": "tiny_colo", "source": "test", "file": "bench/configs/tiny_colo.json",
                             "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": "tiny_colo.calm", "config": "tiny_colo", "traffic": "calm",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "epochs_seen", "unit": "epochs", "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "machine_epochs_per_s", "workloads": ["tiny_colo.calm"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = textwrap.dedent(f"""
        import json, sys, time, types
        sys.path[:0] = [{str(root)!r}, {str(bench_tiny.ROOT / 'src')!r}]
        from bench import check, run
        from bench.generator import load_json
        assert run.ROOT.resolve() == __import__('pathlib').Path({str(root)!r}).resolve()
        b = run.load_benchmark()
        cell = run.find(b["workloads"], "tiny_colo.calm", "workload")
        metrics = run.cell_metrics(b, cell["name"], True)
        assert "epochs_seen" in [m["name"] for m in metrics]
        cfg = load_json("configs", cell["config"])
        res = run.measure(cell["name"], cfg, load_json("traffic", cell["traffic"]), metrics, 5, 0.5,
                          False, time.time(), log=lambda m: None)
        drv = run.driver_of(cfg)
        ref = drv.make_reference(cfg)
        mod = __import__("bench.metrics.epochs_seen", fromlist=["read"])
        seen = mod.read(types.SimpleNamespace(window={{"completed": res["attempted"]}}))
        print(json.dumps({{"correct": res["correct"], "seen": seen, "lam": float(ref.lam),
                          "bins": ref.num_bins}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], cwd=root, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"correct": True, "seen": got["seen"], "lam": 0.25, "bins": 5} and got["seen"] > 0


def test_manager_entry_reaches_the_reference(monkeypatch):
    """The configuration's ``manager`` entry is what both sides run: a
    reference that kept its own default for one knob reads the run as wrong."""
    from bench.drivers import manager_pool

    cfg = bench_tiny.tiny_config()
    cfg["manager"]["ewma_lambda"] = 0.25
    res, _ = bench_tiny.measure(cfg, bench_tiny.tiny_mix("growth"))
    assert res["correct"] is True, res["checks"]
    make = manager_pool.make_reference

    def stale(c, ftype=np.float32):
        return make(dict(c, manager={k: v for k, v in c["manager"].items() if k != "ewma_lambda"}), ftype)

    monkeypatch.setattr(manager_pool, "make_reference", stale)
    res, _ = bench_tiny.measure(cfg, bench_tiny.tiny_mix("growth"))
    assert res["correct"] is False and res["checks"]["fmmr_gap"]["value"] > 0.01, res["checks"]


def test_no_result_without_the_system_under_test(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(bench_tiny.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "bench/run.py", "--workload", "paper_fig8.growth",
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
