"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the exact paper claim it reproduces):

  fig3_*   GUPS single-process overhead + heat-gradient win   (Fig. 3)
  fig4_*   6-process dynamic-QoS timeline                     (Fig. 4)
  fig5_7_* FlexKVS colocation latency/throughput vs baselines (Fig. 5/6/7)
  fig8_*   dynamically changing workload mix                  (Fig. 8)
  fig9/10_* migration-rate + epoch-duration sensitivity       (Fig. 9/10)
  engine_qos_* tiering benefit on the REAL serving stack      (beyond paper)
  serving_colo_* LS p99 under maxmem / static / fixed         (beyond paper)
  roofline_* 40-cell dry-run roofline table                   (scale deliverable)

These rows re-stage the paper's figures in the simulator and on a
smoke-sized serving engine; their times are not chip measurements. The chip
benchmark is ``bench/run.py`` (``BENCHMARK.json``).
"""
import sys
import time


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (
        dynamic_workload,
        engine_qos,
        gups_colocation,
        gups_single,
        kvs_colocation,
        param_sensitivity,
        roofline,
        serving_colocation,
    )

    sections = [
        ("fig3", gups_single),
        ("fig4", gups_colocation),
        ("fig5_7", kvs_colocation),
        ("fig8", dynamic_workload),
        ("fig9_10", param_sensitivity),
        ("engine_qos", engine_qos),
        ("serving_colo", serving_colocation),
        ("roofline", roofline),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in sections:
        t0 = time.time()
        try:
            rows = mod.run()
            rows.print()
            print(f"section_{name}_wall_s,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"section_{name}_FAILED,0,{e!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
