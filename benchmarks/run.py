"""Benchmark orchestrator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring
for the exact paper claim it reproduces):

  fig3_*   GUPS single-process overhead + heat-gradient win   (Fig. 3)
  fig4_*   6-process dynamic-QoS timeline                     (Fig. 4)
  fig5_7_* FlexKVS colocation latency/throughput vs baselines (Fig. 5/6/7)
  fig8_*   dynamically changing workload mix                  (Fig. 8)
  fig9/10_* migration-rate + epoch-duration sensitivity       (Fig. 9/10)
  engine_qos_* tiering benefit on the REAL serving stack      (beyond paper)
  roofline_* 40-cell dry-run roofline table                   (scale deliverable)
  micro_*  host-side primitive timings

Also writes ``BENCH_policy.json`` (policy-engine epochs/sec + per-epoch µs,
single-step vs fused-scan, against the fixed seed baseline),
``BENCH_scenarios.json`` (the 256k-page dynamic colocation scenario across
all four policies: per-phase throughput/p99 curves, the paper's qualitative
ordering check, and the vectorized-vs-seed baseline epoch timings) and
``BENCH_fleet.json`` (the fleet-vectorized sweep engine: one vmapped
K-machine scan vs the serial per-machine drivers, engine-level and full
ScenarioSweep) and ``BENCH_serving.json`` (multi-tenant open-loop serving
colocation on the REAL engine: per-tenant p50/p99 step latency, throughput
and migrated bytes under maxmem vs static vs fixed-partition placement,
plus the gated LS-p99 claim row) and ``BENCH_autotune.json`` (committed
tuned policy profiles replayed against the paper defaults per scenario
family, the online SkewChange recovery race, and the autotuner search
canary) and ``BENCH_scale.json`` (the pages x tenants x machines
scaling sweep with fitted per-axis slopes and the 1M x 256 headline
epoch) so the perf trajectory is tracked across PRs. All payloads carry
a ``platform`` stamp for cross-host normalization in the perf gate.
"""
import json
import sys
import time


def write_policy_json(path: str = "BENCH_policy.json") -> None:
    from benchmarks import microbench

    with open(path, "w") as f:
        json.dump(microbench.policy_bench(), f, indent=2)
    print(f"wrote {path}")


def write_scale_json(path: str = "BENCH_scale.json", smoke: bool = False) -> None:
    """Scaling-curve payload: pages x tenants x machines sweeps with fitted
    per-axis log-log slopes, the 1M x 256 headline epoch, and the stacked
    fleet live-bytes (benchmarks/scale_bench.py, DESIGN.md §10)."""
    from benchmarks import scale_bench

    with open(path, "w") as f:
        json.dump(scale_bench.scale_bench(smoke=smoke), f, indent=2)
    print(f"wrote {path}")


def write_scenarios_json(path: str = "BENCH_scenarios.json", smoke: bool = False) -> None:
    from benchmarks import dynamic_workload

    with open(path, "w") as f:
        json.dump(dynamic_workload.scenarios_bench(smoke=smoke), f, indent=2)
    print(f"wrote {path}")


def write_fleet_json(path: str = "BENCH_fleet.json", smoke: bool = False) -> None:
    """Fleet engine + sweep payload: the vmapped K-machine scan against the
    serial per-machine drivers (engine level) and the full ScenarioSweep
    against the pre-fleet serial sweep loop (>= 4x headline claim)."""
    from benchmarks import dynamic_workload, microbench
    from benchmarks.common import platform_metadata

    payload = {
        "platform": platform_metadata(),
        # the smoke-scale engine section is what the CI perf gate
        # re-measures and tolerance-bands on its own (slower) host
        "engine_smoke": microbench.fleet_bench(
            n_machines=4, n_pages=4096, n_epochs=8
        ),
        "sweep": dynamic_workload.sweep_bench(smoke=smoke),
    }
    if not smoke:
        payload["engine"] = microbench.fleet_bench()
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path}")


def write_serving_json(path: str = "BENCH_serving.json", smoke: bool = False) -> None:
    """Multi-tenant serving colocation payload: the three placement legs
    (maxmem / static / fixed) on the real engine plus the gated LS-p99
    claim row (see benchmarks/serving_colocation.py)."""
    from benchmarks import serving_colocation

    with open(path, "w") as f:
        json.dump(serving_colocation.serving_bench(smoke=smoke), f, indent=2)
    print(f"wrote {path}")


def write_autotune_json(path: str = "BENCH_autotune.json", smoke: bool = False) -> None:
    """Autotuner claims payload: committed tuned profiles replayed against
    the paper defaults per scenario family, the online SkewChange recovery
    race, and the search-completeness canary (benchmarks/autotune_bench.py)."""
    from benchmarks import autotune_bench

    with open(path, "w") as f:
        json.dump(autotune_bench.autotune_bench(smoke=smoke), f, indent=2)
    print(f"wrote {path}")


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.enable()
    from benchmarks import (
        dynamic_workload,
        engine_qos,
        gups_colocation,
        gups_single,
        kvs_colocation,
        microbench,
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
        ("micro", microbench),
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
    try:
        write_policy_json()
    except Exception as e:
        failures += 1
        print(f"section_policy_json_FAILED,0,{e!r}")
    try:
        write_scenarios_json()
    except Exception as e:
        failures += 1
        print(f"section_scenarios_json_FAILED,0,{e!r}")
    try:
        write_fleet_json()
    except Exception as e:
        failures += 1
        print(f"section_fleet_json_FAILED,0,{e!r}")
    try:
        write_serving_json()
    except Exception as e:
        failures += 1
        print(f"section_serving_json_FAILED,0,{e!r}")
    try:
        write_autotune_json()
    except Exception as e:
        failures += 1
        print(f"section_autotune_json_FAILED,0,{e!r}")
    try:
        write_scale_json()
    except Exception as e:
        failures += 1
        print(f"section_scale_json_FAILED,0,{e!r}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
