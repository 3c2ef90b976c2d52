"""Benchmark driver: one function per paper table/figure + the roofline
aggregation. Prints a readable report and overwrites the schema'd
``benchmarks/BENCH_*.json`` perf trajectories in place (the committed,
PR-over-PR diffable record; the old catch-all ``results.json`` scratch
file is gone).

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig5 area  # subset
    PYTHONPATH=src python -m benchmarks.run manager --predictive
                           # only the gated predictive-SLO rows (CI smoke)
    PYTHONPATH=src python -m benchmarks.run manager --adversarial
                           # only the gated quiet-vs-attack isolation rows
                           # (CI smoke; records the attack trace artifact)
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

from benchmarks.fabric_bench import bench_fabric
from benchmarks.manager_bench import (bench_manager,
                                      bench_manager_adversarial,
                                      bench_manager_predictive)
from benchmarks.moe_bench import bench_moe
from benchmarks.paper_tables import (bench_area, bench_bandwidth_allocation,
                                     bench_fig5_elasticity,
                                     bench_fig6_scaling, bench_kernels_cpu,
                                     bench_latency)
from benchmarks.roofline_bench import bench_roofline
from benchmarks.serve_bench import bench_serve

BENCHES = {
    "fig5": ("Fig 5 — §V-C elasticity use case", bench_fig5_elasticity),
    "bandwidth": ("§V-D — dynamic bandwidth allocation",
                  bench_bandwidth_allocation),
    "latency": ("§V-E — communication overhead", bench_latency),
    "fig6": ("Fig 6 — worst-case latency scaling", bench_fig6_scaling),
    "area": ("Tables I/II — area & power", bench_area),
    "kernels": ("kernel microbenchmarks (CPU)", bench_kernels_cpu),
    "fabric": ("repro.fabric — backend comparison", bench_fabric),
    "manager": ("repro.manager — closed-loop autoscaling scenarios",
                bench_manager),
    "moe": ("models.moe — dispatch impls incl. mesh expert parallelism",
            bench_moe),
    "roofline": ("§Roofline — dry-run aggregation", bench_roofline),
    "serve": ("repro.serve — steady-state decode fast path "
              "(plan cache on/off + reconfiguration storm)", bench_serve),
}

# Stable, machine-readable perf trajectory: one schema-versioned file per
# tracked bench, overwritten in place so successive PRs diff cleanly.
TRAJECTORY_FILES = {"fabric": "BENCH_fabric.json",
                    "manager": "BENCH_manager.json",
                    "moe": "BENCH_moe.json",
                    "serve": "BENCH_serve.json"}


def main(argv=None) -> int:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = list(argv if argv is not None else sys.argv[1:])
    predictive = "--predictive" in args
    if predictive:
        args = [a for a in args if a != "--predictive"]
        BENCHES["manager"] = ("repro.manager — predictive-SLO gated rows "
                              "only (CI smoke)", bench_manager_predictive)
    if "--adversarial" in args:
        args = [a for a in args if a != "--adversarial"]
        BENCHES["manager"] = ("repro.manager — quiet-vs-attack isolation "
                              "rows only (CI smoke)",
                              bench_manager_adversarial)
    names = args or list(BENCHES)
    results = {}
    failures = []
    for name in names:
        title, fn = BENCHES[name]
        print(f"\n=== {name}: {title} " + "=" * max(0, 50 - len(title)))
        try:
            rows, claims = fn()
        except Exception as e:              # keep the report going
            failures.append((name, repr(e)))
            print(f"  FAILED: {e!r}")
            continue
        for row in rows[:50]:
            print("  " + ",".join(f"{k}={v}" for k, v in row.items()))
        if len(rows) > 50:
            print(f"  ... ({len(rows)} rows total)")
        print("  claims: " + json.dumps(claims))
        results[name] = {"rows": rows, "claims": claims}

    for name, fname in TRAJECTORY_FILES.items():
        if name not in results:
            continue
        traj = Path(__file__).resolve().parent / fname
        traj.write_text(json.dumps(
            {"schema": 1, "bench": name, **results[name]},
            indent=1, default=str, sort_keys=True))
        print(f"wrote {traj}")
    if failures:
        print("FAILURES:", failures)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
