"""Benchmark orchestrator: one function per paper table/figure plus the
kernel micro-benchmarks and the roofline summary.

Prints ``name,us_per_call,derived`` CSV.  Run:
  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import sys


def main() -> None:
    quick = "--quick" in sys.argv
    from benchmarks import engine_bench, fedar_figs, kernels_bench, roofline

    rows = []
    rows += fedar_figs.table1_trust_events()
    rows += fedar_figs.fig7_trust_trajectories()
    if not quick:
        rows += fedar_figs.fig6_batch_epoch()
        rows += fedar_figs.fig8_straggler_effect()
        rows += fedar_figs.selection_ablation()
        rows += fedar_figs.poisoning_defense()
    engine_rows, engine_summary = engine_bench.bench(quick=quick)
    # mesh-sharded scaling: CPU worker processes (the device flag precedes
    # jax), or in this process on an accelerator, which it already holds
    engine_devices = engine_bench.bench_devices(quick=quick)
    engine_defense = engine_bench.bench_defense(quick=quick)
    engine_scenario = engine_bench.bench_scenario(quick=quick)
    engine_gated = engine_bench.bench_gated(quick=quick)
    for n, modes in engine_bench.bench_gated_packed(quick=quick).items():
        engine_gated.setdefault(n, {}).update(modes)
    engine_bench.write_json(engine_summary, engine_devices, engine_defense,
                            engine_scenario, engine_gated)  # BENCH_engine.json
    rows += engine_rows
    rows += kernels_bench.bench()
    rows += roofline.rows()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
