"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Env:
  BENCH_FULL=1   paper-scale traces/episodes (slower)
  BENCH_ONLY=fig6,fig9  run a subset
Exits non-zero when any selected module raised (its ERROR row is still
printed and the remaining modules still run).
"""
import importlib
import os
import sys
import traceback

from repro.compile_cache import enable_compile_cache

MODULES = [
    ("engine_sweep", "benchmarks.bench_engine"),
    ("fig5_workloads", "benchmarks.bench_workloads"),
    ("fig6_execution_time", "benchmarks.bench_execution_time"),
    ("fig7_hops_util", "benchmarks.bench_hopcount_util"),
    ("fig8_opc", "benchmarks.bench_opc"),
    ("fig9_convergence", "benchmarks.bench_convergence"),
    ("fig10_migration", "benchmarks.bench_migration"),
    ("fig11_mesh_scaling", "benchmarks.bench_mesh_scaling"),
    ("fig12_multiprogram", "benchmarks.bench_multiprogram"),
    ("continual_stream", "benchmarks.bench_continual"),
    ("fleet", "benchmarks.bench_fleet"),
    ("serving", "benchmarks.bench_serving"),
    ("faults", "benchmarks.bench_faults"),
    ("topology_axis", "benchmarks.bench_topology"),
    ("epoch_kernel", "benchmarks.bench_epoch_kernel"),
    ("fig13_sensitivity", "benchmarks.bench_sensitivity"),
    ("fig14_energy", "benchmarks.bench_energy"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> int:
    """Run the selected modules; returns the number that failed."""
    only = os.environ.get("BENCH_ONLY")
    wanted = only.split(",") if only else None
    print("name,us_per_call,derived")
    failed = 0
    for tag, mod_name in MODULES:
        if wanted and not any(w in tag for w in wanted):
            continue
        try:
            mod = importlib.import_module(mod_name)
            mod.run()
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{tag}/ERROR,0,{type(e).__name__}", flush=True)
            failed += 1
    return failed


if __name__ == '__main__':
    enable_compile_cache()
    sys.exit(1 if main() else 0)
