"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived,compile_us`` CSV rows.  Steady-state
time (``us_per_call``) and one-off compile time are separate columns so
dispatch/compile overhead can't masquerade as compute (see
:mod:`benchmarks.timing`); modules that report no timing emit 0.0.

``python -m benchmarks.run --list`` prints every registered benchmark
with a one-line description; ``python -m benchmarks.run <tag>`` runs
just that one.
"""
import sys


def _modules():
    from . import (bench_core, bench_energy, bench_faults,
                   bench_multicluster, bench_resilience, bench_serving,
                   collectives_bench, fig4_random_delay, fig5_kernel_cdf,
                   fig6_kernel_colormap, fig7_5g_app, fig_placement,
                   fig_tuned_tree, fig_workload_tuned, roofline_table)
    return [("fig4", fig4_random_delay), ("fig5", fig5_kernel_cdf),
            ("fig6", fig6_kernel_colormap), ("fig7", fig7_5g_app),
            ("tuned", fig_tuned_tree),
            ("placement", fig_placement),
            ("workload", fig_workload_tuned),
            ("core", bench_core),
            ("multicluster", bench_multicluster),
            ("energy", bench_energy),
            ("collectives", collectives_bench),
            ("resilience", bench_resilience),
            ("faults", bench_faults),
            ("serving", bench_serving),
            ("roofline", roofline_table)]


def main() -> None:
    from repro import compile_cache
    only = sys.argv[1] if len(sys.argv) > 1 else None
    compile_cache.enable()
    mods = _modules()
    if only == "--list":
        for tag, mod in mods:
            desc = (mod.__doc__ or "").strip().splitlines()[0]
            print(f"{tag:14s} {desc}")
        return
    print("name,us_per_call,derived,compile_us")
    for tag, mod in mods:
        if only and tag != only:
            continue
        for row in mod.run():
            name, us, derived = row[:3]
            compile_us = row[3] if len(row) > 3 else 0.0
            print(f"{name},{us:.1f},{derived},{compile_us:.1f}", flush=True)


if __name__ == "__main__":
    main()
