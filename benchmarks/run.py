"""Benchmark harness entry: one module per survey table/figure.
Prints ``name,us_per_call,derived`` CSV; with ``--json`` each module's
rows are also written to repo-root ``BENCH_<module>.json`` in the
repro-bench/v1 schema (same one benchmarks/hotpath.py uses), so every
benchmark contributes to the machine-readable perf trajectory.

Usage: python -m benchmarks.run [module] [--json]
"""
import sys

from benchmarks.common import write_bench_json


def main() -> None:
    from benchmarks import (table1_computing, fig3_topologies,
                            fig5_simulation, fig6_sync, hotpath, sec7_evolution, table2_features,
                            roofline)
    mods = [("table1_computing", table1_computing),
            ("fig3_topologies", fig3_topologies),
            ("fig5_simulation", fig5_simulation),
            ("fig6_sync", fig6_sync),
            ("hotpath", hotpath),
            ("sec7_evolution", sec7_evolution),
            ("table2_features", table2_features),
            ("roofline", roofline)]
    args = [a for a in sys.argv[1:]]
    json_mode = "--json" in args
    args = [a for a in args if a != "--json"]
    only = args[0] if args else None
    print("name,us_per_call,derived")
    for name, mod in mods:
        if only and only != name:
            continue
        try:
            rows = mod.run()
            if json_mode and rows:
                write_bench_json(name, rows)
        except Exception as e:  # keep the harness running
            print(f"{name}/ERROR,,{type(e).__name__}: {e}")


if __name__ == '__main__':
    main()
