"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (for perf rows the middle
column is the relevant scalar; derived carries the paper-claim context).

  PYTHONPATH=src python -m benchmarks.run [--only fig8,tco,...]
"""
import argparse
import sys
import traceback

MODULES = [
    ("fig4_scaleout", "benchmarks.bench_scaleout"),
    ("fig5_throughput", "benchmarks.bench_throughput"),
    ("fig7d_embedding_mgmt", "benchmarks.bench_embedding_mgmt"),
    ("fig8_scheduler", "benchmarks.bench_scheduler"),
    ("fig12_design_space", "benchmarks.bench_design_space"),
    ("fig13_tco", "benchmarks.bench_tco"),
    ("fig14_nmp", "benchmarks.bench_nmp"),
    ("fig11_elastic", "benchmarks.bench_elastic"),
    ("hot_row_cache", "benchmarks.bench_cache"),
    ("cluster_engine", "benchmarks.bench_cluster"),
    ("sla_traffic", "benchmarks.bench_sla"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    args = p.parse_args(argv)
    import importlib

    from repro import compile_cache
    compile_cache.enable()
    failures = 0
    for name, mod in MODULES:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===")
        try:
            importlib.import_module(mod).run()
        except Exception:
            failures += 1
            print(f"{name},nan,ERROR")
            traceback.print_exc()
    return failures


if __name__ == "__main__":
    sys.exit(main())
