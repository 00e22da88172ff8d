"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # smoke scale
    PYTHONPATH=src python -m benchmarks.run --full     # paper scale
    PYTHONPATH=src python -m benchmarks.run --only gram,table1

Prints ``name,...,derived`` CSV rows (assignment format) and writes
experiments/bench_results.csv + the Table-1 speedup summary.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from benchmarks import common, grass_bench, roofline_table, sketch_tasks, speedup_table
from benchmarks import theory_validation
from repro.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale shapes")
    ap.add_argument("--only", default=None,
                    help="comma list: gram,ose,ridge,solve,ablation,table1,"
                         "grass,theory,roofline")
    args = ap.parse_args(argv)
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    # Shapes follow the paper's regime d >> k (§7: d = 16384..262144,
    # k <= 4096); out-of-regime d/k ~ 4 makes any sparse sketch pointless.
    if args.full:
        d, n = 65_536, 512
        k_values = (256, 1024, 4096)
        datasets = common.DATASETS
    else:
        d, n = 16_384, 128
        k_values = (256, 2048)
        datasets = ("gaussian", "llm_weights")
    families = common.default_families()

    all_rows = []
    print(common.CSV_HEADER)

    def emit(rows):
        for r in rows:
            line = r.csv() if hasattr(r, "csv") else str(r)
            print(line)
            all_rows.append(r)

    t0 = time.time()
    if want("gram"):
        emit(sketch_tasks.gram_rows(d, n, k_values, families, datasets))
    if want("ose"):
        emit(sketch_tasks.ose_rows(d, n, k_values, families, datasets))
    if want("ridge"):
        emit(sketch_tasks.ridge_rows(d, n, k_values, families, datasets,
                                     task="ridge"))
    if want("solve"):
        emit(sketch_tasks.ridge_rows(d, n, k_values, families, datasets,
                                     task="solve"))
    if want("ablation"):
        emit(sketch_tasks.ablation_rows(d, n, k_values[0]))

    bench_rows = [r for r in all_rows if isinstance(r, common.BenchRow)]
    if want("table1") and bench_rows:
        table = speedup_table.speedup_table(bench_rows)
        headline = speedup_table.global_geomean_vs_next_best(table)
        print()
        print("## Table 1 — geomean speedups of FlashSketch(blockperm) "
              "vs baselines (measured-CPU× / modeled-TPU×)")
        print(speedup_table.format_markdown(table, headline))
        print()

    if want("theory"):
        for line in theory_validation.all_rows():
            print(line)
    if want("grass"):
        for line in grass_bench.grass_rows("full" if args.full else "smoke"):
            print(line)
    if want("roofline"):
        for line in roofline_table.csv_rows():
            print(line)

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/bench_results.csv", "w") as f:
        f.write(common.CSV_HEADER + "\n")
        for r in bench_rows:
            f.write(r.csv() + "\n")
    print(f"# done in {time.time()-t0:.1f}s; "
          f"{len(bench_rows)} rows -> experiments/bench_results.csv")


if __name__ == "__main__":
    main()
