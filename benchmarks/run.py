"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. Sections:
  paper reproduction (Table 1/2/4, Fig 2/4/5/6/8/9/10/11)
  kernels + wansync micro-benches
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import kernels_bench, paper_tables
    benches = [
        paper_tables.bench_table1,
        paper_tables.bench_table2,
        paper_tables.bench_fig2,
        paper_tables.bench_table4,
        paper_tables.bench_fig5,
        paper_tables.bench_fig6,
        paper_tables.bench_fig8,
        paper_tables.bench_fig9,
        paper_tables.bench_fig10,
        paper_tables.bench_fig11,
        paper_tables.bench_fig4_ml,
        kernels_bench.bench_kernels,
        kernels_bench.bench_wansync_model,
    ]
    print("name,us_per_call,derived")
    for b in benches:
        t0 = time.time()
        try:
            rows = b()
        except Exception as e:  # keep the harness running
            print(f"{b.__name__},nan,ERROR {type(e).__name__}: {e}")
            continue
        for name, val, derived in rows:
            print(f"{name},{val:.4f},{derived}")
        sys.stderr.write(f"[bench] {b.__name__} done in "
                         f"{time.time() - t0:.1f}s\n")


if __name__ == "__main__":
    main()
