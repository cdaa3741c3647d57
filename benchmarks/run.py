"""Benchmark harness entry point: one section per paper table/figure.

  paper_roofline    — Figs. 7-8 (analytic anatomy, A100/K100/v5e)
  bench_axhelm      — Figs. 9-10 (measured variant comparison)
  bench_contraction — §4.2 (contraction strategies)
  bench_nekbone     — Table 6 (end-to-end PCG + invariance check)
  roofline          — assignment §Roofline terms from the dry-run results

Prints CSV lines `name,...` per row.
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_axhelm, bench_contraction, bench_nekbone,
                            bench_paper_roofline, roofline)
    sections = [
        ("paper_roofline", bench_paper_roofline.main),
        ("bench_axhelm", bench_axhelm.main),
        ("bench_contraction", bench_contraction.main),
        ("bench_nekbone", bench_nekbone.main),
        ("roofline", roofline.main),
    ]
    failures = []
    for name, fn in sections:
        try:
            fn()
        except Exception:  # keep the harness running; report at the end
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED sections: {failures}")
        sys.exit(1)
    print("# all benchmark sections completed")


if __name__ == "__main__":
    from repro import compile_cache

    compile_cache.enable()
    main()
