"""Benchmark harness: one module per paper table / case study.

Prints ``name,us_per_call,derived`` CSV rows.  The roofline table (§Roofline,
from dry-run artifacts) is appended when artifacts exist.
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_beamsearch, bench_compile,
                            bench_complexity, bench_fragmentation,
                            bench_fusion, bench_overhead)

    suites = [
        ("Table 1 (complexity)", bench_complexity.run, 1.0),
        ("Table 2 (compile/iteration time)", bench_compile.run, 1e6),
        ("Table 3 (overhead)", bench_overhead.run, 1e6 / 100),
        ("Case 5.2.1 (beam search tape)", bench_beamsearch.run, 1.0),
        ("Case 5.2.2 (fragmentation)", bench_fragmentation.run, 1.0),
        ("Fusion (deferred backend)", bench_fusion.run, 1e6),
    ]
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    failures = 0
    for title, fn, scale in suites:
        print(f"# {title}")
        try:
            for name, val, derived in fn():
                print(f"{name},{val*scale:.2f},{derived}")
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
        print()

    # §Roofline summary from dry-run artifacts (if present)
    try:
        from benchmarks import roofline

        rows = [r for r in (roofline.roofline_row(c) for c in
                            roofline.load_cells("single_pod_16x16",
                                                "baseline")) if r]
        if rows:
            print("# Roofline (single pod, baseline) — "
                  "MFU@bound per live cell")
            for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
                print(f"roofline_{r['arch']}_{r['shape']},"
                      f"{r['bound_s']*1e6:.1f},"
                      f"MFU@bound={r['roofline_fraction']*100:.1f}% "
                      f"dominant={r['dominant']}")
    except Exception:  # noqa: BLE001
        failures += 1
        traceback.print_exc()

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
