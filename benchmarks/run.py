"""Benchmark harness: one function per paper table/figure plus the
TPU-native suites. Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import bench_roofline, paper_tables, tpu_native
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    suites = (paper_tables.ALL + tpu_native.ALL + bench_roofline.ALL)
    print("name,us_per_call,derived")
    failures = 0
    for fn in suites:
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{fn.__name__},0.0,ERROR:{e}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
