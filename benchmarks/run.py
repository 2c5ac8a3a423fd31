"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]``

Prints human-readable tables plus ``name,us_per_call,derived`` CSV lines at
the end (the CSV contract of the repo scaffold).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the big datasets (NE, RE)")
    ap.add_argument("--only", default=None,
                    help="comma list: v,vi,vii,viii,overheads,kernels")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.fast:
        import benchmarks.common as common
        common.DSETS = [d for d in common.DSETS if d not in ("NE", "RE")]

    which = set((args.only or "v,vi,vii,viii,overheads,kernels").split(","))
    csv: list[str] = []
    t0 = time.time()

    from benchmarks import (kernel_bench, overheads, table_v_flops,
                            table_vi_latency, table_vii_heterogeneity,
                            table_viii_scaling)

    if "kernels" in which:
        kernel_bench.run(csv)
    if "v" in which:
        table_v_flops.run(csv)
    if "vi" in which:
        table_vi_latency.run(csv)
    if "vii" in which:
        table_vii_heterogeneity.run(csv)
    if "viii" in which:
        table_viii_scaling.run(csv)
    if "overheads" in which:
        overheads.run(csv)

    print(f"\n[benchmarks done in {time.time() - t0:.1f}s]")
    print("\nname,us_per_call,derived")
    for line in csv:
        print(line)


if __name__ == "__main__":
    main()
