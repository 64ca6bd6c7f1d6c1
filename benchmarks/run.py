"""Benchmark harness: one module per paper table/figure.
Prints ``name,value,derived`` CSV. See DESIGN.md §7 for the figure map."""
from __future__ import annotations

import argparse
import sys

from benchmarks import (bench_decode, bench_e2e, bench_forwarding,
                        bench_goodput, bench_kernels, bench_open_loop,
                        bench_pd_ratio, bench_prefill, bench_prefix_cache,
                        bench_recovery, bench_spec, bench_transfer)
from benchmarks.common import emit
from repro.launch.compile_cache import use_compile_cache

ALL = {
    "transfer": bench_transfer,       # Fig 4, 14c/d
    "forwarding": bench_forwarding,   # Fig 3b, 14a/b
    "pd_ratio": bench_pd_ratio,       # Fig 12, 13a
    "prefix": bench_prefix_cache,     # Fig 1b, 3a
    "e2e": bench_e2e,                 # 6.7x / 60% headline
    "decode": bench_decode,           # fused vs eager decode step
    "spec": bench_spec,               # fused speculative vs plain decode
    "prefill": bench_prefill,         # exact vs bucketed prefill compiles
    "recovery": bench_recovery,       # Fig 13b/c/d
    "kernels": bench_kernels,         # kernel microbench
    "open_loop": bench_open_loop,     # Poisson/tidal arrivals, TTFT/TPOT SLO
    "goodput": bench_goodput,         # autoscaler vs static SLO-goodput
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated subset")
    a = ap.parse_args(argv)
    use_compile_cache()
    picks = [s for s in a.only.split(",") if s] or list(ALL)
    print("name,value,derived")
    for name in picks:
        emit(ALL[name].run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
