"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  bench_kernel_decode   Fig. 8/9/10 (kernel speedups across settings)
  bench_e2e             Fig. 11/12  (end-to-end decode + serving throughput)
  bench_accuracy        Table I     (bits vs fidelity/throughput)
  bench_quant_overhead  Table II + Fig. 13 (quant/pack overhead, residual)
  bench_blocksweep      Table III   (parallelization granularity sweep)
  bench_breakdown       Table IV    (optimization breakdown)
  bench_roofline        §Roofline table from dry-run artifacts
  bench_serve           Offered-load serving sweep (paged engine; BENCH_serve.json)
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_accuracy, bench_blocksweep, bench_breakdown,
                            bench_e2e, bench_flash_prefill,
                            bench_kernel_decode, bench_paged,
                            bench_quant_overhead, bench_roofline, bench_serve)

    print("name,us_per_call,derived")
    failed = []
    for mod in (bench_kernel_decode, bench_paged, bench_flash_prefill,
                bench_accuracy, bench_quant_overhead, bench_blocksweep,
                bench_breakdown, bench_e2e, bench_serve, bench_roofline):
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            failed.append((mod.__name__, e))
            traceback.print_exc(limit=3, file=sys.stderr)
    if failed:
        for name, e in failed:
            print(f"{name},nan,FAILED:{e!r}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
