"""Benchmark runner — one function per paper table.

    PYTHONPATH=src python -m benchmarks.run [--only table1,...]

Prints ``name,us_per_call,derived`` CSV (harness contract). Set
``BENCH_FAST=1`` for a reduced-budget pass. The ``kernels`` suite also
writes ``benchmarks/artifacts/BENCH_decode.json`` — the machine-readable
decode-perf trajectory (tokens/s + HBM-bytes/step per serving variant,
flash-decode cur_len scaling) — and the ``serve`` suite writes
``benchmarks/artifacts/BENCH_serve.json`` (engine-level linear vs paged
cache throughput/memory under a fixed mixed-length trace); CI uploads both
per commit.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import common


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--faults", action="store_true",
                    help="serve suite only: run the fault-injected "
                         "degraded-mode row (half pool + allocator "
                         "brown-out) instead of the full serving matrix")
    ap.add_argument("--prefix", action="store_true",
                    help="serve suite only: run the shared-system-prompt "
                         "prefix-cache trace instead of the full serving "
                         "matrix")
    args = ap.parse_args()

    from benchmarks import (fig3_loss_curves, kernel_bench, kv_cache_ppl,
                            roofline_report, serve_bench, table1_weight_only,
                            table3_w4a4, table4_precision, table5_stability,
                            table6_gradual_mask)
    if args.faults:
        serve_bench.FAULTS_ONLY = True
    if args.prefix:
        serve_bench.PREFIX_ONLY = True
    suites = {
        "table1": table1_weight_only.run,
        "table3": table3_w4a4.run,
        "table4": table4_precision.run,
        "table5": table5_stability.run,
        "table6": table6_gradual_mask.run,
        "fig3": fig3_loss_curves.run,
        "roofline": roofline_report.run,
        "kernels": kernel_bench.run,
        "serve": serve_bench.run,
        "kvppl": kv_cache_ppl.run,
    }
    wanted = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failed = 0
    for name in wanted:
        try:
            common.emit(suites[name]())
        except Exception as e:
            failed += 1
            print(f"{name},0,ERROR:{e!r}", file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
