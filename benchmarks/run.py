"""Benchmark harness: one module per paper table/figure + kernel bench.
Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.emit).

    PYTHONPATH=src:. python -m benchmarks.run [--only accuracy]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "accuracy", "convergence", "locality",
                             "energy", "kernels", "serving"])
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="serving suite: write the JSONL telemetry trace "
                         "(request spans + adaptation decisions) here")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace_out:
        os.environ["BENCH_SERVING_TRACE_OUT"] = args.trace_out

    from . import (accuracy, convergence, energy_latency, kernels, locality,
                   serving)
    suites = {
        "accuracy": accuracy.run,          # paper Table 1 + Fig. 3
        "convergence": convergence.run,    # paper Fig. 2
        "locality": locality.run,          # paper Tables 2-3
        "energy": energy_latency.run,      # paper Table 6 + §5.2
        "kernels": kernels.run,            # Pallas kernels + tile hillclimb
        "serving": serving.run,            # batched service throughput
    }
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        fn()
        print(f"suite_{name}_wall_s,{(time.time() - t0) * 1e6:.0f},done")


if __name__ == "__main__":
    main()
