"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload cmax240-ref.backlog --seed 7 \
        --seconds 30 --trace 0

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its
per-layer metrics read from a profiler trace of the window; both decide
`correct` the same way. The last line of standard output is one JSON
object: correct, attempted, failed, metrics, device, (breakdown,) checks.
The last lines of standard error give each number compared with its
limit. Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.

Two options are for the one-time work of setting the benchmark up, not
for its runs: `--control 1` also reads the control of the comparison
(`bench/check.py`), `--rate` overrides an open-loop mix's rate, for
the sweep that found the rate the mix states, and `--keep-trace DIR`
keeps the profiler trace and its reduction (the recorded trace that
bench/tests/test_trace.py reads was kept so).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root (for `bench`) and the program's sources; the
# script's own directory is dropped so that no module of bench/ shadows
# one of the standard library
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.cell_spec(args.workload)
    if args.rate is not None:
        spec.mix = dict(spec.mix, rate_per_s=args.rate)
    harness.enable_compile_cache(ROOT)
    out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, control=bool(args.control),
                      keep_trace=args.keep_trace)
    print(f"correct = {out['correct']}", file=sys.stderr)
    for name, n in out["checks"].items():
        print(f"check {name} = {n['value']!r} (limit {n['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
